#!/usr/bin/env python
"""Where does a generation cell lose time to the host? One window of the cell without
its plain reference (``python scripts/probe_generate_stalls.py <workload> <seed>
<seconds>``, on the chip through the chip tool), then the engine's own spans beside a
heartbeat thread, the collector's callbacks and the cgroup's throttle counters: every
``generate.pull`` / ``dispatch`` / ``build`` / ``emit`` a good deal longer than a step,
with its time in the window, and every beat of a 1 ms sleeper that came over 20 ms late.
A stall that the heartbeat shares is the whole process standing still (PR 28: 110 ms,
0 to 5 times in 35 s, no collection at the time); ``PROBE_VARIANT=nogc`` freezes and
switches off the collector for the window. The last line is JSON; nothing in it is a
benchmark's number."""
import gc
import json
import os
import sys
import threading
import time

T0 = time.perf_counter()
sys.path.insert(0, os.getcwd())
variant = os.environ.get("PROBE_VARIANT", "base")
workload, seed, seconds = sys.argv[1], int(sys.argv[2]), float(sys.argv[3])

import numpy as np  # noqa: E402
from chipbench import harness  # noqa: E402
from chipbench.drivers import generate as drv  # noqa: E402
from deeplearning4j_tpu.common import telemetry  # noqa: E402

drv.numbers = lambda *a, **k: {}            # no reference pass: the timing is the point


def read(path):
    try:
        return open(path).read()
    except OSError:
        return None


def cg():
    return {p: read(p) for p in ("/sys/fs/cgroup/cpu.stat", "/sys/fs/cgroup/cpu/cpu.stat",
                                 "/sys/fs/cgroup/cpu.max", "/proc/pressure/cpu")}


beats, collections, stop = [], [], False


def heart():
    last = time.perf_counter()
    while not stop:
        time.sleep(0.001)
        now = time.perf_counter()
        if now - last > 0.02:
            beats.append((last, now - last))
        last = now


def on_gc(phase, info, _t=[0.0]):
    if phase == "start":
        _t[0] = time.perf_counter()
    else:
        d = time.perf_counter() - _t[0]
        if info["generation"] >= 2 or d > 0.005:
            collections.append((_t[0], d, info["generation"], info["collected"]))


bench, cell, cfg, mix = harness.cell_of(workload)
base_build = harness.module("models", cfg["builder"]).build


def build(cfg, mix, seed, chips):
    prog = base_build(cfg, mix, seed, chips)
    if variant == "nogc":
        gc.collect()
        gc.freeze()
        gc.disable()
    gc.callbacks.append(on_gc)
    threading.Thread(target=heart, daemon=True).start()
    return prog


before = cg()
ctx = {"cell": cell, "cfg": cfg, "mix": mix, "seed": seed, "seconds": seconds, "trace": False,
       "chips": 1, "t_start": T0, "build": build}
res = drv.run(ctx)
stop = True
after = cg()
t0, t_end = res["records"]["t0"], res["records"]["t_end"]
ev = [e for e in telemetry.trace_events() if e["ph"] == "X" and e["name"].startswith("generate.")]
to_s = lambda us: telemetry.perf_counter_of(us) if hasattr(telemetry, "perf_counter_of") else us / 1e6  # noqa: E731
by = {}
for e in ev:
    s = to_s(e["ts"])
    if t0 <= s <= t_end:
        by.setdefault(e["name"], []).append((s - t0, e["dur"] / 1e3, e["args"]))
out = {"variant": variant, "workload": workload, "seed": seed, "e2e": res["end_to_end"],
       "attempted": res["attempted"], "failed": res["failed"]}
for name, v in sorted(by.items()):
    d = np.array([x[1] for x in v])
    out[name] = {"n": len(d), "p50": float(np.percentile(d, 50)), "p99": float(np.percentile(d, 99)),
                 "max": float(d.max()), "sum_s": float(d.sum() / 1e3)}
steps = by.get("generate.decode_step", [])
step_ms = float(np.percentile([x[1] for x in steps], 50)) if steps else 0.0
for name in ("generate.pull", "generate.dispatch", "generate.build", "generate.emit"):
    out[name + ".long"] = [(round(s, 3), round(d, 1)) for s, d, _ in by.get(name, []) if d > step_ms + 15]
out["prefill.by_bucket"] = {}
for s, d, a in by.get("generate.prefill", []):
    out["prefill.by_bucket"].setdefault(a.get("bucket"), []).append(d)
out["prefill.by_bucket"] = {k: [len(v), round(float(np.percentile(v, 50)), 2), round(max(v), 2)]
                            for k, v in out["prefill.by_bucket"].items()}
out["heartbeat.late"] = [(round(s - t0, 3), round(d * 1e3, 1)) for s, d in beats if t0 <= s <= t_end]
out["gc"] = [(round(s - t0, 3), round(d * 1e3, 1), gen, n) for s, d, gen, n in collections if t0 <= s <= t_end]
out["gc.counts"] = gc.get_stats()
out["cgroup.before"], out["cgroup.after"] = before, after
out["ncpu"] = os.cpu_count()
out["affinity"] = len(os.sched_getaffinity(0))
print(json.dumps(out))
