"""One run of one cell: find the cell's files by the names in ``BENCHMARK.json``, look for
the chip, hand over to the configuration's window driver, and reduce the trace to the
cell's per-layer metrics through each metric's own reader."""
import contextlib
import importlib
import json
import os
import shutil
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def module(group, name):
    return importlib.import_module(f"chipbench.{group}.{name}")


def load_json(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def cell_of(workload):
    bench = load_json("BENCHMARK.json")
    cell = next((w for w in bench["workloads"] if w["name"] == workload), None)
    if cell is None:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    return bench, cell, load_json(conf["file"]), load_json("chipbench", "traffic", f"{cell['traffic']}.json")


def memory_peak():
    import jax
    stats = [d.memory_stats() for d in jax.local_devices()]
    return max((s or {}).get("peak_bytes_in_use", 0) for s in stats)


def trace_dir(ctx):
    return os.path.join(HERE, ".out", f"trace-{ctx['cell']['name']}")


def start_trace(ctx):
    import jax
    shutil.rmtree(trace_dir(ctx), ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir(ctx), profiler_options=opts)


def stop_trace(ctx):
    import jax
    jax.profiler.stop_trace()


@contextlib.contextmanager
def window_span(ctx):
    """The traced window: a ``cb.window`` span on the profiler's clock, and its two
    ends on the host's clock under ``ctx['traced']``."""
    import jax
    with jax.profiler.TraceAnnotation("cb.window"):
        ta = time.perf_counter()
        yield
        ctx["traced"] = (ta, time.perf_counter())


@contextlib.contextmanager
def traced(ctx):
    start_trace(ctx)
    try:
        with window_span(ctx):
            yield
    finally:
        stop_trace(ctx)


def chip(cell):
    """This machine's devices and their peaks, or no result: a cell runs on TPU chips, as
    many as it asks for, of a kind whose peaks the benchmark knows."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        raise SystemExit(f"{cell['name']} needs {cell['chips']} TPU chip(s); "
                         f"found {len(devices)} x {devices[0].platform}")
    peaks = load_json("chipbench", "peaks.json").get(devices[0].device_kind)
    if peaks is None:
        raise SystemExit(f"no peaks for device kind {devices[0].device_kind!r}")
    return devices, peaks


def read_metrics(names, view, workload):
    """Each per-layer metric through the reader that its own file names. A reader that
    finds nothing to read returns nothing and the metric is left out; a kernel's roofline
    may fall silent so, when the kernel leaves the path, but a metric marked ``required``
    (the whole step's time, share and gap) may not: then the trace is no longer being read."""
    values = {}
    for name in names:
        spec = load_json("chipbench", "metrics", f"{name}.json")
        v = module("readers", spec["reader"]).read(view, **spec.get("args", {}))
        if v is not None:
            values[name] = v
        elif spec.get("required"):
            raise RuntimeError(f"{name} found nothing to read in the trace of {workload}")
    return values


def measure(workload, seed, seconds, trace, t_start=None, require_chip=True, build=None):
    """Run the cell once and return the result line as a dict."""
    t_start = time.perf_counter() if t_start is None else t_start
    bench, cell, cfg, mix = cell_of(workload)
    if require_chip:
        devices, peaks = chip(cell)
    else:                                   # the tests' whole runs at a tiny size
        import jax
        devices, peaks = jax.devices(), None
    ctx = {"cell": cell, "cfg": cfg, "mix": mix, "seed": int(seed), "seconds": float(seconds),
           "trace": bool(trace), "chips": cell["chips"], "t_start": t_start, "build": build}
    res = module("drivers", cfg["driver"]).run(ctx)

    names = [m["name"] for m in bench["per_layer" if trace else "end_to_end"]
             if workload in m.get("workloads", [workload])]
    units = {m["name"]: m["unit"] for m in bench["per_layer"] + bench["end_to_end"]}
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": cell["chips"], "memory_peak_bytes": res["memory_peak_bytes"]}
    out = {"correct": all(v <= limit for v, limit in res["checks"].values()),
           "attempted": res["attempted"], "failed": res["failed"]}
    if trace:
        from chipbench import trace as tr
        t = tr.read(trace_dir(ctx), cfg["programs"])
        shutil.rmtree(trace_dir(ctx), ignore_errors=True)
        lo, hi = t["spans"]["cb.window"][0]
        view = {"trace": t, "window": (lo, hi), "host_window": ctx["traced"], "cfg": cfg,
                "mix": mix, "peaks": peaks, "chips": cell["chips"], "records": res["records"],
                "counts": module("counts", cfg["counts"])}
        values = read_metrics(names, view, workload)
        busy = [tr.busy_seconds(d, lo, hi) for d in t["devices"]]
        device["busy_s"], device["window_s"] = sum(busy) / len(busy), hi - lo
        worst = t["devices"][busy.index(min(busy))]
    else:
        values = {n: res["end_to_end"][n] for n in names if n in res["end_to_end"]}
    out["metrics"] = {n: {"value": v, "unit": units[n]} for n, v in values.items()}
    out["device"] = device
    if trace:
        out["breakdown"] = tr.breakdown(worst, lo, hi)
    out["checks"] = {k: {"value": v, "limit": limit} for k, (v, limit) in res["checks"].items()}
    return out
