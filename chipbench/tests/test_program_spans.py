"""Tests of the readers of the program's own spans (PR 26), on views made by hand:
``JAX_PLATFORMS=cpu python -m pytest chipbench/tests -q``. No number here is a device's."""
import numpy as np
import pytest

from chipbench import harness

# -- the program's own spans, on a view made by hand -------------------------------------
MS = 1e-3
SKEW = -1.2 * MS        # add to a device time to get the host's: the device's lines run ahead


def engine_view(steps=6, admit_before=3, ring=True):
    """Device programs of 100 ms on the device's clock and the engine's spans round
    them on ``perf_counter``: an iteration is build 3 ms, dispatch 0.3 ms (the device
    starts 0.1 ms after it returns, under the next pull), pull until 0.3 ms after the device
    ends, emit 2 ms and 0.5 ms that no span covers. Iteration ``admit_before`` admits first (4 ms, with a program of
    its own), so the gap before it is not one between two decode steps."""
    ta, lo = 5000.0, 17.0                   # perf_counter and the profiler's clock
    host = lambda t: ta + t - lo            # noqa: E731
    mods, events, t = [], [], lo + 1 * MS
    for i in range(1, steps + 1):
        def span(name, dur, **args):
            nonlocal t
            events.append((host(t), host(t + dur), f"generate.{name}", dict(args, iter=i)))
            t += dur
        if i == admit_before:
            mods.append((t + 1 * MS - SKEW, t + 3 * MS - SKEW, "jit_fn(1)", "admit"))
            span("admit", 4 * MS, admitted=1)
        span("build", 3 * MS)
        start = t + 0.4 * MS
        mods.append((start - SKEW, start + 100 * MS - SKEW, "jit_fn(2)", "decode_step"))
        mods.append((start - SKEW - 0.2 * MS, start - SKEW - 0.1 * MS, "jit_fold(3)", "other"))
        span("dispatch", 0.3 * MS, program="decode_step")
        span("pull", start + 100.3 * MS - t)
        events.append((host(start - 1 * MS), host(t), "generate.decode_step",
                       {"iter": i, "live": 12 + i % 3, "bucket": 32,
                        "pool_live": 300 + 10 * i, "pool_usable": 384}))
        span("emit", 2 * MS, tokens=13, retired=0)
        t += 0.5 * MS
    view = {"trace": {"devices": [{"modules": sorted(mods)}]}, "window": (lo, t + 1 * MS),
            "host_window": (ta, host(t + 1 * MS)), "ring": events if ring else [],
            "records": {"t0": ta - 10.0, "t_end": host(t) + 10.0}}
    return view


def test_span_ms_and_span_attr_on_spans_made_by_hand():
    from chipbench.readers import span_attr, span_ms
    view = engine_view()
    assert span_ms.read(view, "generate.emit") == pytest.approx(2.0)
    assert span_ms.read(view, "generate.build", percentile=100) == pytest.approx(3.0)
    assert span_ms.read(view, "fit.batch") is None
    occ = span_attr.read(view, "generate.decode_step", "live", "bucket")
    assert occ == pytest.approx(100 * np.mean([12 + i % 3 for i in range(1, 7)]) / 32)
    assert span_attr.read(view, "generate.decode_step", "pool_live", "pool_usable", stat="max") \
        == pytest.approx(100 * 360 / 384)
    # the whole timed window, not the traced part of it: narrow the records and spans drop out
    view["records"] = {"t0": view["ring"][0][0], "t_end": view["ring"][0][0] + 0.25}
    assert span_attr.read(view, "generate.decode_step", "pool_live", "pool_usable", stat="max") \
        == pytest.approx(100 * 320 / 384)
    view["host_window"] = (0.0, 1.0)
    assert span_ms.read(view, "generate.emit") is None


NEW_GEN = ["decode_occupancy_share", "kv_pool_peak_share"]


def test_the_new_metrics_are_read_by_name_and_the_required_ones_may_not_fall_silent(monkeypatch):
    got = harness.read_metrics(NEW_GEN, engine_view(), "x")
    assert set(got) == set(NEW_GEN) and got["kv_pool_peak_share"] == pytest.approx(93.75)
    empty = engine_view(ring=False)         # a program with the clock and no generate.* span
    assert harness.read_metrics(["kv_pool_peak_share"], empty, "x") == {}
    with pytest.raises(RuntimeError, match="no generate.decode_step span"):
        harness.read_metrics(["decode_occupancy_share"], empty, "x")
    assert harness.read_metrics(["fit_host_ms_p50"], empty, "x") == {}
    # a program from before the one clock (the parent of PR 26) has nothing to place:
    # every new metric is left out, and none raises
    from deeplearning4j_tpu.common import telemetry
    monkeypatch.delattr(telemetry, "perf_counter_of")
    before = engine_view()
    del before["ring"]
    assert harness.read_metrics(NEW_GEN + ["fit_host_ms_p50"], before, "x") == {}


def test_the_ring_of_this_program_is_read_in_process():
    from chipbench.readers import ring, span_ms
    from deeplearning4j_tpu.common import telemetry
    import time
    ta = time.perf_counter()
    with telemetry.span("fit.batch", iter=0):
        time.sleep(0.002)
    view = {"window": (100.0, 101.0), "host_window": (ta, time.perf_counter())}
    assert 2.0 <= span_ms.read(view, "fit.batch") < 50.0
    (s, e, args), = ring.to_profiler(view, ring.inside(view, "fit.batch", view["host_window"]))
    assert 100.0 <= s < e <= 100.0 + (view["host_window"][1] - ta) and args == {"iter": 0}
