"""Tests of the readers of an admission's records (PR 36), on views made by hand:
``JAX_PLATFORMS=cpu python -m pytest chipbench/tests -q``. No number here is a device's."""
import json
import os

import numpy as np
import pytest

from chipbench import harness

MS = 1e-3
SKEW = -1.2 * MS        # add to a device time to get the host's: the device's lines run ahead


def admission_view(skew=SKEW, ring=True, parent=False, prefills=1):
    """Three admissions between decode steps of 10 ms, on the device's clock, and the
    engine's records of them on ``perf_counter``. At an admission the step in flight ends,
    the device idles 1.4 ms, then runs a request's three programs (prefill 4 ms, commit
    0.8 ms with a hole of 0.1 ms in it, sample 0.65 ms) back to back, idles 0.7 ms before
    the next request's three where the admission took ``prefills`` of them, and 5.2 ms
    after the last sample until the step built afresh starts. A key fold of kind ``other``
    runs 10 us in the idle before. ``parent`` is the ring of a program from before
    ``generate.stall``; with ``ring`` false the program has no records at all."""
    ta, lo = 7000.0, 31.0
    host = lambda t: ta + t - lo            # noqa: E731
    dev = lambda t: t - skew                # noqa: E731
    mods, ops, events, t, it = [], [], [], lo + 2 * MS, 0

    def program(start, dur, name, kind, hole=0.0):
        mods.append((dev(start), dev(start + dur), name, kind))
        half = (dur - hole) / 2
        ops.append((dev(start), dev(start + half), "%fusion.1 = f32[] fusion()"))
        ops.append((dev(start + half + hole), dev(start + dur), "%fusion.2 = f32[] fusion()"))

    def step():
        nonlocal t, it
        it += 1
        events.append((host(t), host(t + 0.5 * MS), "generate.dispatch",
                       {"iter": it, "program": "decode_step", "parent": "generate.decode_step"}))
        program(t + 0.7 * MS, 10 * MS, "jit_fn(2)", "decode_step")
        t += 10.7 * MS
        events.append((host(t), host(t + 0.3 * MS), "generate.emit",
                       {"iter": it, "tokens": 8, "retired": 0}))
        t += 0.3 * MS

    for _ in range(3):
        for _ in range(3):
            step()
        it += 1
        t0 = t                              # the step in flight ended 0.3 ms ago
        program(t0 + 0.5 * MS, 0.01 * MS, "jit_fold(3)", "other")
        p = t0 + 1.11 * MS
        for _ in range(prefills):
            events.append((host(p - 1 * MS), host(p + 6 * MS), "generate.prefill",
                           {"iter": it, "tokens": 40, "bucket": 64, "parent": "generate.admit"}))
            program(p, 4 * MS, "jit_fn(5)", "admit")
            program(p + 4 * MS, 0.8 * MS, "jit_fn(6)", "admit", hole=0.1 * MS)
            program(p + 4.8 * MS, 0.65 * MS, "jit_sample_logits(7)", "sample")
            p += 5.45 * MS + 0.7 * MS
        if not parent:
            events.append((host(t0 - 0.3 * MS), host(p + 16 * MS), "generate.stall",
                           {"iter": it + 5, "rows": 3, "prefills": prefills,
                            "prompt_tokens": 40 * prefills, "model": "m"}))
        t = p - 0.7 * MS + 5.2 * MS - 0.7 * MS      # the next step starts 0.7 ms after t
    for _ in range(3):
        step()
    return {"trace": {"devices": [{"modules": sorted(mods), "ops": sorted(ops)}]},
            "window": (lo, t + 1 * MS), "host_window": (ta, host(t + 1 * MS)),
            "ring": events if ring else [], "records": {"t0": ta - 1.0, "t_end": host(t) + 1.0}}


def idle_of(view, percentile=50):
    from chipbench.readers import admit_idle
    return {p: admit_idle.read(view, "decode_step", p, percentile)
            for p in ("total", "before", "within", "after")}


def test_admit_idle_splits_a_clusters_idle_by_the_devices_own_edges(capsys):
    from chipbench.readers import admit_idle
    got = idle_of(admission_view())
    # the hole in the commit is the cluster's, and lies within it
    assert got == {"total": pytest.approx(6.7), "before": pytest.approx(1.4),
                   "within": pytest.approx(0.1), "after": pytest.approx(5.2)}
    err = capsys.readouterr().err
    assert err.count("admit_idle decode_step") == 1     # worked out once, read four times
    assert "3 admissions of 9 programs idle 0.0201 s of the window's 0.0" in err
    assert "before 0.0042, within 0.0003, after 0.0156 s" in err
    view = admission_view()
    assert admit_idle.read(view, "no_such_kind") is None
    assert admit_idle.read(view, "decode_step", "no_such_phase") is None


def test_admit_idle_counts_the_idle_between_two_requests_of_one_admission_within():
    got = idle_of(admission_view(prefills=3))
    assert got == {"total": pytest.approx(6.7 + 2 * 0.8), "before": pytest.approx(1.4),
                   "within": pytest.approx(0.3 + 2 * 0.7), "after": pytest.approx(5.2)}
    assert sum(v for p, v in got.items() if p != "total") == pytest.approx(got["total"])


def test_admit_idle_sums_the_gaps_round_each_cluster_as_a_walk_over_all_of_them():
    """Thousands of short operations, hundreds of gaps: the reader looks only at the gaps
    near a cluster and reads what a sum over every gap of the window reads."""
    from chipbench import trace as tr
    from chipbench.readers import admit_idle
    view = admission_view(prefills=2)
    dev = view["trace"]["devices"][0]
    lo, hi = view["window"]
    rs = np.random.RandomState(7)
    starts = np.sort(rs.uniform(lo, hi, 4000))
    dev["ops"] = sorted(dev["ops"] + [(s, s + d, "%copy.1 = f32[] copy()") for s, d
                                      in zip(starts, rs.uniform(1e-6, 2e-5, starts.size))])
    busy = tr.union(dev["ops"], lo, hi)
    idle = [(a[1], b[0]) for a, b in zip([[lo, lo]] + busy, busy + [[hi, hi]]) if b[0] > a[1]]
    walk = lambda a, b: sum(max(0.0, min(e, b) - max(s, a)) for s, e in idle)  # noqa: E731
    want = {"total": [], "before": [], "after": []}
    for a_end, b_start, mods in admit_idle.clusters(dev, "decode_step", lo, hi):
        want["total"].append(walk(a_end, b_start))
        want["before"].append(walk(a_end, mods[0][0]))
        want["after"].append(walk(max(m[1] for m in mods), b_start))
    got = admit_idle.split(view, "decode_step")
    assert len(idle) > 500 and len(want["total"]) == 3
    assert {p: got[p] for p in want} == want


@pytest.mark.parametrize("skew", [SKEW, 0.0, 3.3 * MS])
@pytest.mark.parametrize("how", ["parent", "no_ring"])
def test_admit_idle_needs_no_record_of_the_program_and_no_clock_but_the_devices(skew, how):
    """The parent's program, and one with no ring at all, read what the change reads:
    the skew between the clocks is nothing to this reader."""
    view = admission_view(skew=skew, parent=how == "parent", ring=how != "no_ring")
    assert idle_of(view) == idle_of(admission_view())


def test_admit_idle_reads_only_the_clusters_whole_inside_the_window():
    view = admission_view()
    first = [m for m in view["trace"]["devices"][0]["modules"] if m[3] == "admit"][0]
    view["window"] = (first[0] + 1 * MS, view["window"][1])     # cuts the first cluster
    assert idle_of(view) == idle_of(admission_view())           # p50 of two as of three
    view = admission_view()
    view["window"] = (view["window"][0], first[0] - 1 * MS)     # before any admission
    assert idle_of(view) == dict.fromkeys(("total", "before", "within", "after"))


def test_admit_stall_reads_the_whole_window(capsys):
    from chipbench.readers import admit_stall
    view = admission_view(prefills=2)
    assert admit_stall.read(view, "ms") == pytest.approx(0.3 + 1.11 + 2 * 6.15 + 16)
    assert admit_stall.read(view, "ms", percentile=95) == pytest.approx(admit_stall.read(view, "ms"))
    # three episodes of three rows over the twelve emits' 8 tokens each
    assert admit_stall.read(view, "gap_share") == pytest.approx(100 * 9 / 96)
    assert capsys.readouterr().err == ("admit_stall: 3 episodes of 6 prefills, 240 prompt tokens; "
                                       "9 of 96 inter-token gaps crossed one\n")
    view = admission_view()
    at = [e[2] for e in view["ring"]].index("generate.stall")
    view["ring"][at] = (view["ring"][0][0], view["ring"][0][0] + 0.012, "generate.stall",
                        dict(view["ring"][at][3], rows=1))
    assert admit_stall.read(view, "ms", percentile=0) == pytest.approx(12.0)
    assert admit_stall.read(view, "gap_share") == pytest.approx(100 * 7 / 96)
    for view in (admission_view(parent=True), admission_view(ring=False)):
        assert admit_stall.read(view, "ms") is None and admit_stall.read(view, "gap_share") is None


STALL = ["admit_stall_ms_p50", "admit_stall_ms_p95", "admit_gap_share"]
IDLE = ["admit_idle_ms_p50"] + [f"admit_idle_ms_p50.{p}" for p in ("before", "within", "after")]


def served_cells(bench):
    """The cells that report the rate of served tokens."""
    return {w for m in bench["end_to_end"] if m["name"] == "gen_tok_per_s" for w in m["workloads"]}


#: the admission's entries in ``per_layer``: source, the end-to-end metric each moves, unit
ENTRIES = {**dict.fromkeys(STALL[:2], ("program_span", "itl_p95_ms", "ms")),
           STALL[2]: ("program_counter", "itl_p95_ms", "%"),
           **dict.fromkeys(IDLE, ("device_trace", "gen_tok_per_s", "ms")),
           "admit_joined_share": ("program_counter", "gen_tok_per_s", "%")}


def check_admission_entries(bench, root=harness.ROOT):
    """The admission's entries in ``bench``, by name and wherever they stand in
    ``per_layer``: their fields, every served cell among their workloads, and their files
    under ``root``, none of them ``required``."""
    mine = {m["name"]: m for m in bench["per_layer"] if m["name"] in ENTRIES}
    assert set(mine) == set(ENTRIES)
    for name, m in mine.items():
        assert (m["source"], m["moves"], m["unit"]) == ENTRIES[name]
        assert set(m["workloads"]) == served_cells(bench) and m["layer"] == "generative engine"
        with open(os.path.join(root, "chipbench", "metrics", f"{name}.json")) as f:
            assert not json.load(f).get("required")


def test_the_admission_metrics_are_read_by_name_and_the_stall_falls_silent_on_the_parents_program():
    got = harness.read_metrics(STALL + IDLE, admission_view(), "x")
    assert set(got) == set(STALL + IDLE) and got["admit_idle_ms_p50.after"] == pytest.approx(5.2)
    assert set(harness.read_metrics(STALL + IDLE, admission_view(parent=True), "x")) == set(IDLE)
    check_admission_entries(json.load(open(os.path.join(harness.ROOT, "BENCHMARK.json"))))


def admits(*found):
    """A ring of ``generate.admit`` spans, one a ``(behind, joined)``; None for a span of a
    program whose admission has neither (it lands its queue before every prefill)."""
    ring = []
    for i, bj in enumerate(found):
        args = {"iter": i + 1, "admitted": 1}
        if bj is not None:
            args.update(behind=bj[0], joined=bj[1])
        ring.append((100 + 0.01 * i, 100.002 + 0.01 * i, "generate.admit", args))
    return {"ring": ring, "records": {"t0": 99.0, "t_end": 101.0}}


@pytest.mark.parametrize("found, want", [
    (((1, 1), (2, 2), (1, 1)), 100.0),          # every joiner found a hole
    (((1, 1), (2, 0), (1, 1), (0, 0)), 200 / 3),  # one landed; an idle engine's is no admission behind
    (((0, 0), (0, 0)), None),                   # nothing was ever in flight
    ((None, None), None),                       # a program that lands its queue: no such counts
], ids=["all_joined", "one_landed", "none_behind", "landing_program"])
def test_admit_joined_share_is_the_mean_over_the_admissions_behind_the_steps(found, want):
    got = harness.read_metrics(["admit_joined_share"], admits(*found), "x")
    assert got == ({} if want is None else {"admit_joined_share": pytest.approx(want)})
