"""``sampler_ordered_row_share`` (PR 31) on rings made by hand: the share of the rows of
the decode steps for which the sampler's program ordered the vocabulary. The metric is a
data file over ``readers/span_attr.py``; the driver runs it over the parent's program too,
whose spans carry neither attribute. ``JAX_PLATFORMS=cpu python -m pytest chipbench/tests -q``."""
import pytest

from chipbench import harness

NAME = "sampler_ordered_row_share"


def view_of(*ordered, rows=32):
    """One ``generate.decode_step`` span a value of ``ordered``, 10 ms each from
    ``perf_counter`` 100; None for a span of the parent's program, which has the older
    attributes only."""
    ring = []
    for i, n in enumerate(ordered):
        args = {"iter": i + 1, "live": 12, "bucket": rows}
        if n is not None:
            args.update(sample_path="top_k" if n else "argmax", sample_rows=rows, sample_ordered=n)
        ring.append((100 + 0.01 * i, 100.009 + 0.01 * i, "generate.decode_step", args))
    return {"ring": ring, "records": {"t0": 99.0, "t_end": 101.0}}


@pytest.mark.parametrize("ordered, want", [
    ((0, 0, 0, 0), 0.0),                # every step all greedy
    ((32, 32, 32), 100.0),              # a row with a top_k in every step
    ((0, 32, 0, 0), 25.0),              # one step of four
])
def test_the_share_is_the_mean_over_the_steps(ordered, want):
    got = harness.read_metrics([NAME], view_of(*ordered), "x")
    assert got == {NAME: pytest.approx(want)}


@pytest.mark.parametrize("view", [
    view_of(None, None, None),          # the parent's spans: no sample_rows
    view_of(),                          # no decode step at all
    {"ring": None, "records": {"t0": 0.0, "t_end": 1.0}},   # a program without the clock
], ids=["parent", "empty", "no_clock"])
def test_nothing_to_read_is_nothing_and_no_error(view):
    assert harness.read_metrics([NAME], view, "x") == {}


def test_the_entry_in_the_benchmark_lists_cells_that_report_what_it_moves():
    bench = harness.load_json("BENCHMARK.json")
    (entry,) = [m for m in bench["per_layer"] if m["name"] == NAME]
    (moved,) = [m for m in bench["end_to_end"] if m["name"] == entry["moves"]]
    assert entry["source"] == "program_counter" and entry["better"] == "lower"
    assert entry["workloads"] and set(entry["workloads"]) <= set(moved["workloads"])
