"""Tests of the benchmark itself: ``JAX_PLATFORMS=cpu python -m pytest chipbench/tests -q``.
Nothing here reads a time, a rate or a share from the CPU as a device number."""
import json
import os

import numpy as np
import pytest

from chipbench import harness, rehearse, traffic
from chipbench import trace as tr
from chipbench.counts import decoder as dcount
from chipbench.counts import resnet as rcount

HERE = os.path.dirname(os.path.abspath(__file__))
STEP = [{"kind": "step", "module": r"^jit_small\(", "op": "fusion"}]


def cfg_of(name):
    return harness.load_json("chipbench", "configs", f"{name}.json")


# -- the trace reduction, on a trace recorded on the chip (five runs of one matmul) ----
def test_trace_reduction_on_recorded_trace():
    t = tr.read(os.path.join(HERE, "small.xplane.pb"), STEP)
    assert len(t["devices"]) == 1 and len(t["spans"]["cb.step"]) == 5
    dev = t["devices"][0]
    assert [m[3] for m in dev["modules"]] == ["step"] * 5
    lo, hi = dev["modules"][0][0], dev["modules"][-1][1]
    assert hi - lo == pytest.approx(13.719299e-3, rel=1e-6)
    assert tr.busy_seconds(dev, lo, hi) == pytest.approx(454.926e-6, rel=1e-5)
    assert tr.op_seconds(dev, "^fusion", lo, hi) == pytest.approx(454.846e-6, rel=1e-5)
    assert tr.op_seconds(dev, "^copy", lo, hi, kind="step") == pytest.approx(80e-9, rel=1e-3)
    gaps = [g for g in tr.idle_gaps(dev, lo, hi) if g[1] == "between_steps"]
    assert [round(g[0] * 1e6) for g in gaps] == [3811, 3111, 3129, 3213]
    b = tr.breakdown(dev, lo, hi)
    assert b["device_ops"][0][0] == "fusion" and b["idle_gaps"][0][0] == "between_steps"
    assert sum(s for s, _ in tr.idle_gaps(dev, lo, hi)) + tr.busy_seconds(dev, lo, hi) \
        == pytest.approx(hi - lo, rel=1e-9)


def test_readers_on_recorded_trace():
    t = tr.read(os.path.join(HERE, "small.xplane.pb"), STEP)
    dev = t["devices"][0]
    view = {"trace": t, "window": (dev["modules"][0][0], dev["modules"][-1][1])}
    from chipbench.readers import idle_share, program_gap, program_time
    assert program_time.read(view, "step") == pytest.approx(0.090926, rel=1e-3)
    assert program_gap.read(view, "step") == pytest.approx(3.171, rel=1e-3)
    assert idle_share.read(view) == pytest.approx(100 * (1 - 454.926e-6 / 13.719299e-3), rel=1e-5)
    assert program_time.read(view, "no_such_kind") is None      # nothing to read: nothing


def test_the_decode_step_is_the_engine_program_run_most_whatever_it_is_lowered_to():
    programs = cfg_of("gpt2-large")["programs"]
    names = (["jit_fn(1)", "jit_fn(2)", "jit_convert(7)", "jit_sample_logits(9)"] * 2 + ["jit_fn(3)", "jit_convert(7)"] * 5)
    runs = [(float(i), i + 0.5, n) for i, n in enumerate(names)]
    assert tr.kinds(runs, [], programs) == {
        "jit_fn(1)": "admit", "jit_fn(2)": "admit", "jit_fn(3)": "decode_step",
        "jit_sample_logits(9)": "sample", "jit_convert(7)": "other"}
    assert not any("op" in p for p in programs)         # no kernel's name decides a kind


def test_a_required_metric_that_reads_nothing_fails_the_run():
    t = tr.read(os.path.join(HERE, "small.xplane.pb"), STEP)
    dev = t["devices"][0]
    view = {"trace": t, "window": (dev["modules"][0][0], dev["modules"][-1][1]),
            "cfg": None, "counts": None}
    assert harness.read_metrics(["paged_attn_roofline"], view, "x") == {}    # a kernel may fall silent
    assert set(harness.read_metrics(["device_idle_share.gen"], view, "x")) == {"device_idle_share.gen"}
    with pytest.raises(RuntimeError, match="decode_step_ms_p50 found nothing"):
        harness.read_metrics(["paged_attn_roofline", "decode_step_ms_p50"], view, "x")


# -- the generator ---------------------------------------------------------------------
def test_the_seed_draws_the_tokens_and_not_the_work():
    import itertools
    m = traffic.load("decode-offline")
    first = lambda seed: [r for w in itertools.islice(traffic.waves(m, seed, 50257), 3) for r in w]  # noqa: E731
    a, b, c = (first(s) for s in (5, 5, 2**31 + 9))
    assert a == b and a != c and len(a) == 3 * 32
    assert [r["client"] for r in a] == list(range(32)) * 3
    lengths = lambda rs: [(len(r["prompt"]), r["max_tokens"]) for r in rs]  # noqa: E731
    assert lengths(a) == lengths(c) and len(set(lengths(a)[:32])) == 32
    assert all(32 <= len(r["prompt"]) <= 256 and 128 <= r["max_tokens"] <= 512 for r in a)
    assert all(0 <= t < 50257 for r in a for t in r["prompt"])


class FakePool:
    live_blocks = 0

    def __init__(self, blocks):
        self.usable_blocks = blocks

    def blocks_for(self, n):
        return -(-n // 16)


class FakeStream:
    reason = None

    def __init__(self, prog, n):
        self.prog, self.left = prog, n

    def next(self, timeout=None):
        import queue
        if self.left == 0:
            if self.reason is None:
                self.reason = "max_tokens"
                self.prog.flying -= 1
            return None
        if np.random.rand() < 0.7:
            raise queue.Empty
        self.left -= 1
        return 1

    def cancel(self):
        self.left = 0


class FakeProgram:
    def __init__(self, blocks=6):
        self.pool, self.flying, self.peak, self.refuse = FakePool(blocks), 0, 0, set()

    def submit(self, prompt, max_tokens):
        if len(prompt) in self.refuse:
            raise RuntimeError("refused")
        self.flying += 1
        self.peak = max(self.peak, self.flying)
        return FakeStream(self, max_tokens)

    def compiles_in_window(self):
        return 0

    def close(self):
        pass


MIX = {"kind": "closed", "clients": 4, "prompt": {"dist": "uniform", "lo": 4, "hi": 20},
       "output": {"dist": "uniform", "lo": 2, "hi": 12}}


def _drive(mix, prog, monkeypatch, seconds=0.5):
    from chipbench.drivers import generate
    monkeypatch.setattr(generate, "numbers", lambda *a, **k: {"served_gap_sq": 0.0})
    cfg = {"reference": "decoder", "builder": "decoder", "vocab_size": 96, "max_in_flight": 4,
           "max_len": 64, "limits": {"served_gap_sq": 1.0}}
    ctx = {"cfg": cfg, "mix": mix, "seed": 1, "seconds": seconds, "trace": False, "chips": 1,
           "t_start": 0.0, "build": lambda *a: prog}
    return generate.run(ctx)


def test_never_more_in_flight_than_the_pool_holds_to_the_last_token(monkeypatch):
    prog = FakeProgram()            # 6 blocks of 16 tokens; a request takes 1 or 2
    res = _drive(MIX, prog, monkeypatch)
    assert 3 <= prog.peak <= 4 and res["attempted"] > 8 and res["failed"] == 0
    with pytest.raises(RuntimeError, match="more clients"):
        _drive(dict(MIX, clients=5), FakeProgram(), monkeypatch)


def test_a_request_fails_only_by_error(monkeypatch):
    prog = FakeProgram()
    prog.refuse = {4, 20}
    res = _drive(MIX, prog, monkeypatch)
    assert res["attempted"] > 8 and 1 <= res["failed"] < res["attempted"]


def test_closed_loop_cancels_what_is_in_flight_and_does_not_count_it(monkeypatch):
    mix = dict(MIX, output={"dist": "uniform", "lo": 20, "hi": 400})
    with pytest.raises(RuntimeError, match="whole pool"):
        _drive(mix, FakeProgram(), monkeypatch)
    prog = FakeProgram(blocks=100)
    res = _drive(mix, prog, monkeypatch)
    assert res["failed"] == 0 and res["attempted"] >= 1
    assert res["end_to_end"]["gen_tok_per_s"] > 0
    issued = len(res["records"]["requests"])
    assert issued - res["attempted"] == prog.peak == 4      # cancelled: issued, not attempted


# -- the counts, against numbers worked by hand ------------------------------------------
def test_gpt2_large_counts():
    cfg = cfg_of("gpt2-large")
    # 36 x (4 x 1280^2 + 2 x 1280 x 5120 + biases and LayerNorms) + embeddings + untied head
    assert dcount.n_params(cfg) == 838_174_720
    assert dcount.kv_bytes_per_token(cfg) == 2 * 36 * 1280 * 2 == 184_320
    body, head = dcount.matmul_params(cfg)
    assert (body, head) == (707_788_800, 64_328_960)
    assert dcount.decode_flops(cfg, 500) == 2 * (body + head) + 4 * 36 * 1280 * 500
    assert dcount.prefill_flops(cfg, 2) == 4 * body + 2 * head + 4 * 36 * 1280 * 3
    assert dcount.paged_attention_bytes(cfg, [100, 300]) == 184_320 * 400 + 36 * 2 * 2 * 1280 * 4


def test_resnet50_counts():
    cfg = cfg_of("resnet50")
    sites, feat = rcount.conv_sites(cfg)
    assert len(sites) == 53 and feat == 2048
    fwd = rcount.forward_flops(cfg)
    # 3.86 G multiply-adds: the v1 net with the stride on the 1x1, as the zoo builds it
    assert fwd == pytest.approx(7.72e9, rel=5e-3)
    # The records' 22.25 GFLOP a sample (ROADMAP's driver figure) is 3 x a forward of
    # 7.42 GFLOP; this count is 3 x 7.72 less the stem's input gradient: 4 % more.
    assert rcount.train_flops(cfg) == 3 * fwd - 2 * 112 * 112 * 49 * 3 * 64
    assert rcount.train_flops(cfg) / 22.25e9 == pytest.approx(1.03, abs=0.02)


# -- a whole run at a tiny size, from files added to a copy ------------------------------
@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    dest = rehearse.tiny_copy(str(tmp_path_factory.mktemp("copy")))
    with open(os.path.join(dest, "chipbench", "metrics", "decode_step_ms_p90.json"), "w") as f:
        json.dump({"reader": "program_time", "args": {"kind": "step", "percentile": 90}}, f)
    return dest


KEYS = {"correct", "attempted", "failed", "metrics", "device", "checks"}
BROKEN_TRAIN = """
from chipbench.models import resnet
class Stuck(resnet.Program):
    def step(self, i):
        import jax
        keep = jax.tree_util.tree_map(jax.numpy.copy, (self.net.params, self.net.updater_states))
        loss = super().step(i)
        self.net.params, self.net.updater_states = keep
        return loss
class Half(resnet.Program):
    def __init__(self, *a):
        super().__init__(*a)
        from deeplearning4j_tpu.datasets.dataset import DataSet
        self.ring = [DataSet(d.features[:4], d.labels[:4]) for d in self.ring]
import chipbench.harness as h
_m = h.measure
h.measure = lambda *a, **k: _m(*a, **dict(k, build=lambda *b: %s(*b)))
"""
BROKEN_TOKEN = """
from chipbench.models import decoder
class Altered(decoder.Program):
    def submit(self, prompt, max_tokens):
        s = super().submit(prompt, max_tokens)
        nxt, seen = s.next, []
        def altered(timeout=None):
            tok = nxt(timeout=timeout)
            seen.append(tok)
            return (tok + 1) % 96 if tok is not None and len(seen) == 2 else tok
        s.next = altered
        return s
import chipbench.harness as h
_m = h.measure
h.measure = lambda *a, **k: _m(*a, **dict(k, build=lambda *b: Altered(*b)))
"""


@pytest.mark.parametrize("cell", list(rehearse.CELLS))
def test_a_cell_added_as_files_runs_and_prints_the_contracts_line(copy, cell):
    out = rehearse.run_cell(copy, cell)
    assert set(out) == KEYS and list(out)[-1] == "checks" and out["correct"] is True
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    bench = json.load(open(os.path.join(copy, "BENCHMARK.json")))
    want = {m["name"] for m in bench["end_to_end"] if cell in m.get("workloads", [cell])}
    assert set(out["metrics"]) == want and "setup_s" in want and len(want) >= 2
    assert all(set(v) == {"value", "unit"} and v["value"] > 0 for v in out["metrics"].values())
    assert all(set(c) == {"value", "limit"} for c in out["checks"].values())


def test_a_metric_added_as_a_file_is_read_by_name(copy):
    spec = json.load(open(os.path.join(copy, "chipbench", "metrics", "decode_step_ms_p90.json")))
    t = tr.read(os.path.join(HERE, "small.xplane.pb"), STEP)
    dev = t["devices"][0]
    view = {"trace": t, "window": (dev["modules"][0][0], dev["modules"][-1][1])}
    got = harness.module("readers", spec["reader"]).read(view, **spec["args"])
    assert got == pytest.approx(0.0909, rel=5e-3)


# -- the timed path broken underneath: correct has to come out false ---------------------
@pytest.mark.parametrize("cell,patch", [
    ("resnet-tiny.train-tiny", BROKEN_TRAIN % "Stuck"),
    ("resnet-tiny.train-tiny", BROKEN_TRAIN % "Half"),
    ("decoder-tiny.closed-tiny", BROKEN_TOKEN),
], ids=["state_unchanged", "half_the_batch", "token_altered"])
def test_a_broken_timed_path_is_not_correct(copy, cell, patch):
    assert rehearse.run_cell(copy, cell, patch=patch)["correct"] is False


# -- the controls, at a size a test run holds: the next lower precision is not correct -----
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_float8_control_fails_a_training_limit(seed):
    from chipbench.drivers import train
    from chipbench.reference import resnet as ref
    cfg = dict(cfg_of("resnet50"), **rehearse.TINY["resnet-tiny"][1])
    xs, ys = ref.make_batches(cfg, seed, cfg["proof_steps"], 32)
    params = ref.make_params(cfg, seed)
    want = ref.first_steps(cfg, params, xs, ys)
    assert all(v == 0 for v, _ in train.compare(want, want, cfg["limits"]).values())
    low = train.compare(ref.first_steps(cfg, params, xs, ys, low=True), want, cfg["limits"])
    assert any(v > limit for v, limit in low.values())


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_bfloat16_control_fails_the_serving_limit(seed):
    from chipbench.reference import decoder as ref
    cfg = dict(cfg_of("gpt2-large"), vocab_size=4096, n_layers=24, n_heads=4, d_model=256,
               d_ff=1024, max_len=256, init_std=0.045)       # logits as wide as at 1280
    params = ref.make_params(cfg, seed)
    rs = np.random.RandomState(seed)
    own, low = [], []
    for _ in range(8):
        tokens = rs.randint(0, 4096, 256).astype(np.int32)
        low.append(np.asarray(ref.served_gaps(cfg, params, tokens, True), np.float64))
        best = np.argmax(np.asarray(ref.forward(4, params, tokens)), -1)
        tokens[1:] = best[:-1]          # the reference's own first choice at every position
        own.append(np.asarray(ref.served_gaps(cfg, params, tokens), np.float64)[:1])
    assert low[0].shape == (255,) and max(g.max() for g in own) < 1e-6
    assert np.mean(np.square(np.concatenate(low))) > cfg["limits"]["served_gap_sq"]


def test_no_chip_no_result(monkeypatch):
    with pytest.raises(SystemExit):
        harness.measure("resnet50.train-1chip", 1, 1, 0)
    monkeypatch.setattr("sys.argv", ["calibrate", "--workload", "gpt2-large.decode-offline", "--seeds", "1"])
    from chipbench import calibrate
    with pytest.raises(SystemExit):
        calibrate.main()
