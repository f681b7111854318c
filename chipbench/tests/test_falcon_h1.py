"""The Falcon-H1 cell at a tiny size on the CPU, from files added to a copy of the
benchmark: it runs and is ``correct``; with each planted fault underneath it is not; the
counts. Nothing here reads a time, a rate or a share from the CPU as a device number."""
import json
import os

import pytest

from chipbench import harness, rehearse
from chipbench.counts import falcon_h1 as counts
from chipbench.faults import falcon_h1 as faults

CELL = "falcon-h1-tiny.chat-tiny"
#: every published ratio kept: 5 query heads a KV head, 2 state groups, a convolution of
#: width 4; ``init_std`` widens the draws so that a 40-wide net's logits have margins, and
#: the mixer's two branch multipliers are moved so that its 40-wide fan-in and 32-wide
#: fan-out weigh as the published 5120 and 4096 do (sqrt(5120 / 40) = 11 times 0.25)
TINY = {"hidden_size": 40, "num_attention_heads": 10, "num_key_value_heads": 2, "head_dim": 8,
        "intermediate_size": 64, "vocab_size": 4096, "num_hidden_layers": 2, "mamba_d_ssm": 32,
        "mamba_n_heads": 4, "mamba_d_head": 8, "mamba_d_state": 16, "mamba_chunk_size": 8,
        "lm_head_multiplier": 1.0, "init_std": 0.3, "ssm_in_multiplier": 2.5,
        "ssm_out_multiplier": 1.0, "max_len": 64, "max_in_flight": 4,
        "engine": {"kv_blocks": 17, "kv_block_size": 16, "kv_dtype": "bf16", "state_slots": 5,
                   "prompt_buckets": [16, 32], "decode_buckets": [4], "max_seq_len": 64,
                   "paged": True},
        "limits": {"served_gap_sq": 1e-4}}
MIX = {"kind": "closed", "clients": 4, "prompt": {"dist": "uniform", "lo": 4, "hi": 24},
       "output": {"dist": "uniform", "lo": 6, "hi": 24}}


def cfg_of(name):
    return harness.load_json("chipbench", "configs", f"{name}.json")


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """The rehearsal's copy of the benchmark with the tiny hybrid cell added: files and
    entries only."""
    dest = rehearse.tiny_copy(str(tmp_path_factory.mktemp("copy")))
    cfg = dict(cfg_of("falcon-h1-34b"), name="falcon-h1-tiny", **TINY)
    with open(os.path.join(dest, "chipbench", "configs", "falcon-h1-tiny.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(dest, "chipbench", "traffic", "chat-tiny.json"), "w") as f:
        json.dump(MIX, f)
    with open(os.path.join(dest, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "falcon-h1-tiny", "source": cfg["source"], "why": "tiny",
                             "file": "chipbench/configs/falcon-h1-tiny.json",
                             "reduced": ["num_hidden_layers"]})
    bench["workloads"].append({"name": CELL, "config": "falcon-h1-tiny", "traffic": "chat-tiny",
                               "chips": 1, "why": "tiny"})
    for m in bench["end_to_end"]:
        if m["name"] in ("gen_tok_per_s", "itl_p95_ms"):
            m["workloads"].append(CELL)
    with open(os.path.join(dest, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return dest


def test_the_cell_runs_through_the_harness_and_is_correct(copy):
    out = rehearse.run_cell(copy, CELL)
    assert out["correct"] is True and out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"setup_s", "gen_tok_per_s", "itl_p95_ms"}
    assert out["checks"]["served_gap_sq"]["value"] < out["checks"]["served_gap_sq"]["limit"] / 3


@pytest.mark.parametrize("fault", faults.FAULTS)
def test_a_planted_fault_is_not_correct(copy, fault):
    patch = f"from chipbench.faults import falcon_h1 as f\nf.plant({fault!r})"
    out = rehearse.run_cell(copy, CELL, patch=patch)
    assert out["failed"] == 0 and out["correct"] is False


def test_the_benchmark_names_the_cell_and_its_metrics():
    bench, cell, cfg, mix = harness.cell_of("falcon-h1-34b.chat-closed")
    assert cell["chips"] == 1 and mix["clients"] == cfg["max_in_flight"] == 32
    per_layer = {m["name"] for m in bench["per_layer"] if cell["name"] in m.get("workloads", [])}
    assert {"ssm_state_roofline", "prefill_ms_p50", "paged_attn_roofline", "decode_step_mfu",
            "decode_step_ms_p50", "device_idle_share.gen"} <= per_layer
    for name in per_layer:      # every metric has its file and its reader
        spec = harness.load_json("chipbench", "metrics", f"{name}.json")
        assert hasattr(harness.module("readers", spec["reader"]), "read")
    # the published config whole, the top level as it is run: only the depth differs
    changed = {k for k, v in cfg["published"].items() if cfg[k] != v}
    assert changed == set(cfg["reduced"]) == {"num_hidden_layers"}
    # the pool holds every request to its last token, and every row has a slot
    longest = -(-(mix["prompt"]["hi"] + mix["output"]["hi"]) // 16)
    assert 32 * longest <= cfg["engine"]["kv_blocks"] - 1
    assert cfg["engine"]["state_slots"] == max(cfg["engine"]["decode_buckets"]) + 1


def test_falcon_h1_counts():
    cfg = cfg_of("falcon-h1-34b")
    body, head = counts.matmul_params(cfg)
    layer = 5120 * 2560 + 2 * 5120 * 512 + 2560 * 5120 + 5120 * 9248 + 4096 * 5120 + 3 * 5120 * 21504
    assert body == 6 * layer and head == 5120 * 261120
    assert counts.n_params(cfg) / 1e9 == pytest.approx(5.255, abs=0.002)     # 10.51 GB in bfloat16
    assert counts.kv_bytes_per_token(cfg) == 2 * 6 * 4 * 128 * 2
    assert counts.ssm_state_bytes(cfg, 32) == 32 * 6 * 2 * 32 * 128 * 256 * 4  # 1.6 GB a step
    assert counts.paged_attention_bytes(cfg, [650] * 32) == 12288 * 650 * 32 + 6 * 32 * 2 * 2560 * 4   # 0.26 GB
    one = counts.decode_flops(cfg, 1)
    assert one == 2 * (body + head) + 4 * 6 * 2560 + 6 * (5 * 32 * 128 * 256 + 2 * 4 * 5120)
    assert counts.decode_flops(cfg, 101) - one == 4 * 6 * 2560 * 100
    assert counts.prefill_flops(cfg, 1) == one
    assert counts.prefill_flops(cfg, 512) < 512 * counts.decode_flops(cfg, 512)


# -- the accepted readers of the gap between decode steps, on a loop that keeps a step in flight
MS = 1e-3


def chained_view(steps=8, skew=-1.2 * MS):
    """Decode programs of 100 ms, each queued while the one before runs (it starts 0.02 ms
    after that ends), and the engine's spans as PR 28's loop records them: an iteration
    is build 3 ms, the dispatch of its own step 0.3 ms, the pull of the step *before* until
    0.3 ms after that one's program ends, its emit 2 ms, and 0.5 ms under no span."""
    ta, lo = 5000.0, 17.0
    host = lambda t: ta + t - lo            # noqa: E731
    mods, events, t, flying = [], [], lo + 1 * MS, None
    for i in range(1, steps + 1):
        def span(name, dur, **args):
            nonlocal t
            events.append((host(t), host(t + dur), f"generate.{name}", dict(args, iter=i)))
            t += dur
        span("build", 3 * MS)
        opened = t
        start = max(t + 0.4 * MS, flying[1] + 0.02 * MS if flying else 0.0)
        mods.append((start - skew, start + 100 * MS - skew, "jit_fn(2)", "decode_step"))
        span("dispatch", 0.3 * MS, program="decode_step")
        if flying:
            span("pull", flying[1] + 0.3 * MS - t)
        events.append((host(opened), host(t), "generate.decode_step",
                       {"iter": i, "live": 30, "bucket": 32, "pool_live": 300, "pool_usable": 384}))
        if flying:
            span("emit", 2 * MS, tokens=30, retired=0)
        flying, t = (start, start + 100 * MS), t + 0.5 * MS
    end = flying[1] + 1 * MS
    return {"trace": {"devices": [{"modules": sorted(mods)}]}, "window": (lo, end),
            "host_window": (ta, host(end)), "ring": events,
            "records": {"t0": ta - 10.0, "t_end": host(end) + 10.0}}


def test_the_gaps_readers_read_a_loop_that_keeps_a_step_in_flight():
    """The device no longer waits for the host between steps: the gap reader finds the
    gap that is left, and the span reader reads the steps' counts without raising."""
    from chipbench.readers import program_gap, span_attr
    view = chained_view()
    assert program_gap.read(view, "decode_step") == pytest.approx(0.02, abs=1e-6)
    assert span_attr.read(view, "generate.decode_step", "live", "bucket",
                          required=True) == pytest.approx(100 * 30 / 32)
