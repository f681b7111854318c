"""The MiMo-V2.5 cell at a tiny size on the CPU, from files added to a copy of the
benchmark: it runs and is ``correct``; with each planted fault underneath it is not; the
bfloat16 control reads over the limit; the counts; the new metrics' readers. Nothing here
reads a time, a rate or a share from the CPU as a device number."""
import json
import os
import subprocess
import sys

import pytest

from chipbench import harness, rehearse
from chipbench.counts import mimo_v2 as counts
from chipbench.faults import mimo_v2 as faults

CELL = "mimo-tiny.agent-tiny"
#: the published shape kept (K heads half again as wide as V heads, 8 query heads on 2 and 4
#: KV heads, a router 16 wide with 4 a token, a quarter of the experts held, the first four
#: entries of the published patterns: full, window x 3; dense, experts x 3); a window of 8 so
#: that every prompt wraps its rings in the prefill; ``init_std`` widens the draws so that a
#: 64-wide net's scores, router logits and logits spread as the published widths' do (1.2 to
#: 1.4), ``bias_std`` so that the selection bias moves the top 4 of 16 as often as 0.02 moves
#: the top 8 of 256, ``sink_mean`` so that a sink beside 8 keys takes the share that 5 takes
#: beside 128. Readings at this size (seeds 3, 4, 5): sound 1.2e-4 to 3.6e-4; the
#: smallest fault (``expert_dropped``) 5.8e-3
TINY = {"hidden_size": 64, "num_attention_heads": 8, "num_key_value_heads": 2,
        "swa_num_key_value_heads": 4, "head_dim": 24, "v_head_dim": 16, "intermediate_size": 128,
        "moe_intermediate_size": 32, "router_experts": 16, "n_routed_experts": 4, "experts_first": 4,
        "num_experts_per_tok": 4, "sliding_window": 8, "num_hidden_layers": 4, "vocab_size": 4096,
        "init_std": 0.15, "bias_std": 0.1, "sink_mean": 3.0, "max_len": 64, "max_in_flight": 4,
        "engine": {"kv_blocks": 33, "kv_block_size": 8, "kv_dtype": "bf16", "state_slots": 5,
                   "prompt_buckets": [16, 32], "decode_buckets": [4], "max_seq_len": 64,
                   "paged": True},
        "limits": {"served_gap_sq": 8e-4}}
MIX = {"kind": "closed", "clients": 4, "prompt": {"dist": "uniform", "lo": 9, "hi": 24},
       "output": {"dist": "uniform", "lo": 10, "hi": 40}}


def cfg_of(name):
    return harness.load_json("chipbench", "configs", f"{name}.json")


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """The rehearsal's copy of the benchmark with the tiny cell added: files and entries only."""
    dest = rehearse.tiny_copy(str(tmp_path_factory.mktemp("copy")))
    cfg = dict(cfg_of("mimo-v2.5"), name="mimo-tiny", **TINY)
    with open(os.path.join(dest, "chipbench", "configs", "mimo-tiny.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(dest, "chipbench", "traffic", "agent-tiny.json"), "w") as f:
        json.dump(MIX, f)
    with open(os.path.join(dest, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "mimo-tiny", "source": cfg["source"], "why": "tiny",
                             "file": "chipbench/configs/mimo-tiny.json", "reduced": []})
    bench["workloads"].append({"name": CELL, "config": "mimo-tiny", "traffic": "agent-tiny",
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
    patch = f"from chipbench.faults import mimo_v2 as f\nf.plant({fault!r})"
    out = rehearse.run_cell(copy, CELL, patch=patch)
    assert out["failed"] == 0 and out["correct"] is False
    assert out["checks"]["served_gap_sq"]["value"] > 3 * out["checks"]["served_gap_sq"]["limit"]


def test_the_bfloat16_control_reads_apart_from_the_sound_run(copy):
    """The plain reference with activations in bfloat16, judged on what a sound run served. At 64
    wide and 4 layers the rounding of the products' bfloat16 operands, which program and control
    share, is most of either reading, so the control reads only 1.4 times the program here
    (2.0e-4 against 1.4e-4; 2.2 times with a float32 cache); what it reads at the published
    widths, where the limit is set, is in PERF.md section 4."""
    code = ("import json, time\nfrom chipbench import harness\n"
            f"bench, cell, cfg, mix = harness.cell_of({CELL!r})\n"
            "driver = harness.module('drivers', cfg['driver'])\n"
            "ref = harness.module('reference', cfg['reference'])\n"
            "ctx = {'cell': cell, 'cfg': cfg, 'mix': mix, 'seed': 3, 'trace': False, 'seconds': 2.0,\n"
            "       'chips': 1, 't_start': time.perf_counter(), 'build': None, 'keep': True}\n"
            "res = driver.run(ctx)\n"
            "print(json.dumps({'program': res['numbers'], **driver.controls(ctx, ref, res)}))")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=rehearse.ROOT)
    p = subprocess.run([sys.executable, "-c", code], cwd=copy, env=env, capture_output=True,
                       text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    limit = TINY["limits"]["served_gap_sq"]
    assert out["program"]["served_gap_sq"] < limit / 3
    assert out["control_bf16"]["served_gap_sq"] > 1.25 * out["program"]["served_gap_sq"]


def test_the_benchmark_names_the_cell_and_its_metrics():
    bench, cell, cfg, mix = harness.cell_of("mimo-v2.5.agent-closed")
    assert cell["chips"] == 1 and mix["kind"] == "closed"
    assert mix["clients"] == cfg["max_in_flight"] == 128
    assert (mix["prompt"]["dist"], mix["prompt"]["lo"], mix["prompt"]["hi"]) == ("uniform", 256, 2048)
    assert (mix["output"]["dist"], mix["output"]["lo"], mix["output"]["hi"]) == ("uniform", 256, 768)
    per_layer = {m["name"] for m in bench["per_layer"] if cell["name"] in m.get("workloads", [])}
    assert {"moe_grouped_roofline.prefill", "moe_experts_hit_share",
            "moe_rows_max_share", "kv_read_window_share", "prefill_ms_p50", "paged_attn_roofline",
            "paged_live_block_share", "decode_step_mfu", "decode_step_mfu.itl", "decode_step_ms_p50",
            "device_idle_share.gen", "kv_pool_peak_share", "sampler_ordered_row_share"} <= per_layer
    assert not {"ssm_state_roofline", "selective_state_roofline", "selective_scan_roofline",
                "prefill_layer_position_share"} & per_layer
    for name in per_layer:      # every metric has its file and its reader
        spec = harness.load_json("chipbench", "metrics", f"{name}.json")
        assert hasattr(harness.module("readers", spec["reader"]), "read")
    # every published key at the top level as it is run; three keys cut, no width among them
    conf = next(c for c in bench["configs"] if c["name"] == "mimo-v2.5")
    assert conf["reduced"] == cfg["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert conf["source"] == cfg["source"]
    differ = {k for k, v in cfg["published"].items() if cfg[k] != v}
    assert differ == set(cfg["reduced"])
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"], cfg["vocab_size"]) == (7, 16, 19072)
    assert cfg["router_experts"] == cfg["published"]["n_routed_experts"] == 256
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert cfg["hybrid_layer_pattern"][:7] == [0, 1, 1, 1, 1, 0, 1]
    assert cfg["moe_layer_freq"][:7] == [0, 1, 1, 1, 1, 1, 1]
    # the pool holds every request to its last token, every row has a slot, every prompt wraps
    longest = -(-(mix["prompt"]["hi"] + mix["output"]["hi"]) // 16)
    assert 128 * longest <= cfg["engine"]["kv_blocks"] - 1
    assert mix["prompt"]["hi"] + mix["output"]["hi"] <= cfg["engine"]["max_seq_len"] == cfg["max_len"]
    assert cfg["engine"]["state_slots"] == max(cfg["engine"]["decode_buckets"]) + 1
    assert cfg["sliding_window"] % cfg["engine"]["kv_block_size"] == 0
    assert mix["prompt"]["lo"] > cfg["sliding_window"]
    assert max(cfg["engine"]["prompt_buckets"]) >= mix["prompt"]["hi"]


def test_mimo_v2_counts():
    cfg = cfg_of("mimo-v2.5")
    s = counts.sizes(cfg)
    assert (s["full"], s["window"], s["dense"], s["moe"]) == (2, 5, 1, 6)
    assert counts.attention_params(cfg, False) == 4096 * (12288 + 768 + 512) + 8192 * 4096    # 89.1 M
    assert counts.attention_params(cfg, True) == 4096 * (12288 + 1536 + 1024) + 8192 * 4096   # 94.4 M
    assert counts.expert_params(cfg) == 3 * 4096 * 2048                                        # 25.17 M
    assert counts.n_params(cfg) / 1e9 == pytest.approx(3.431, abs=0.002)                       # 6.86 GB
    body, head = counts.matmul_params(cfg)
    assert head == 4096 * 19072
    # of the experts a token's share: 8 x 16 / 256 = half an expert a layer
    assert body == (2 * counts.attention_params(cfg, False) + 5 * counts.attention_params(cfg, True)
                    + 3 * 4096 * 16384 + 6 * (4096 * 256 + 0.5 * counts.expert_params(cfg)))
    assert counts.kv_bytes_per_token(cfg) == 2 * 4 * (192 + 128) * 2 == 5120
    assert counts.ring_bytes_per_token(cfg) == 5 * 8 * (192 + 128) * 2 == 5 * 5120
    io = 7 * 2 * 64 * (192 + 128) * 4
    assert counts.paged_attention_bytes(cfg, [100, 1100]) == 5120 * 1200 + 5 * 5120 * 228 + io
    one = counts.decode_flops(cfg, 1)
    assert one == 2 * (body + head) + 7 * 2 * 64 * 320
    assert counts.decode_flops(cfg, 129) - counts.decode_flops(cfg, 128) == 2 * 2 * 64 * 320   # the window is full
    assert counts.prefill_flops(cfg, 1) == one
    # a decode step of 128 rows that hits 94 experts of 96 with 384 routed rows: the weights
    assert counts.expert_bytes(cfg, 94, 384) == 94 * 3 * 4096 * 2048 * 2 + 384 * 4096 * 6
    assert counts.expert_flops(cfg, 384) == 384 * 2 * 3 * 4096 * 2048


def test_the_new_metrics_read_the_spans_and_fall_silent_without_them():
    """The three metrics this cell brings, on a view made by hand; on a program that has
    neither the counts on its spans nor a grouped product to name they read nothing and
    do not raise."""
    from chipbench.readers import moe_share, span_attr
    cfg = cfg_of("mimo-v2.5")
    ta, lo = 5000.0, 17.0
    moe = {"moe_rows": 380, "moe_rows_all": 128 * 8 * 6, "moe_experts_hit": 94, "moe_experts_held": 96,
           "moe_rows_max": 60}
    pre = {"moe_rows": 6000, "moe_rows_all": 2000 * 8 * 6, "moe_experts_hit": 96, "moe_experts_held": 96,
           "moe_rows_max": 500}
    ring = [(ta + 0.10, ta + 0.14, "generate.prefill", dict(pre, tokens=2000, bucket=2048)),
            (ta + 0.20, ta + 0.21, "generate.emit", dict(moe, tokens=128, retired=0))]
    dev = {"modules": [(lo + 0.10, lo + 0.135, "jit_fn(1)", "admit"),
                       (lo + 0.19, lo + 0.205, "jit_fn(2)", "decode_step")],
           "ops": [(lo + 0.101, lo + 0.111, "%pallas.moe_grouped_matmul.3 = f32[2048,2048] custom-call(..)"),
                   (lo + 0.191, lo + 0.197, "%pallas.moe_grouped_matmul.9 = f32[128,2048] custom-call(..)"),
                   (lo + 0.198, lo + 0.199, "%fusion.7 = f32[8] fusion(..)")]}
    view = {"trace": {"devices": [dev]}, "window": (lo, lo + 1), "host_window": (ta, ta + 1),
            "ring": ring, "records": {"t0": ta, "t_end": ta + 1}, "cfg": cfg, "counts": counts,
            "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}}
    spec = lambda name: harness.load_json("chipbench", "metrics", f"{name}.json")["args"]  # noqa: E731
    # the same reader over a decode step's counts and programs (no metric: the auto rung's
    # decode step has no grouped product to name; it reads under DL4J_TPU_MOE_GROUPED=1)
    decode = dict(spec("moe_grouped_roofline.prefill"), span="generate.emit", kind="decode_step")
    assert span_attr.read(view, **spec("moe_experts_hit_share")) == pytest.approx(100 * 94 / 96)
    assert span_attr.read(view, **spec("moe_rows_max_share")) == pytest.approx(100 * 60 / 380)
    # decode: the weights' read is the nearer roof; prefill at 6000 rows: still the bytes
    # (under about 240 rows an expert the read of its 50 MB takes longer than its products)
    step = counts.expert_bytes(cfg, 94, 380) / 819e9
    assert step > counts.expert_flops(cfg, 380) / 197e12
    assert moe_share.read(view, **decode) == pytest.approx(100 * step / 0.006)
    admit = max(counts.expert_bytes(cfg, 96, 6000) / 819e9, counts.expert_flops(cfg, 6000) / 197e12)
    assert moe_share.read(view, **spec("moe_grouped_roofline.prefill")) == pytest.approx(100 * admit / 0.010)
    # a lowering with no operation to name, and the parent's program (no counts on its spans)
    unnamed = dict(view, trace={"devices": [dict(dev, ops=dev["ops"][2:])]})
    assert moe_share.read(unnamed, **decode) is None
    assert moe_share.read(unnamed, **spec("moe_grouped_roofline.prefill")) is None
    old = dict(view, ring=[(s, e, n, {"tokens": 128, "bucket": 2048}) for s, e, n, _ in ring])
    for args in (decode, spec("moe_grouped_roofline.prefill")):
        assert moe_share.read(old, **args) is None
    for name in ("moe_experts_hit_share", "moe_rows_max_share"):
        assert span_attr.read(old, **spec(name)) is None
    from chipbench.counts import falcon_h1
    assert moe_share.read(dict(view, counts=falcon_h1), **spec("moe_grouped_roofline.prefill")) is None
