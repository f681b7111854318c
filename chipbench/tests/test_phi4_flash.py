"""The Phi-4-mini-flash cell at a tiny size on the CPU, from files added to a copy of
the benchmark: it runs and is ``correct``; with each planted fault underneath it is not;
the bfloat16 control reads over the limit's ratio; the counts. Nothing here reads a time,
a rate or a share from the CPU as a device number."""
import json
import os
import subprocess
import sys

import pytest

from chipbench import harness, rehearse
from chipbench.counts import phi4_flash as counts
from chipbench.faults import phi4_flash as faults

CELL = "phi4-flash-tiny.reason-tiny"
#: the published ratios kept (query width = hidden, 2 query heads a KV head, an MLP of 4,
#: a state of 16); a window of 8 so that every request's ring wraps several times;
#: ``init_std`` widens the draws so that a 64-wide net's logits have margins and
#: ``x_proj_std`` so that its 128-wide state's read-out weighs as at 5120 (section 4)
TINY = {"hidden_size": 64, "num_attention_heads": 8, "num_key_value_heads": 4,
        "intermediate_size": 256, "vocab_size": 4096, "num_hidden_layers": 8, "sliding_window": 8,
        "mamba_dt_rank": 4, "init_std": 0.3, "x_proj_std": 0.15, "max_len": 64, "max_in_flight": 4,
        "engine": {"kv_blocks": 33, "kv_block_size": 8, "kv_dtype": "bf16", "state_slots": 5,
                   "prompt_buckets": [16, 32], "decode_buckets": [4], "max_seq_len": 64,
                   "paged": True},
        "limits": {"served_gap_sq": 4e-4}}
MIX = {"kind": "closed", "clients": 4, "prompt": {"dist": "uniform", "lo": 4, "hi": 24},
       "output": {"dist": "uniform", "lo": 10, "hi": 40}}


def cfg_of(name):
    return harness.load_json("chipbench", "configs", f"{name}.json")


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """The rehearsal's copy of the benchmark with the tiny cell added: files and entries
    only."""
    dest = rehearse.tiny_copy(str(tmp_path_factory.mktemp("copy")))
    cfg = dict(cfg_of("phi4-mini-flash"), name="phi4-flash-tiny", **TINY)
    with open(os.path.join(dest, "chipbench", "configs", "phi4-flash-tiny.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(dest, "chipbench", "traffic", "reason-tiny.json"), "w") as f:
        json.dump(MIX, f)
    with open(os.path.join(dest, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "phi4-flash-tiny", "source": cfg["source"], "why": "tiny",
                             "file": "chipbench/configs/phi4-flash-tiny.json", "reduced": []})
    bench["workloads"].append({"name": CELL, "config": "phi4-flash-tiny", "traffic": "reason-tiny",
                               "chips": 1, "why": "tiny"})
    for m in bench["end_to_end"]:
        if m["name"] in ("gen_tok_per_s", "itl_p95_ms"):
            m["workloads"].append(CELL)
    with open(os.path.join(dest, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return dest


def test_the_cell_runs_through_the_harness_and_is_correct(copy):
    """Sound, the reading lies a sixth of the way to the limit (bfloat16 products and a
    bfloat16 cache on logits to 10: 6.4e-5 of 4e-4 on this seed, to 2.3e-4 on others)."""
    out = rehearse.run_cell(copy, CELL)
    assert out["correct"] is True and out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"setup_s", "gen_tok_per_s", "itl_p95_ms"}
    assert out["checks"]["served_gap_sq"]["value"] < out["checks"]["served_gap_sq"]["limit"] / 3


@pytest.mark.parametrize("fault", faults.FAULTS)
def test_a_planted_fault_is_not_correct(copy, fault):
    """Each reads 10 times the limit or more at this size (0.015 to 4 of 4e-4)."""
    patch = f"from chipbench.faults import phi4_flash as f\nf.plant({fault!r})"
    out = rehearse.run_cell(copy, CELL, patch=patch)
    assert out["failed"] == 0 and out["correct"] is False
    assert out["checks"]["served_gap_sq"]["value"] > 10 * out["checks"]["served_gap_sq"]["limit"]


def test_the_bfloat16_control_reads_over_the_limit(copy):
    """The plain reference with activations and state in bfloat16, judged on what a
    sound run served: 3 times the limit or more (1.5e-3 of 4e-4 on this seed; a net 64 wide
    with a bfloat16 cache leaves less room between the two than the published widths do)."""
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
    assert out["control_bf16"]["served_gap_sq"] > 3 * limit


def test_the_benchmark_names_the_cell_and_its_metrics():
    bench, cell, cfg, mix = harness.cell_of("phi4-mini-flash.reason-closed")
    assert cell["chips"] == 1 and mix["clients"] == cfg["max_in_flight"] == 64
    assert (mix["prompt"]["lo"], mix["prompt"]["hi"]) == (64, 512)
    assert (mix["output"]["lo"], mix["output"]["hi"]) == (512, 1536)
    per_layer = {m["name"] for m in bench["per_layer"] if cell["name"] in m.get("workloads", [])}
    assert {"selective_state_roofline", "selective_scan_roofline", "prefill_layer_position_share", "kv_read_window_share",
            "paged_attn_roofline", "paged_live_block_share", "prefill_ms_p50", "decode_step_mfu",
            "decode_step_mfu.itl", "decode_step_ms_p50", "device_idle_share.gen",
            "kv_pool_peak_share", "sampler_ordered_row_share"} <= per_layer
    assert "ssm_state_roofline" not in per_layer        # Mamba-2's kernel does not run here
    for name in per_layer:      # every metric has its file and its reader
        spec = harness.load_json("chipbench", "metrics", f"{name}.json")
        assert hasattr(harness.module("readers", spec["reader"]), "read")
    # the published config whole, at the top level as it is run: nothing is cut
    assert cfg["reduced"] == [] and all(cfg[k] == v for k, v in cfg["published"].items())
    conf = next(c for c in bench["configs"] if c["name"] == "phi4-mini-flash")
    assert conf["reduced"] == [] and conf["source"] == cfg["source"]
    # the pool holds every request to its last token, and every row has a slot
    longest = -(-(mix["prompt"]["hi"] + mix["output"]["hi"]) // 16)
    assert 64 * longest <= cfg["engine"]["kv_blocks"] - 1
    assert mix["prompt"]["hi"] + mix["output"]["hi"] <= cfg["engine"]["max_seq_len"] == cfg["max_len"]
    assert cfg["engine"]["state_slots"] == max(cfg["engine"]["decode_buckets"]) + 1
    assert cfg["sliding_window"] % cfg["engine"]["kv_block_size"] == 0


def test_phi4_flash_counts():
    cfg = cfg_of("phi4-mini-flash")
    assert counts.kinds(cfg) == {"mamba": 9, "window": 8, "full": 1, "gmu": 7, "cross": 7}
    per = counts.mixer_params(cfg)
    assert per["mamba"] == 2560 * 10240 + 5120 * 192 + 160 * 5120 + 5120 * 2560    # 41.1 M
    assert per["window"] == 3 * 2560 * 2560 and per["gmu"] == 2 * per["cross"] == 4 * 2560 * 2560
    body, head = counts.matmul_params(cfg)
    assert head == 2560 * 200064
    assert body == 32 * 3 * 2560 * 10240 + 9 * per["mamba"] + 9 * per["window"] + 7 * per["gmu"] + 7 * per["cross"]
    assert counts.n_params(cfg) / 1e9 == pytest.approx(3.8526, abs=0.0002)      # 7.70 GB in bfloat16
    assert counts.kv_bytes_per_token(cfg) == 2 * 1280 * 2
    assert counts.ssm_state_bytes(cfg, 64) == 64 * 9 * 2 * 5120 * 16 * 4        # 0.38 GB a step
    # 8 layers read the grown context, 8 a ring of at most 512; q in and out
    io = 16 * 2 * 3 * 2560 * 4
    assert counts.paged_attention_bytes(cfg, [100, 1100]) == 8 * 5120 * 1200 + 8 * 5120 * 612 + io
    one = counts.decode_flops(cfg, 1)
    assert one == 2 * (body + head) + 16 * 6 * 2560 + 9 * (7 * 5120 * 16 + 2 * 4 * 5120)
    assert counts.decode_flops(cfg, 513) - counts.decode_flops(cfg, 512) == 8 * 6 * 2560   # the window is full
    assert counts.decode_flops(cfg, 101) - one == 16 * 6 * 2560 * 100
    assert counts.prefill_flops(cfg, 1) == one
    # the upper 15 layers and the head run once: the lower 17 layers are 1864 M of the
    # 3851 M weights that a decoded token is multiplied through
    whole = 300 * counts.decode_flops(cfg, 150)
    assert 0.47 < counts.prefill_flops(cfg, 300) / whole < 0.50


def test_the_new_metrics_read_the_spans_and_fall_silent_without_them():
    """The three metrics this cell brings, on a view made by hand: two counters of the
    engine's spans and the prefill scan's share of the HBM roof; on a program that has
    neither the spans' new counts nor the kernel they read nothing and do not raise."""
    from chipbench.readers import scan_share, span_attr
    cfg = cfg_of("phi4-mini-flash")
    ta, lo = 5000.0, 17.0
    ring = [(ta + 0.10, ta + 0.12, "generate.prefill",
             {"tokens": 300, "bucket": 512, "positions": 512, "layer_positions": 17 * 512 + 15,
              "layer_positions_dense": 32 * 512}),
            (ta + 0.20, ta + 0.21, "generate.decode_step",
             {"live": 2, "bucket": 64, "window_read_tokens": 8 * 1024, "kv_read_tokens": 8 * 1024 + 8 * 3072})]
    dev = {"modules": [(lo + 0.10, lo + 0.115, "jit_fn(1)", "admit")],
           "ops": [(lo + 0.101, lo + 0.103, "%pallas.selective_scan.3 = f32[1,64,8,5120] custom-call(..)"),
                   (lo + 0.104, lo + 0.105, "%fusion.7 = f32[8] fusion(..)")]}
    view = {"trace": {"devices": [dev]}, "window": (lo, lo + 1), "host_window": (ta, ta + 1),
            "ring": ring, "records": {"t0": ta, "t_end": ta + 1}, "cfg": cfg, "counts": counts,
            "peaks": {"hbm_bytes_per_s": 819e9}}
    spec = lambda name: harness.load_json("chipbench", "metrics", f"{name}.json")["args"]  # noqa: E731
    assert span_attr.read(view, **spec("prefill_layer_position_share")) == pytest.approx(
        100 * (17 * 512 + 15) / (32 * 512))
    assert span_attr.read(view, **spec("kv_read_window_share")) == pytest.approx(25.0)
    moved = counts.selective_scan_bytes(cfg, 300)
    assert moved == 9 * 4 * (300 * (3 * 5120 + 32) + 5120 * 16)
    assert scan_share.read(view, **spec("selective_scan_roofline")) == pytest.approx(
        100 * moved / (0.002 * 819e9))
    # the parent's program: spans without the counts, no such operation
    old = dict(view, ring=[(s, e, n, {"tokens": 300, "live": 2, "bucket": 64}) for s, e, n, _ in ring],
               trace={"devices": [dict(dev, ops=dev["ops"][1:])]})
    assert span_attr.read(old, **spec("prefill_layer_position_share")) is None
    assert span_attr.read(old, **spec("kv_read_window_share")) is None
    assert scan_share.read(old, **spec("selective_scan_roofline")) is None
    from chipbench.counts import falcon_h1
    assert scan_share.read(dict(view, counts=falcon_h1), "selective_scan") is None
