"""Secondary benchmark: BERT-base MLM pretraining throughput
(BASELINE config #4). bf16 + per-layer FULL remat + XLA fused
attention, batch 128 x seq 128, fit_steps fori-loop protocol — the
late-r4 sweep's winner (BENCH_notes_r04.md: once the per-step
dispatch+sync tax is amortized by the fori loop, SMALL batches win —
b128 49.2% of bf16 peak vs b1024's 43.9%; the earlier "batch is the
MFU lever" finding was partly that tax). Full remat still beats
dots_saveable/no-remat at every batch. XLA fused attention measured
1.33x over the Pallas flash kernel at BERT shapes and 1.8x at seq
512 (kernel-backward era re-measurement); flash remains the
long-context/CP path (crossover ~2k tokens).

Prints ONE JSON line: {"metric": "bert_mlm_train_throughput", ...}.
CLI flags reproduce the published A/B legs:
  --seq 512 --batch 12 --max-predictions 76      (seq-512 leg — the
      r5 sweep's winner: full remat + b12 = 140.6k tokens/s, 41.9%
      bf16 peak; see BENCH_notes_r05.md for the remat x batch grid.
      At seq 512 SMALL batches win — attention memory is O(b*t^2))
  --flash                                        (Pallas kernel leg)
"""
from __future__ import annotations

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
from benchmarks.cost_util import V5E_BF16_PEAK_TFLOPS  # noqa: E402


def main(batch=128, seq=128, steps=60, max_predictions=32,
         flash=False, remat="full", fused_qkv=False):
    from deeplearning4j_tpu.learning import Adam
    from deeplearning4j_tpu.models.bert import Bert, BertConfig

    on_tpu = jax.devices()[0].platform == "tpu"
    if not on_tpu:
        batch, seq, steps = 4, 128, 2
        conf = BertConfig.tiny(compute_dtype="bfloat16",
                               hidden_dropout_prob=0.0,
                               attention_probs_dropout_prob=0.0)
    else:
        # use_flash_attention=False by default: at seq 128 (and 512)
        # XLA's fused attention beats the Pallas flash kernel on v5e —
        # 109k vs 82k tokens/s measured (BENCH_notes_r03.md). The
        # flash kernel's domain is LONG sequences (ring-attention CP),
        # not BERT-base shapes.
        # remat policy from the r4 MFU sweep (BENCH_notes_r04.md):
        # "full" recomputes the whole layer, "dots" saves matmul
        # outputs, "none" stores everything (needs a smaller batch)
        conf = BertConfig(compute_dtype="bfloat16",
                          remat=False if remat == "none" else remat,
                          use_flash_attention=flash,
                          hidden_dropout_prob=0.0,
                          attention_probs_dropout_prob=0.0,
                          max_predictions_per_seq=max_predictions,
                          fused_qkv=fused_qkv,
                          max_position_embeddings=max(512, seq))

    model = Bert(conf, Adam(1e-4)).init()
    rng = np.random.RandomState(0)
    ids = rng.randint(0, conf.vocab_size, (batch, seq)).astype(np.int32)
    mlm_labels = np.where(rng.rand(batch, seq) < 0.15,
                          rng.randint(0, conf.vocab_size, (batch, seq)),
                          -1).astype(np.int32)
    batch_d = {"input_ids": jax.device_put(jnp.asarray(ids)),
               "mlm_labels": jax.device_put(jnp.asarray(mlm_labels))}

    model.fit_steps(batch_d, steps)   # compile; syncs on final loss

    from benchmarks.timing import median_throughput

    def run_once():
        # ONE fori-loop dispatch + one loss sync per trial: the
        # per-step host dispatch+sync tax is fixed, so amortizing it
        # measures device-limited throughput (the char-RNN protocol,
        # BENCH_notes_r04.md)
        loss = model.fit_steps(batch_d, steps)
        assert np.isfinite(loss)

    stats = median_throughput(run_once, steps * batch * seq,
                              n_trials=5 if on_tpu else 3)
    best = stats["value"]
    line = {"metric": "bert_mlm_train_throughput"
                      + ("" if on_tpu else "_cpu_proxy"),
            **stats,
            "unit": "tokens/sec/chip"}

    # Analytic matmul FLOPs (XLA's cost_analysis undercounts dot FLOPs
    # inside fusions and cannot see the Pallas flash custom call —
    # see BENCH_notes_r02.md). fwd multiply-adds x2, train = 3x fwd
    # (+1 fwd again under remat, counted separately as recompute).
    H, I = conf.hidden_size, conf.intermediate_size
    L, V = conf.num_hidden_layers, conf.vocab_size
    k = conf.max_predictions_per_seq or seq
    per_layer = 4 * 2 * H * H + 2 * 2 * H * I + 4 * seq * H
    head = (2 * H * H + 2 * H * V) * (k / seq)
    fwd_per_token = L * per_layer + head
    train_flops_per_token = 3 * fwd_per_token
    tf = best * train_flops_per_token / 1e12
    line["tflops_analytic"] = round(tf, 1)
    if on_tpu:
        line["pct_bf16_peak"] = round(100 * tf / V5E_BF16_PEAK_TFLOPS, 1)
    print(json.dumps(line))


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--max-predictions", type=int, default=32)
    ap.add_argument("--flash", action="store_true",
                    help="use the Pallas flash-attention kernel "
                         "instead of XLA fused attention")
    ap.add_argument("--fused-qkv", action="store_true",
                    help="q/k/v as one [H,3H] GEMM (A/B flag)")
    ap.add_argument("--remat", default="full",
                    choices=["full", "dots", "none"],
                    help="activation rematerialization policy")
    a = ap.parse_args()
    main(batch=a.batch, seq=a.seq, steps=a.steps,
         max_predictions=a.max_predictions, flash=a.flash,
         remat=a.remat, fused_qkv=a.fused_qkv)
