"""Long-context attention TRAIN-step A/B (round-3 verdict ask #3).

The r3 forward tuning gave the Pallas flash kernel 4.2x over its
untuned self at seq 8k — but training pays fwd+bwd, and the flash
BACKWARD is jax.vjp through the blockwise-attention reference
(parallel/sequence.py _flash_bwd), not a hand kernel.  This benchmark
measures what long-context TRAINING actually costs per step for:

  xla    — dense jnp attention (materializes [b,h,t,t] scores)
  flash  — Pallas forward + blockwise-autodiff backward (current)
  block  — blockwise_attention fwd+bwd (pure lax.scan, no Pallas)

Each leg times grad(loss) of one attention call at [b, h, t, d],
median of n_trials, synced on the loss scalar.  Prints ONE JSON line
per leg.  Use --seq for a single point, or --sweep for the committed
8192 / 16384 / 32768 ladder (one JSON summary line,
``{"metric": "longcontext"}``, that bench.py folds in) — the shapes
where the kernel-select auto rung (t_k >= 4096 + HBM headroom,
ops/attention_pallas.py) picks flash on its own.  Off-TPU the sweep
collapses to one seq-512 proxy point, but each entry still records
the analytic TPU-platform ladder decision for its nominal shape.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def xla_attention(q, k, v):
    d = q.shape[-1]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / jnp.sqrt(
        jnp.asarray(d, q.dtype))
    p = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


def main(seq=8192, batch=1, heads=8, d=128, dtype="bfloat16",
         trials=5, steps=10, legs=("xla", "flash", "block")):
    from benchmarks.timing import median_throughput
    from deeplearning4j_tpu.parallel.sequence import (
        blockwise_attention, flash_attention)

    on_tpu = jax.devices()[0].platform == "tpu"
    if not on_tpu:
        seq, trials = 512, 2
    dt = jnp.dtype(dtype)
    rng = np.random.RandomState(0)
    shape = (batch, heads, seq, d)
    q = jax.device_put(jnp.asarray(
        rng.randn(*shape) * 0.1, dt))
    k = jax.device_put(jnp.asarray(rng.randn(*shape) * 0.1, dt))
    v = jax.device_put(jnp.asarray(rng.randn(*shape) * 0.1, dt))

    fns = {
        "xla": xla_attention,
        "flash": functools.partial(flash_attention, causal=False),
        "block": lambda q, k, v: blockwise_attention(q, k, v),
    }
    results = {}
    for leg in legs:
        fn = fns[leg]

        @jax.jit
        def train_step(q, k, v, fn=fn):
            def loss(q, k, v):
                o = fn(q, k, v)
                return jnp.sum(o.astype(jnp.float32) ** 2)

            l, grads = jax.value_and_grad(loss, argnums=(0, 1, 2))(
                q, k, v)
            return l, grads

        try:
            l, g = train_step(q, k, v)          # compile
            jax.block_until_ready(g)
            assert np.isfinite(float(l))

            def run_once():
                # dispatch `steps` independent steps, sync ONCE on the
                # last loss: a per-step float() sync is a host round
                # trip per step and would blur the kernel time
                l = None
                for _ in range(steps):
                    l, _g = train_step(q, k, v)
                assert np.isfinite(float(l))

            stats = median_throughput(run_once, steps,
                                      n_trials=trials)
            step_ms = 1000.0 / stats["value"]
            line = {"metric": f"longcontext_attn_train_step_{leg}",
                    "value": round(step_ms, 2), "unit": "ms/step",
                    "seq": seq, "batch": batch, "heads": heads,
                    "d": d, "dtype": dtype,
                    "min_ms": round(1000.0 / stats["max"], 2),
                    "max_ms": round(1000.0 / stats["min"], 2),
                    "steps_per_trial": steps,
                    "n_trials": stats["n_trials"]}
        except Exception as e:                   # OOM legs are data too
            line = {"metric": f"longcontext_attn_train_step_{leg}",
                    "value": None, "seq": seq,
                    "error": f"{type(e).__name__}: {str(e)[:160]}"}
        results[leg] = line
        print(json.dumps(line))
    return results


def sweep(seqs=(8192, 16384, 32768), batch=1, heads=8, d=128,
          dtype="bfloat16", trials=5, steps=10,
          legs=("xla", "flash")):
    """The committed long-context ladder: one measured point per seq
    (xla-OOM legs are data too), each stamped with the decision the
    kernel-select auto rung would take for that shape ON TPU — the
    evidence that the t_k >= 4096 heuristic fires exactly where the
    measured win is."""
    import jax

    from deeplearning4j_tpu.ops.attention_pallas import \
        select_attention_backend

    on_tpu = jax.devices()[0].platform == "tpu"
    out = {"metric": "longcontext", "batch": batch, "heads": heads,
           "d": d, "dtype": dtype, "proxy": not on_tpu, "sweep": []}
    for seq in (seqs if on_tpu else seqs[:1]):
        res = main(seq=seq, batch=batch, heads=heads, d=d,
                   dtype=dtype, trials=trials, steps=steps, legs=legs)
        entry = {"seq": seq if on_tpu else 512,
                 "legs": {leg: {k: v for k, v in line.items()
                               if k != "metric"}
                          for leg, line in res.items()}}
        qk = (batch, heads, seq, d)
        backend, reason = select_attention_backend(
            qk, qk, platform="tpu", override=None,
            use_env_override=False)
        entry["auto_backend_on_tpu"] = backend
        entry["auto_reason"] = reason
        out["sweep"].append(entry)
    print(json.dumps(out))
    return out


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq", type=int, default=8192)
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--d", type=int, default=128)
    ap.add_argument("--trials", type=int, default=5)
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--legs", default="xla,flash,block")
    ap.add_argument("--sweep", action="store_true",
                    help="run the 8192/16384/32768 ladder and print "
                         "the one-line summary bench.py folds in")
    a = ap.parse_args()
    if a.sweep:
        sweep(batch=a.batch, heads=a.heads, d=a.d, trials=a.trials,
              steps=a.steps,
              legs=tuple(l for l in a.legs.split(",")
                         if l != "block"))
    else:
        main(seq=a.seq, batch=a.batch, heads=a.heads, d=a.d,
             trials=a.trials, steps=a.steps,
             legs=tuple(a.legs.split(",")))
