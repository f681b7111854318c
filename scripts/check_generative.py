#!/usr/bin/env python
"""CI gate: the generative-serving conformance contract.

Holds the ISSUE-16 acceptance bar on the CPU backend (the Pallas
paged-decode kernel runs in interpret mode — same arithmetic, no
accelerator needed):

1. **Paged == dense** — greedy decode through the full engine with
   the paged kernel FORCED (``DL4J_TPU_PAGED_ATTENTION=1``) must
   produce token-for-token the same ids as
   ``DecoderLM.reference_decode`` (a full dense re-forward per step,
   no KV cache at all) for a spread of prompts and lengths.
2. **Zero post-warmup retraces across churn** — staggered submits
   with different max_tokens make sequences join and leave the
   decode batch mid-flight; the engine's RetraceGuard must record
   ZERO new signatures after warmup (continuous batching never
   recompiles in steady state).
3. **Pool accounting reconciles** — every block (and every
   recurrent-state slot) allocated during the churn must be back on
   the free list afterwards, and
   ``diagnostics.memory_report()`` must carry the pool as its own
   resident class with bytes equal to the ``dl4j_kv_pool_bytes``
   gauge.

The gate runs once for each model class with the serving contract:
``DecoderLM``, ``FalconH1LM`` (grouped-query attention beside a
state-space mixer: KV blocks and a state slot a sequence),
``Phi4FlashLM`` (one shared K/V layer, window rings and Mamba-1 state
in the slot) and ``MiMoV2LM`` (K and V of different widths in pool and
rings, a sink in the window layers, sparse experts of which a share is
held: the routing counts ride each step's pull).

Usage: JAX_PLATFORMS=cpu python scripts/check_generative.py
Exit 0 = gate holds, 1 = a clause failed.
"""
from __future__ import annotations

import os
import sys
import threading

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
# clause 1 forces the paged kernel everywhere the ladder consults the
# env override — set before any engine import
os.environ["DL4J_TPU_PAGED_ATTENTION"] = "1"

import numpy as np  # noqa: E402


def _decoder():
    from deeplearning4j_tpu.models.decoder import (DecoderConfig,
                                                   DecoderLM)
    from deeplearning4j_tpu.serving.kvcache import KVBlockPool
    conf = DecoderConfig.tiny()
    model = DecoderLM(conf)
    return model, KVBlockPool(conf.n_layers, 64, 8, conf.n_heads,
                              conf.head_dim, name="gate")


def _falcon_h1():
    """The hybrid model class: fewer KV heads than query heads, and a
    recurrent-state slot a sequence beside its blocks."""
    from deeplearning4j_tpu.models.falcon_h1 import (FalconH1Config,
                                                     FalconH1LM)
    from deeplearning4j_tpu.serving.kvcache import KVBlockPool
    conf = FalconH1Config()
    model = FalconH1LM(conf)
    return model, KVBlockPool(conf.n_layers, 64, 8, conf.n_kv_heads,
                              conf.head_dim, name="gate",
                              state=model.state_shapes(), state_slots=9)


def _phi4_flash():
    """The decoder-decoder: one K/V layer that grows, read by every
    cross layer; window rings and Mamba-1 state in the slot."""
    from deeplearning4j_tpu.models.phi4_flash import (Phi4FlashConfig,
                                                      Phi4FlashLM)
    from deeplearning4j_tpu.serving.kvcache import KVBlockPool
    conf = Phi4FlashConfig()
    model = Phi4FlashLM(conf)
    return model, KVBlockPool(model.kv_layers, 64, 8, conf.n_kv_heads,
                              conf.head_dim, name="gate",
                              state=model.state_shapes(), state_slots=9)


def _mimo_v2():
    """Full and window layers with K heads wider than V heads, and
    expert layers that compute a quarter of the experts' part."""
    from deeplearning4j_tpu.models.mimo_v2 import MiMoV2Config, MiMoV2LM
    from deeplearning4j_tpu.serving.kvcache import KVBlockPool
    conf = MiMoV2Config()
    model = MiMoV2LM(conf)
    return model, KVBlockPool(model.kv_layers, 64, 8, conf.n_kv_heads,
                              conf.head_dim, v_head_dim=conf.v_head_dim,
                              name="gate", state=model.state_shapes(),
                              state_slots=9)


def main() -> int:
    import gc
    failures = []
    for build in (_decoder, _falcon_h1, _phi4_flash, _mimo_v2):
        model, pool = build()
        label = type(model).__name__
        print(f"== {label}")
        failures += [f"{label}: {f}" for f in gate(model, pool)]
        del model, pool
        gc.collect()            # the next pool reconciles alone
    if failures:
        for f in failures:
            print(f"FAIL: {f}")
        return 1
    print("OK: paged decode is token-equal to the dense reference, "
          "churn never retraced, and the pool reconciles")
    return 0


def gate(model, pool) -> list:
    """The three clauses for one model class; returns the failures."""
    from deeplearning4j_tpu.common import diagnostics
    from deeplearning4j_tpu.serving.generative import DecodeEngine
    from deeplearning4j_tpu.serving.kvcache import _bytes_gauge

    failures = []
    conf = model.conf
    params = model.init()
    eng = DecodeEngine(model, params, pool, name="gate",
                       prompt_buckets=(16,), decode_buckets=(4, 8),
                       max_seq_len=64, paged=True)
    eng.warmup()

    # -- clause 1: paged greedy == dense full-re-forward reference ----
    rng = np.random.default_rng(7)
    cases = [(rng.integers(2, 60, size=n), m)
             for n, m in ((3, 10), (8, 6), (13, 12), (1, 4))]
    for prompt, max_tokens in cases:
        got = list(eng.submit(prompt, max_tokens))
        ref = list(model.reference_decode(params, prompt, max_tokens,
                                          eos_id=conf.eos_id))
        if got != ref:
            failures.append(
                f"paged != dense for prompt len {prompt.size}: "
                f"{got} vs {ref}")
    print(f"clause 1: {len(cases)} paged-vs-reference greedy decodes "
          f"compared")

    # -- clause 2: join/leave churn, zero retraces --------------------
    streams, toks = [], {}
    lock = threading.Lock()

    def client(i):
        prompt = rng.integers(2, 60, size=int(rng.integers(2, 14)))
        s = eng.submit(prompt, int(rng.integers(2, 10)),
                       temperature=0.8 if i % 3 else 0.0,
                       top_k=20 if i % 2 else 0)
        got = list(s)
        with lock:
            toks[i] = (got, s.reason)

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(12)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if len(toks) != 12 or any(not g for g, _ in toks.values()):
        failures.append(f"churn: {len(toks)}/12 sequences completed, "
                        f"some empty: {toks}")
    retraces = eng.retraces_since_warmup()
    if retraces != 0:
        failures.append(f"{retraces} post-warmup retraces across "
                        f"join/leave churn (must be 0)")
    print(f"clause 2: 12 churning sequences decoded, "
          f"{retraces} post-warmup retraces")

    # -- clause 3: pool accounting reconciles -------------------------
    if pool.live_blocks != 0 or pool.live_sequences != 0:
        failures.append(
            f"pool leak after churn: {pool.live_blocks} blocks / "
            f"{pool.live_sequences} sequences still live")
    if pool.free_slots != pool.usable_slots:
        failures.append(
            f"state-slot leak after churn: {pool.free_slots} of "
            f"{pool.usable_slots} slots free")
    report = diagnostics.memory_report()
    pools = report.get("kv_pools", [])
    if not pools:
        failures.append("memory_report carries no kv_pools resident "
                        "class")
    gauge_bytes = _bytes_gauge().value(pool="gate")
    if pools and int(gauge_bytes) != int(report["kv_pool_bytes"]):
        failures.append(
            f"kv_pool_bytes gauge ({gauge_bytes}) != memory_report "
            f"({report['kv_pool_bytes']})")
    if report["kv_pool_bytes"] <= 0:
        failures.append("kv pool accounts zero bytes")
    print(f"clause 3: pool fully freed, {report['kv_pool_bytes']} "
          f"bytes reconciled with the gauge")
    eng.shutdown()
    return failures


if __name__ == "__main__":
    sys.exit(main())
