"""Serving benchmark (r4 verdict Weak #4: ParallelInference had never
been measured). Reference role: org.deeplearning4j.parallelism.
ParallelInference exists for exactly this — request batching for
throughput without unbounded latency.

Legs (each printed as one JSON line):
  resnet50_serving_latency     — single-request (b=1) p50/p95/p99 ms
  resnet50_serving_throughput  — SEQUENTIAL large-batch img/s
  resnet50_serving_batched     — BATCHED mode: many b=1 requests
                                 aggregated, batch_limit sweep
  bert_imported_serving        — the S6-imported BERT-base served via
                                 SameDiff.output: b=1 latency
                                 percentiles + large-batch tokens/s
The per-request legs include the host->device transfer of the
request; the device-resident leg takes it out.
"""
from __future__ import annotations

import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def _percentiles(times_s):
    a = np.asarray(times_s) * 1e3
    return {"p50_ms": round(float(np.percentile(a, 50)), 2),
            "p95_ms": round(float(np.percentile(a, 95)), 2),
            "p99_ms": round(float(np.percentile(a, 99)), 2),
            "n": len(a)}


def bench_resnet(on_tpu, n_lat=100):
    from deeplearning4j_tpu.models.zoo import ResNet50
    from deeplearning4j_tpu.parallel.inference import (InferenceMode,
                                                       ParallelInference)
    hw = 224 if on_tpu else 64
    kw = {} if on_tpu else {"STAGES": ((1, 8), (1, 16))}
    net = ResNet50(num_classes=1000, height=hw, width=hw,
                   compute_dtype="bfloat16", **kw).init()
    pi = (ParallelInference.Builder(net)
          .inference_mode(InferenceMode.BATCHED)
          .batch_limit(32).build())
    rng = np.random.RandomState(0)
    one = rng.randn(1, hw, hw, 3).astype(np.float32)

    pi.output(one)                       # compile b=1
    times = []
    for _ in range(n_lat if on_tpu else 10):
        t0 = time.perf_counter()
        pi.output(one)                   # np.asarray inside = sync
        times.append(time.perf_counter() - t0)
    print(json.dumps({"metric": "resnet50_serving_latency_b1",
                      "unit": "ms", **_percentiles(times)}))

    big_n = 256 if on_tpu else 16
    big = rng.randn(big_n, hw, hw, 3).astype(np.float32)
    pi.output(big)                       # compile big batch
    t0 = time.perf_counter()
    trials = 5 if on_tpu else 2
    for _ in range(trials):
        pi.output(big)
    dt = time.perf_counter() - t0
    print(json.dumps({"metric": "resnet50_serving_throughput",
                      "value": round(trials * big_n / dt, 1),
                      "unit": "images/sec/chip", "batch": big_n,
                      "note": "wire-inclusive (host->device transfer "
                              "per request)"}))

    # device-resident leg: the CHIP's serving ceiling — input already
    # on device, time the jitted forward alone (what a co-located
    # host sees, plus ~0.1 ms dispatch)
    import jax.numpy as jnp
    xd = jax.device_put(jnp.asarray(big, jnp.bfloat16))
    np.asarray(pi._fwd(net.params, net.states, xd))   # warm
    t0 = time.perf_counter()
    for _ in range(trials):
        out = pi._fwd(net.params, net.states, xd)
    np.asarray(out)                      # one final sync
    dt = time.perf_counter() - t0
    print(json.dumps({"metric":
                      "resnet50_serving_throughput_device_resident",
                      "value": round(trials * big_n / dt, 1),
                      "unit": "images/sec/chip", "batch": big_n}))

    reqs = [rng.randn(1, hw, hw, 3).astype(np.float32)
            for _ in range(big_n)]
    sweep = {}
    for bl in (8, 32, 128, 256) if on_tpu else (4, 16):
        pi.batch_limit = bl
        pi.output_batched(reqs[:bl])     # compile this window size
        t0 = time.perf_counter()
        out = pi.output_batched(reqs)
        dt = time.perf_counter() - t0
        assert len(out) == len(reqs)
        sweep[bl] = round(len(reqs) / dt, 1)
    print(json.dumps({"metric": "resnet50_serving_batched_reqs_per_s",
                      "unit": "requests/sec (b=1 each)",
                      "by_batch_limit": sweep}))

    # async observable path: concurrent submits through the batching
    # worker, latency under load + sustained req/s per window setting
    pi.batch_limit = 32
    for window_ms in (2.0, 10.0) if on_tpu else (5.0,):
        pi.batch_window_ms = window_ms
        futs = [pi.submit(r) for r in reqs[:8]]   # warm worker+compile
        [f.result(timeout=300) for f in futs]
        t0 = time.perf_counter()
        lat = []

        def one(r):
            s = time.perf_counter()
            pi.submit(r).result(timeout=300)
            lat.append(time.perf_counter() - s)

        import concurrent.futures as cf
        with cf.ThreadPoolExecutor(16) as ex:
            list(ex.map(one, reqs))
        dt = time.perf_counter() - t0
        print(json.dumps({
            "metric": "resnet50_serving_async_submit",
            "window_ms": window_ms,
            "reqs_per_s": round(len(reqs) / dt, 1),
            **_percentiles(lat)}))
    pi.shutdown()


def bench_bert_imported(on_tpu, n_lat=50):
    from deeplearning4j_tpu.learning import Adam
    from benchmarks.tf_bert_builder import (build_frozen_bert,
                                            import_and_attach_mlm)
    if on_tpu:
        seq, vocab, hidden, heads, layers, inter = \
            128, 30522, 768, 12, 12, 3072
    else:
        seq, vocab, hidden, heads, layers, inter = 16, 50, 16, 2, 2, 32
    # a frozen GraphDef bakes its batch dim into reshape consts, so
    # the b=1 latency leg and the large-batch throughput leg each
    # import at their own batch
    def import_at(b):
        gd, _ = build_frozen_bert(seq, b, vocab=vocab, hidden=hidden,
                                  heads=heads, layers=layers,
                                  intermediate=inter)
        sd, _ = import_and_attach_mlm(gd, b, seq, vocab=vocab,
                                      hidden=hidden,
                                      updater=Adam(1e-4))
        return sd

    rng = np.random.RandomState(0)

    def feeds(b):
        return {"ids": rng.randint(0, vocab, (b, seq), dtype=np.int32),
                "seg": np.zeros((b, seq), np.int32),
                "mask": np.ones((b, seq), np.int32)}
    def cls_var(sd_model, b):
        """Serve the CLS vector [b, H], not the full [b, T, H] hidden
        states — a realistic serving head; the full tensor would ship
        ~50 MB back across the wire per request and measure only the
        link."""
        out_var = ("encoder_out"
                   if sd_model.has_variable("encoder_out") else
                   [n for n in sd_model.vars if "Identity" in n][0])
        v = sd_model._op("slice", [sd_model.get_variable(out_var)],
                         {"begin": [0, 0, 0], "size": [b, 1, hidden]})
        return v.name

    sd = import_at(1)
    cv = cls_var(sd, 1)
    one = feeds(1)
    sd.output(one, [cv])                 # compile b=1
    times = []
    for _ in range(n_lat if on_tpu else 5):
        t0 = time.perf_counter()
        np.asarray(sd.output(one, [cv])[cv])
        times.append(time.perf_counter() - t0)
    print(json.dumps({"metric": "bert_imported_serving_latency_b1",
                      "seq": seq, "unit": "ms",
                      **_percentiles(times)}))

    b = 128 if on_tpu else 4
    sd = import_at(b)
    cv = cls_var(sd, b)
    big = feeds(b)
    sd.output(big, [cv])                 # compile big batch
    trials = 5 if on_tpu else 2
    t0 = time.perf_counter()
    for _ in range(trials):
        np.asarray(sd.output(big, [cv])[cv])
    dt = time.perf_counter() - t0
    print(json.dumps({
        "metric": "bert_imported_serving_throughput",
        "value": round(trials * b * seq / dt, 1),
        "unit": "tokens/sec/chip", "batch": b, "seq": seq,
        "served_output": "CLS vector [b, hidden]"}))


def main():
    on_tpu = jax.devices()[0].platform == "tpu"
    bench_resnet(on_tpu)
    bench_bert_imported(on_tpu)


if __name__ == "__main__":
    main()
