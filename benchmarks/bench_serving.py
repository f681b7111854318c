"""Serving-path benchmark: continuous vs fixed-window batching,
SLO-adaptive admission, the zero-copy serialization tax, and
sharded-model residency.

Measures the ISSUE-15 claims the way an operator would check them:

- **Open-loop Poisson A/B** — ``flush_policy="continuous"`` vs the
  fixed ``batch_window_ms`` seed at EQUAL offered load. Arrivals are
  pre-scheduled from an exponential inter-arrival draw and latency is
  measured from the *scheduled* arrival (open loop: a slow server
  cannot slow the clients down and hide its own queueing). Reports
  p50/p95/p99 plus goodput (completions inside the SLO per second).
- **Admission static vs SLO-adaptive** — saturating closed-loop
  clients against a deliberately slow model: the static budget admits
  everything and lets queueing blow the SLO; the adaptive budget
  sheds early so admitted requests stay inside it.
- **Serialization tax** — per-request JSON encode/decode vs the
  zero-copy ``.npy`` codec (``npy_view`` / ``npy_header``).
- **Sharded residency** — dense vs ``mode="fsdp"`` per-chip resident
  parameter bytes on the virtual 8-device mesh, with the bitwise
  output check.
- **warm vs cold first request** — the shape-bucketed warmup payoff.
- **Serving observatory overhead** — the same HTTP predict loop with
  per-request tracing ON (default) vs OFF: ``trace_overhead_pct``
  must stay ≤ 1% at p50, the cost of leaving the observatory on in
  production.

Bench honesty: every latency figure here is measured in-process, from
the scheduled arrival to the completed future — no network hop is in
it and none is added. ``meta.proxy`` marks CPU-proxy rounds.

Prints ONE JSON line (``bench.py`` folds it into its ``serving``
block):

  {"metric": "serving_latency", "policies": {...}, "admission": {...},
   "serialization": {...}, "residency": {...},
   "first_request_ms": {...}, "meta": {...}}

Run: JAX_PLATFORMS=cpu python benchmarks/bench_serving.py
"""
from __future__ import annotations

import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=8")

import jax  # noqa: E402

if os.environ.get("DL4J_TPU_TEST_PLATFORM", "cpu") == "cpu":
    jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402

BUCKETS = (8, 32)
#: open-loop offered load and sample size per policy
RATE_RPS = 250.0
N_REQS = 300
SLO_MS = 25.0
#: the fixed-window seed's knob (the PR-3 default)
WINDOW_MS = 2.0


def _net():
    from deeplearning4j_tpu.activations import Activation
    from deeplearning4j_tpu.learning import Adam
    from deeplearning4j_tpu.lossfunctions import LossFunction
    from deeplearning4j_tpu.nn import (InputType, MultiLayerNetwork,
                                       NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.conf.layers import (DenseLayer,
                                                   OutputLayer)
    return MultiLayerNetwork(
        (NeuralNetConfiguration.Builder().seed(7).updater(Adam(1e-2))
         .list()
         .layer(DenseLayer(n_out=16, activation=Activation.RELU))
         .layer(OutputLayer(n_out=4,
                            loss_function=LossFunction.MCXENT,
                            activation=Activation.SOFTMAX))
         .set_input_type(InputType.feed_forward(8)).build())).init()


def _batcher(net, policy: str, mesh=None, mode: str = "dense"):
    from deeplearning4j_tpu.serving.batcher import ServingBatcher
    return ServingBatcher(net, BUCKETS, mesh, name="bench",
                          batch_window_ms=WINDOW_MS,
                          flush_policy=policy, mode=mode)


def _pcts(ms: np.ndarray) -> dict:
    return {f"p{q}_ms": round(float(np.percentile(ms, q)), 2)
            for q in (50, 95, 99)}


def _open_loop(batcher, rate_rps: float, n: int, seed: int) -> dict:
    """Submit ``n`` requests on a pre-scheduled Poisson arrival clock;
    latency counts from the SCHEDULED arrival, so dispatcher or server
    lag shows up as latency instead of silently thinning the load."""
    rng = np.random.RandomState(seed)
    sched = np.cumsum(rng.exponential(1.0 / rate_rps, size=n))
    xs = [rng.randn(1, 8).astype(np.float32) for _ in range(n)]
    lats, lock = [], threading.Lock()
    pairs = []
    t0 = time.perf_counter()
    for i in range(n):
        target = t0 + sched[i]
        delay = target - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        fut = batcher.submit(xs[i])

        def done(f, t=target):
            with lock:
                lats.append(time.perf_counter() - t)
        fut.add_done_callback(done)
        pairs.append(fut)
    for f in pairs:
        f.result(timeout=120)
    wall = time.perf_counter() - t0
    ms = np.asarray(sorted(lats)) * 1e3
    good = int(np.sum(ms <= SLO_MS))
    return {"offered_rps": round(rate_rps, 1),
            "goodput_rps": round(good / wall, 1),
            "slo_ms": SLO_MS,
            "in_slo_pct": round(100.0 * good / n, 1),
            **_pcts(ms)}


def _policy_leg(line: dict):
    """Continuous vs fixed-window at equal offered load — the
    tentpole A/B. Occupancy comes from the policy-labelled serving
    histogram the flushes feed."""
    from deeplearning4j_tpu.common import telemetry
    policies = {}
    for policy in ("window", "continuous"):
        net = _net()
        b = _batcher(net, policy)
        b.warmup((8,))
        policies[policy] = _open_loop(b, RATE_RPS, N_REQS,
                                      seed=17)
        b.shutdown()
        h = telemetry.histogram("dl4j_serving_batch_occupancy")
        cnt = h.count_of(model="bench", policy=policy)
        if cnt:
            policies[policy]["occupancy_mean"] = round(
                h.sum_of(model="bench", policy=policy) / cnt, 3)
    line["policies"] = policies


class _SlowModel:
    """A generic model whose forward costs ~1 ms/row — enough work
    that saturating clients actually queue on the CPU proxy."""

    def output(self, x):
        x = np.asarray(x)
        time.sleep(0.001 * x.shape[0])
        return x[:, :1]


def _admission_leg(line: dict):
    """Static budget vs SLO-adaptive budget under the same saturating
    closed loop: goodput counts only completions INSIDE the SLO, so
    admitting everything and queueing past the SLO loses."""
    from deeplearning4j_tpu.serving.admission import (
        AdmissionController, ShedError)
    slo_ms = 40.0
    out = {}
    for label, slo in (("static", None), ("adaptive", slo_ms)):
        adm = AdmissionController(max_queue=48, latency_slo_ms=slo,
                                  adapt_window=16)
        b = _batcher(_SlowModel(), "continuous")
        lats, shed = [], [0]
        lock = threading.Lock()

        def client(n_reqs, adm=adm, b=b, lats=lats, shed=shed):
            x = np.zeros((1, 8), np.float32)
            for _ in range(n_reqs):
                t0 = time.perf_counter()
                try:
                    with adm.track("bench"):
                        b.submit(x).result(timeout=30)
                except ShedError:
                    with lock:
                        shed[0] += 1
                    continue
                dt = time.perf_counter() - t0
                adm.observe_total("bench", dt)
                with lock:
                    lats.append(dt)

        threads = [threading.Thread(target=client, args=(12,))
                   for _ in range(24)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        b.shutdown()
        ms = np.asarray(lats) * 1e3
        good = int(np.sum(ms <= slo_ms))
        out[label] = {
            "slo_ms": slo_ms,
            "completed": len(lats),
            "shed": shed[0],
            "p95_ms": round(float(np.percentile(ms, 95)), 2),
            "goodput_rps": round(good / wall, 1),
            "final_budget": adm.budget("bench"),
        }
    line["admission"] = out


def _serialization_leg(line: dict):
    """The per-request tax the zero-copy ``.npy`` path removes: JSON
    encode+decode of a request-sized tensor vs npy_header + a
    frombuffer view."""
    from deeplearning4j_tpu.common.httputil import npy_header, npy_view
    x = np.random.RandomState(3).randn(32, 256).astype(np.float32)
    reps = 50

    t0 = time.perf_counter()
    for _ in range(reps):
        body = json.dumps({"inputs": x.tolist()}).encode()
        np.asarray(json.loads(body.decode())["inputs"],
                   dtype=np.float32)
    json_ms = (time.perf_counter() - t0) / reps * 1e3

    raw = npy_header(x) + memoryview(x).cast("B").tobytes()
    t0 = time.perf_counter()
    for _ in range(reps):
        parts = [npy_header(x), memoryview(x)]      # response side
        sum(memoryview(p).cast("B").nbytes for p in parts)
        npy_view(raw)                               # request side
    npy_ms = (time.perf_counter() - t0) / reps * 1e3

    line["serialization"] = {
        "tensor_bytes": int(x.nbytes),
        "json_roundtrip_ms": round(json_ms, 3),
        "npy_roundtrip_ms": round(npy_ms, 3),
        "speedup": round(json_ms / max(npy_ms, 1e-9), 1),
    }


def _observatory_leg(line: dict):
    """The serving-observatory overhead claim: the same HTTP predict
    loop with request tracing ON (the default — trace ids, phase
    spans, exemplars, flight-recorder records) vs forced OFF
    (``DL4J_TPU_REQUEST_TRACE=0`` equivalent, via the in-process
    override). Tracing is supposed to be default-on in production, so
    the p50 overhead must stay ≤ 1%."""
    import urllib.request

    from deeplearning4j_tpu.common import tracectx
    from deeplearning4j_tpu.serving.registry import ModelRegistry
    from deeplearning4j_tpu.serving.server import InferenceServer

    registry = ModelRegistry(default_buckets=BUCKETS)
    registry.register("bench-obs", _net(), warmup_shape=(8,))
    srv = InferenceServer(registry).start(0)
    body = json.dumps(
        {"inputs": np.zeros((1, 8), np.float32).tolist()}).encode()
    url = f"{srv.url}/v1/models/bench-obs:predict"
    n = 150

    def loop() -> np.ndarray:
        lats = []
        for _ in range(n):
            req = urllib.request.Request(
                url, data=body,
                headers={"Content-Type": "application/json"})
            t0 = time.perf_counter()
            with urllib.request.urlopen(req) as resp:
                resp.read()
            lats.append(time.perf_counter() - t0)
        return np.asarray(lats) * 1e3

    try:
        # warm both paths before timing (HTTP keep-alive, caches)
        tracectx.set_enabled(True)
        loop()
        p50_on = float(np.percentile(loop(), 50))
        tracectx.set_enabled(False)
        loop()
        p50_off = float(np.percentile(loop(), 50))
    finally:
        tracectx.set_enabled(None)
        srv.stop(drain=False)
        registry.shutdown()
    line["serving_observatory"] = {
        "n": n,
        "p50_on_ms": round(p50_on, 3),
        "p50_off_ms": round(p50_off, 3),
        "trace_overhead_pct": round(
            100.0 * (p50_on - p50_off) / max(p50_off, 1e-9), 2),
    }


def _residency_leg(line: dict):
    """Dense vs fsdp per-chip resident parameter bytes, plus the
    bitwise output check that makes the savings claim honest."""
    if len(jax.devices()) < 8:
        print("residency leg skipped: needs the 8-device mesh",
              file=sys.stderr)
        return
    from deeplearning4j_tpu.parallel.mesh import make_mesh
    from deeplearning4j_tpu.serving.residency import \
        resident_param_bytes
    mesh = make_mesh({"data": 8}, jax.devices()[:8])
    x = np.random.RandomState(5).randn(4, 8).astype(np.float32)

    net = _net()
    ref = np.asarray(net.output(x))
    dense_bytes = resident_param_bytes(net.params)

    b = _batcher(net, "continuous", mesh=mesh, mode="fsdp")
    b.warmup((8,))
    out = np.asarray(b.submit(x).result(timeout=120))
    fsdp_bytes = resident_param_bytes(b.params)
    b.shutdown()

    line["residency"] = {
        "dense_bytes_per_chip": int(dense_bytes),
        "fsdp_bytes_per_chip": int(fsdp_bytes),
        "savings_ratio": round(dense_bytes / max(fsdp_bytes, 1), 2),
        "bitwise_equal": bool(np.array_equal(out, ref)),
    }


def main():
    from deeplearning4j_tpu.common import telemetry

    on_proxy = jax.default_backend() == "cpu"
    line = {"metric": "serving_latency",
            "buckets": list(BUCKETS),
            "meta": {"proxy": on_proxy}}

    # warm vs cold first request (the warmup payoff)
    net = _net()
    cold = _batcher(net, "continuous")
    t0 = time.perf_counter()
    cold.submit(np.zeros((1, 8), np.float32)).result(timeout=120)
    cold_ms = (time.perf_counter() - t0) * 1e3
    cold.shutdown()
    warm = _batcher(net, "continuous")
    warm.warmup((8,))
    t0 = time.perf_counter()
    warm.submit(np.zeros((1, 8), np.float32)).result(timeout=120)
    warm_ms = (time.perf_counter() - t0) * 1e3
    warm.shutdown()
    line["first_request_ms"] = {"cold": round(cold_ms, 2),
                                "warm": round(warm_ms, 2)}

    _policy_leg(line)
    _admission_leg(line)
    _serialization_leg(line)
    try:
        _observatory_leg(line)
    except Exception as e:
        print(f"observatory leg failed: {e!r}", file=sys.stderr)
    try:
        _residency_leg(line)
    except Exception as e:
        print(f"residency leg failed: {e!r}", file=sys.stderr)

    # the live registry's own quantile estimate (bucket-resolution)
    # for the aggregate queue stage — what /metrics scrapers see
    h = telemetry.histogram("dl4j_serving_latency_seconds")
    line["queue_p95_ms_registry"] = round(
        h.quantile(0.95, model="bench", stage="queue") * 1e3, 2)
    print(json.dumps(line))


if __name__ == "__main__":
    main()
