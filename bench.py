"""Benchmark: ResNet-50 training throughput, images/sec/chip.

BASELINE.md metric #2 (single-chip leg of the north star). Synthetic
ImageNet-shaped data, pre-placed on device (the metric is compute
throughput; the input pipeline is benchmarked separately). Mixed
precision: bfloat16 compute with float32 master params — the
MXU-native configuration.

`BASELINE.json.published` is empty — no reference number exists, so
``vs_baseline`` is reported as 1.0 until a reference measurement lands
(BASELINE.md measurement protocol step 4).

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "images/sec/chip", "vs_baseline": N}
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np

_ROOT = os.path.dirname(os.path.abspath(__file__))


def main():
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.models.zoo import ResNet50

    on_tpu = jax.devices()[0].platform == "tpu"
    batch = 256 if on_tpu else 8     # 256 ≈ +15% over 128 on v5e
    hw = 224 if on_tpu else 64

    net = ResNet50(num_classes=1000, height=hw, width=hw,
                   compute_dtype="bfloat16").init()

    rng = np.random.RandomState(0)
    x = rng.randn(batch, hw, hw, 3).astype(np.float32)
    y = np.eye(1000, dtype=np.float32)[rng.randint(0, 1000, batch)]
    # device-resident batch: measure the train step, not the feed
    ds = DataSet(jax.device_put(jnp.asarray(x)),
                 jax.device_put(jnp.asarray(y)))

    steps = 60 if on_tpu else 3
    # fit_steps: `steps` iterations per dispatch (steps_per_execution),
    # removing the per-step host dispatch gap (~+13% at this shape)
    net.fit_steps(ds, steps)     # warmup (compile)
    jax.block_until_ready(net.params)
    float(net.score())

    from benchmarks.timing import median_throughput

    def run_once():
        net.fit_steps(ds, steps)
        jax.block_until_ready(net.params)
        # score() syncs on the final step's loss — guarantees the whole
        # dispatch chain actually executed before we stop the clock
        # (the sync lives OUTSIDE the assert: python -O must not
        # remove it)
        s = float(net.score())
        assert np.isfinite(s)

    stats = median_throughput(run_once, steps * batch,
                              n_trials=5 if on_tpu else 3)
    ips = stats["value"]
    line = {
        "metric": "resnet50_train_throughput"
                  + ("" if on_tpu else f"_cpu_proxy_{hw}px"),
        **stats,
        "unit": "images/sec/chip",
        "vs_baseline": 1.0,
    }
    # provenance stamp: schema version, git rev, jax version, device
    # kind/count, DL4J_TPU_* env — BENCH_r*.json trajectories are only
    # comparable when the rig that produced them is on record
    try:
        from deeplearning4j_tpu.common import diagnostics
        line["meta"] = diagnostics.bench_meta()
        # top-level proxy marker: a CPU-proxy round and a TPU round
        # are not comparable — check_bench_regression.py refuses to
        # diff across a flip of this flag
        line["meta"]["proxy"] = not on_tpu
    except Exception as e:
        print(f"meta block failed: {e!r}", file=sys.stderr)
    # Roofline evidence (BENCH_notes_r02.md): XLA cost analysis of the
    # optimized train step (shared helper; flops are a floor), run
    # through the automatic classifier (which roof binds, % of it).
    try:
        from benchmarks.cost_util import graph_step_cost
        from deeplearning4j_tpu.common import diagnostics
        flops, byts = graph_step_cost(net, x, y)
        step_s = batch / ips
        # peaks by device_kind; a device outside the table gets none
        peaks = diagnostics.device_peaks() or {}
        roof = diagnostics.roofline(
            flops, byts, step_s,
            peak_tflops=peaks.get("tflops"),
            peak_hbm_gbps=peaks.get("hbm_gbps"))
        # keep the historical top-level keys (r02+ trajectory) AND the
        # full classification
        line["tflops"] = round(roof["tflops"], 1)
        if peaks:
            line["pct_bf16_peak"] = roof["pct_compute_peak"]
            line["pct_hbm_peak"] = roof["pct_hbm_peak"]
        line["roofline"] = roof
    except Exception as e:
        print(f"roofline block failed: {e!r}", file=sys.stderr)
    # HBM attribution: where the bytes actually live after the run —
    # device allocator live/peak plus per-buffer accounting (params /
    # updater state / staging / activations+workspace residual)
    try:
        from deeplearning4j_tpu.common import diagnostics
        line["memory"] = diagnostics.memory_report(net)
    except Exception as e:
        print(f"memory block failed: {e!r}", file=sys.stderr)
    # Scaling-observatory breakdown: where the run's step time went
    # (data_wait / compute / collective / updater / host_sync /
    # checkpoint_stall) — phase means sum to ~the mean step time, so a
    # future throughput regression comes pre-attributed to a phase.
    try:
        from deeplearning4j_tpu.common import stepstats
        bd = stepstats.collector().summary()
        if bd.get("steps"):
            line["step_breakdown"] = bd
    except Exception as e:
        print(f"step-breakdown block failed: {e!r}", file=sys.stderr)
    # exercise the pod scaling harness's REAL clock path at n=1 (the
    # round-2 verdict asked that parallel/scaling.py time something
    # real before it is trusted on a pod); small shape — this checks
    # the machinery, not the headline number
    try:
        from deeplearning4j_tpu.datasets.dataset import DataSet as DS
        from deeplearning4j_tpu.models.zoo import LeNet
        from deeplearning4j_tpu.parallel.scaling import \
            measure_dp_scaling

        def _mk_batch(n):
            r = np.random.RandomState(1)
            return DS(r.randn(n, 28, 28, 1).astype(np.float32),
                      np.eye(10, dtype=np.float32)[
                          r.randint(0, 10, n)])

        sizes = (1,) if not on_tpu else tuple(sorted(
            {1, len(jax.devices())}))
        rep = measure_dp_scaling(
            lambda: LeNet(num_classes=10).init(), _mk_batch, sizes,
            per_chip_batch=64, steps=5, warmup=1)
        # clock-path CANARY, not a throughput: 5 LeNet steps are
        # dispatch-dominated (the old name scaling_n1_ips invited
        # misreading)
        line["scaling_harness_canary_ips"] = round(
            rep["throughput"][1], 1)
        # the ROADMAP item-2 `scaling` block: per-chip throughput and
        # efficiency at each mesh size vs the smallest-size baseline,
        # with the cross-host observatory's skew report when a
        # SharedTrainingMaster leader ran one (single host: zero skew)
        from deeplearning4j_tpu.common import stepstats
        line["scaling"] = stepstats.scaling_block(rep)
        # wire-cost context for the efficiency curve: what one step's
        # update exchange moves per replica at the largest mesh size
        from deeplearning4j_tpu.parallel import zero
        line["scaling"]["update_exchange"] = zero.exchange_report(
            LeNet(num_classes=10).init().params, max(sizes))
    except Exception as e:
        print(f"scaling-harness leg failed: {e!r}", file=sys.stderr)
    # CPU-proxy pipeline overhead, every round (regressions in the
    # host data-path software must be caught whatever the device
    # does). Subprocess on the CPU backend.
    try:
        env = {**os.environ, "JAX_PLATFORMS": "cpu"}
        out = subprocess.run(
            [sys.executable,
             os.path.join(_ROOT, "benchmarks", "bench_pipeline.py")],
            capture_output=True, text=True, timeout=900, env=env,
            cwd=_ROOT)
        if out.returncode != 0:
            raise RuntimeError(
                f"rc={out.returncode}: {out.stderr.strip()[-400:]}")
        for ln in out.stdout.strip().splitlines():
            if not ln.startswith("{"):
                continue              # tolerate library banners
            rec = json.loads(ln)
            if rec["metric"].startswith("input_pipeline_overhead"):
                line["pipeline_overhead_cpu_proxy_pct"] = rec["value"]
        if "pipeline_overhead_cpu_proxy_pct" not in line:
            print("pipeline-proxy leg: no overhead line in child "
                  "output", file=sys.stderr)
    except Exception as e:
        print(f"pipeline-proxy leg failed: {e!r}", file=sys.stderr)
    # Feeding-ladder leg: per-step input-pipeline stall under the
    # three feeding modes (sync / host-async / device-prefetch), so
    # BENCH_*.json rounds track feeding overhead alongside throughput.
    # CPU-proxy subprocess, like the pipeline leg above.
    try:
        env = {**os.environ, "JAX_PLATFORMS": "cpu"}
        out = subprocess.run(
            [sys.executable,
             os.path.join(_ROOT, "benchmarks",
                          "bench_input_pipeline.py")],
            capture_output=True, text=True, timeout=900, env=env,
            cwd=_ROOT)
        if out.returncode != 0:
            raise RuntimeError(
                f"rc={out.returncode}: {out.stderr.strip()[-400:]}")
        for ln in out.stdout.strip().splitlines():
            if not ln.startswith("{"):
                continue              # tolerate library banners
            rec = json.loads(ln)
            if rec["metric"] == "input_pipeline_stall_pct":
                line["input_pipeline_stall_pct"] = rec["value"]
                line["input_pipeline_stall_sync_pct"] = rec["sync_pct"]
                line["input_pipeline_stall_host_async_pct"] = \
                    rec["host_async_pct"]
        if "input_pipeline_stall_pct" not in line:
            print("feeding-ladder leg: no stall line in child output",
                  file=sys.stderr)
    except Exception as e:
        print(f"feeding-ladder leg failed: {e!r}", file=sys.stderr)
    # Serving leg: batcher latency percentiles vs batch window + the
    # warm/cold first-request gap (the shape-bucketed-warmup payoff).
    # CPU-proxy subprocess, like the pipeline legs above.
    try:
        env = {**os.environ, "JAX_PLATFORMS": "cpu"}
        out = subprocess.run(
            [sys.executable,
             os.path.join(_ROOT, "benchmarks", "bench_serving.py")],
            capture_output=True, text=True, timeout=900, env=env,
            cwd=_ROOT)
        if out.returncode != 0:
            raise RuntimeError(
                f"rc={out.returncode}: {out.stderr.strip()[-400:]}")
        for ln in out.stdout.strip().splitlines():
            if not ln.startswith("{"):
                continue              # tolerate library banners
            rec = json.loads(ln)
            if rec.get("metric") == "serving_latency":
                rec.pop("metric")
                line["serving"] = rec
        if "serving" not in line:
            print("serving leg: no latency line in child output",
                  file=sys.stderr)
    except Exception as e:
        print(f"serving leg failed: {e!r}", file=sys.stderr)
    # Generative leg: paged-KV decode goodput, streaming TTFT /
    # inter-token percentiles, pool occupancy vs shed rate, and the
    # paged-vs-dense decode-attention A/B. CPU-proxy subprocess, like
    # the serving leg above.
    try:
        env = {**os.environ, "JAX_PLATFORMS": "cpu"}
        out = subprocess.run(
            [sys.executable,
             os.path.join(_ROOT, "benchmarks", "bench_generative.py")],
            capture_output=True, text=True, timeout=900, env=env,
            cwd=_ROOT)
        if out.returncode != 0:
            raise RuntimeError(
                f"rc={out.returncode}: {out.stderr.strip()[-400:]}")
        for ln in out.stdout.strip().splitlines():
            if not ln.startswith("{"):
                continue              # tolerate library banners
            rec = json.loads(ln)
            if rec.get("metric") == "generative":
                rec.pop("metric")
                line["generative"] = rec
        if "generative" not in line:
            print("generative leg: no line in child output",
                  file=sys.stderr)
    except Exception as e:
        print(f"generative leg failed: {e!r}", file=sys.stderr)
    # Update-sharding leg: ZeRO-1 sharded vs dense exchange — per-chip
    # updater-state residency + step time, and the accumulation-window
    # micro-step times. CPU-proxy subprocess on the virtual 8-device
    # mesh, like the legs above.
    try:
        env = {**os.environ, "JAX_PLATFORMS": "cpu"}
        out = subprocess.run(
            [sys.executable,
             os.path.join(_ROOT, "benchmarks",
                          "bench_update_sharding.py")],
            capture_output=True, text=True, timeout=900, env=env,
            cwd=_ROOT)
        if out.returncode != 0:
            raise RuntimeError(
                f"rc={out.returncode}: {out.stderr.strip()[-400:]}")
        for ln in out.stdout.strip().splitlines():
            if not ln.startswith("{"):
                continue              # tolerate library banners
            rec = json.loads(ln)
            if rec.get("metric") == "update_sharding":
                rec.pop("metric")
                line["update_sharding"] = rec
        if "update_sharding" not in line:
            print("update-sharding leg: no line in child output",
                  file=sys.stderr)
    except Exception as e:
        print(f"update-sharding leg failed: {e!r}", file=sys.stderr)
    # FSDP leg: ZeRO-3 vs ZeRO-1 vs dense — per-chip param + updater-
    # state residency and step time, plus the fsdp accumulation-window
    # micro-step times. CPU-proxy subprocess on the virtual 8-device
    # mesh, like the legs above.
    try:
        env = {**os.environ, "JAX_PLATFORMS": "cpu"}
        out = subprocess.run(
            [sys.executable,
             os.path.join(_ROOT, "benchmarks", "bench_fsdp.py")],
            capture_output=True, text=True, timeout=900, env=env,
            cwd=_ROOT)
        if out.returncode != 0:
            raise RuntimeError(
                f"rc={out.returncode}: {out.stderr.strip()[-400:]}")
        for ln in out.stdout.strip().splitlines():
            if not ln.startswith("{"):
                continue              # tolerate library banners
            rec = json.loads(ln)
            if rec.get("metric") == "fsdp":
                rec.pop("metric")
                line["fsdp"] = rec
        if "fsdp" not in line:
            print("fsdp leg: no line in child output", file=sys.stderr)
    except Exception as e:
        print(f"fsdp leg failed: {e!r}", file=sys.stderr)
    # 2D-parallelism leg: (data x model) and (fsdp x model) training
    # modes vs dp-only — per-mode step time, per-axis update wire
    # bytes (the model axis must move zero), and per-chip residency.
    # CPU-proxy subprocess on the virtual 8-device mesh, like the
    # legs above.
    try:
        env = {**os.environ, "JAX_PLATFORMS": "cpu"}
        out = subprocess.run(
            [sys.executable,
             os.path.join(_ROOT, "benchmarks", "bench_2d.py")],
            capture_output=True, text=True, timeout=900, env=env,
            cwd=_ROOT)
        if out.returncode != 0:
            raise RuntimeError(
                f"rc={out.returncode}: {out.stderr.strip()[-400:]}")
        for ln in out.stdout.strip().splitlines():
            if not ln.startswith("{"):
                continue              # tolerate library banners
            rec = json.loads(ln)
            if rec.get("metric") == "scaling_2d":
                rec.pop("metric")
                line["scaling_2d"] = rec
        if "scaling_2d" not in line:
            print("2d leg: no line in child output", file=sys.stderr)
    except Exception as e:
        print(f"2d leg failed: {e!r}", file=sys.stderr)
    # Pipeline-parallelism leg: the promoted pp fit path — analytic
    # bubble-vs-n_micro sweep, gpipe-vs-1f1b peak activation
    # residency, and measured pp2 / pp2xdp2 step time + stage idle.
    # CPU-proxy subprocess on the virtual 8-device mesh, like the
    # legs above.
    try:
        env = {**os.environ, "JAX_PLATFORMS": "cpu"}
        out = subprocess.run(
            [sys.executable,
             os.path.join(_ROOT, "benchmarks", "bench_pipeline.py"),
             "--pp"],
            capture_output=True, text=True, timeout=900, env=env,
            cwd=_ROOT)
        if out.returncode != 0:
            raise RuntimeError(
                f"rc={out.returncode}: {out.stderr.strip()[-400:]}")
        for ln in out.stdout.strip().splitlines():
            if not ln.startswith("{"):
                continue              # tolerate library banners
            rec = json.loads(ln)
            if rec.get("metric") == "pipeline":
                rec.pop("metric")
                line["pipeline"] = rec
        if "pipeline" not in line:
            print("pipeline leg: no line in child output",
                  file=sys.stderr)
    except Exception as e:
        print(f"pipeline leg failed: {e!r}", file=sys.stderr)
    # Fault-tolerance leg: checkpoint step-loop stall (fully
    # synchronous vs deferred async snapshot) and warm-cache resume
    # latency — the costs the preemption/auto-resume machinery pays.
    # CPU-proxy subprocess, like the legs above.
    try:
        env = {**os.environ, "JAX_PLATFORMS": "cpu"}
        out = subprocess.run(
            [sys.executable,
             os.path.join(_ROOT, "benchmarks",
                          "bench_fault_tolerance.py")],
            capture_output=True, text=True, timeout=900, env=env,
            cwd=_ROOT)
        if out.returncode != 0:
            raise RuntimeError(
                f"rc={out.returncode}: {out.stderr.strip()[-400:]}")
        for ln in out.stdout.strip().splitlines():
            if not ln.startswith("{"):
                continue              # tolerate library banners
            rec = json.loads(ln)
            if rec.get("metric") == "fault_tolerance":
                rec.pop("metric")
                line["fault_tolerance"] = rec
        if "fault_tolerance" not in line:
            print("fault-tolerance leg: no line in child output",
                  file=sys.stderr)
    except Exception as e:
        print(f"fault-tolerance leg failed: {e!r}", file=sys.stderr)
    # Graph-optimizer leg: per-pass rewrite counts + fused-vs-unfused
    # imported-BERT step time, and the flash-vs-dense compiled temp
    # memory floor at a long-sequence shape. CPU-proxy subprocess,
    # like the legs above.
    try:
        env = {**os.environ, "JAX_PLATFORMS": "cpu"}
        out = subprocess.run(
            [sys.executable,
             os.path.join(_ROOT, "benchmarks", "bench_graphopt.py")],
            capture_output=True, text=True, timeout=900, env=env,
            cwd=_ROOT)
        if out.returncode != 0:
            raise RuntimeError(
                f"rc={out.returncode}: {out.stderr.strip()[-400:]}")
        for ln in out.stdout.strip().splitlines():
            if not ln.startswith("{"):
                continue              # tolerate library banners
            rec = json.loads(ln)
            if rec.get("metric") == "graph_optimizer":
                rec.pop("metric")
                line["graph_optimizer"] = rec
        if "graph_optimizer" not in line:
            print("graph-optimizer leg: no line in child output",
                  file=sys.stderr)
    except Exception as e:
        print(f"graph-optimizer leg failed: {e!r}", file=sys.stderr)
    # Conv-kernel leg: fused (DL4J_TPU_FUSED_CONV Pallas epilogue
    # family) vs unfused ResNet-bottleneck train step — step time,
    # compiled temp bytes, cost-analysis bytes, and pct_of_roof from
    # the roofline classifier. CPU-proxy subprocess (interpret-mode
    # kernels; the line carries meta.proxy).
    try:
        env = {**os.environ, "JAX_PLATFORMS": "cpu"}
        out = subprocess.run(
            [sys.executable,
             os.path.join(_ROOT, "benchmarks",
                          "bench_conv_kernels.py")],
            capture_output=True, text=True, timeout=900, env=env,
            cwd=_ROOT)
        if out.returncode != 0:
            raise RuntimeError(
                f"rc={out.returncode}: {out.stderr.strip()[-400:]}")
        for ln in out.stdout.strip().splitlines():
            if not ln.startswith("{"):
                continue              # tolerate library banners
            rec = json.loads(ln)
            if rec.get("metric") == "conv_kernels":
                rec.pop("metric")
                line["conv_kernels"] = rec
        if "conv_kernels" not in line:
            print("conv-kernel leg: no line in child output",
                  file=sys.stderr)
    except Exception as e:
        print(f"conv-kernel leg failed: {e!r}", file=sys.stderr)
    # Long-context leg: the 8192/16384/32768 attention train-step
    # ladder (collapses to one seq-512 proxy point off-TPU), each
    # entry stamped with the kernel-select auto decision for its
    # nominal TPU shape.
    try:
        env = {**os.environ, "JAX_PLATFORMS": "cpu"}
        out = subprocess.run(
            [sys.executable,
             os.path.join(_ROOT, "benchmarks", "bench_longcontext.py"),
             "--sweep"],
            capture_output=True, text=True, timeout=900, env=env,
            cwd=_ROOT)
        if out.returncode != 0:
            raise RuntimeError(
                f"rc={out.returncode}: {out.stderr.strip()[-400:]}")
        for ln in out.stdout.strip().splitlines():
            if not ln.startswith("{"):
                continue              # tolerate library banners
            rec = json.loads(ln)
            if rec.get("metric") == "longcontext":
                rec.pop("metric")
                line["longcontext"] = rec
        if "longcontext" not in line:
            print("long-context leg: no line in child output",
                  file=sys.stderr)
    except Exception as e:
        print(f"long-context leg failed: {e!r}", file=sys.stderr)
    # Layer-attribution leg: per-layer time/flops/bytes roofline with
    # the kernel-select decision join, on ResNet-50 + BERT-tiny — the
    # top-k layers each round so a regression comes pre-attributed to
    # a layer. CPU-proxy subprocess, like the legs above.
    try:
        env = {**os.environ, "JAX_PLATFORMS": "cpu"}
        out = subprocess.run(
            [sys.executable,
             os.path.join(_ROOT, "benchmarks",
                          "bench_layer_attribution.py")],
            capture_output=True, text=True, timeout=900, env=env,
            cwd=_ROOT)
        if out.returncode != 0:
            raise RuntimeError(
                f"rc={out.returncode}: {out.stderr.strip()[-400:]}")
        for ln in out.stdout.strip().splitlines():
            if not ln.startswith("{"):
                continue              # tolerate library banners
            rec = json.loads(ln)
            if rec.get("metric") == "layer_attribution":
                rec.pop("metric")
                line["layer_attribution"] = rec
        if "layer_attribution" not in line:
            print("layer-attribution leg: no line in child output",
                  file=sys.stderr)
    except Exception as e:
        print(f"layer-attribution leg failed: {e!r}", file=sys.stderr)
    # Telemetry panel: the registry the run's hot paths recorded into
    # (train-step histogram, compile-cache counters, prefetch stats
    # when an iterator fed) — the same data /metrics would serve.
    try:
        from deeplearning4j_tpu.common.telemetry import MetricsRegistry
        reg = MetricsRegistry.get()
        if reg.enabled:
            line["telemetry"] = reg.summary()
    except Exception as e:
        print(f"telemetry leg failed: {e!r}", file=sys.stderr)
    print(json.dumps(line))


if __name__ == "__main__":
    main()
