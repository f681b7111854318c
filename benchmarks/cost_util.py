"""Shared XLA cost-analysis helper for the benchmark scripts.

One home for the cost-analysis read of the compiled ComputationGraph
train step (lowered through ``net.lower_train_step``), so bench.py and
benchmarks/profile_resnet.py cannot drift apart. Byte accounting from
XLA cost analysis is accurate on TPU (it predicts the ResNet-50 step
time at the HBM roofline to ~1%); flops for dots inside fusions
undercount, so treat the returned flops as a floor
(BENCH_notes_r02.md).
"""
from __future__ import annotations

from deeplearning4j_tpu.common.diagnostics import DEVICE_PEAKS

# TPU v5e single-chip peaks, read from the one table
# (common.diagnostics.DEVICE_PEAKS, keyed by device_kind, with source)
V5E_BF16_PEAK_TFLOPS = DEVICE_PEAKS["TPU v5 lite"]["tflops"]
V5E_HBM_GBPS = DEVICE_PEAKS["TPU v5 lite"]["hbm_gbps"]


def graph_step_cost(net, x, y) -> tuple[float, float]:
    """(flops, bytes accessed) of one optimized ComputationGraph train
    step. ``net`` must be initialized with its train step built (one
    ``fit`` call suffices)."""
    ca = net.lower_train_step(x, y).compile().cost_analysis()
    if isinstance(ca, list):
        ca = ca[0]
    return ca.get("flops", 0.0), ca.get("bytes accessed", 0.0)
