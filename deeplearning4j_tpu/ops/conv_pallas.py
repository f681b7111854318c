"""Fused conv/BN/ReLU epilogue kernels — the Pallas conv family.

Reference parity: ``CudnnConvolutionHelper`` with
``cudnnConvolutionBiasActivationForward`` (SURVEY.md D9; the cuDNN
playbook of PAPERS.md 1410.0759 fuses the conv epilogue into the
matmul's output tiles).  BENCH_r05 puts the ResNet-50 step at 93.5%
of the HBM roofline but only 29.3% of bf16 peak: bytes, not flops,
are the step time, and the profiler attributes the gap to the conv
path — XLA lowers conv → bias/BN scale-shift → ReLU as separate
elementwise fusions that re-read the conv result from HBM.

Three kernels close those round-trips:

  * **epilogue** — ``y = act(x·scale + shift)`` with per-channel f32
    coefficients, tiled ``[bm, C]`` (the bn_pallas block policy).
    One read, one write; serves conv bias+activation, BN inference
    (scale/shift folded from running stats), and the training-mode
    BN normalize.  Backward is a single fused pass producing
    ``dx = dy·act′·scale`` plus the ``Σdy·act′`` / ``Σdy·act′·x``
    channel reductions (dshift/dscale) — no re-read.
  * **channel stats** — one-pass per-channel ``Σx`` / ``Σx²`` with
    f32 accumulation, so training-mode BN derives mean/var from ONE
    read of the conv output instead of XLA's separate reduction
    fusions; composes with the existing bn_pallas fused backward
    (``bn_forward_math`` routes its statistics here when selected).
  * **matmul epilogue** — pointwise (1×1, stride 1) convs ARE
    matmuls; the MXU matmul kernel applies bias+activation in the
    output tile before it ever reaches HBM (the ResNet-50 bottleneck
    stages are 1×1-dominated).

Dispatch runs through the unified ``ops/kernel_select.py`` ladder
(kernel families ``conv_epilogue`` / ``bn_fwd``, both riding the
``DL4J_TPU_FUSED_CONV`` tri-state gate): structural gates — dtype,
sublane channel alignment, streamable activation (relu/identity),
training vs inference BN — demote to the dense lowering with a
counted reason; unset, the auto rung picks XLA's own lowering on
every platform, because no chip run shows a whole program faster
with these kernels (PERF.md section 6, PR 33: the ``pallas_call``
border costs a relayout each way and the fusions XLA makes across
it); ``=1`` forces them on.  Off-TPU the kernels run in Pallas
interpret mode, so the f64 gradient checks exercise the SAME code
path the chip runs (the bn_pallas.py pattern).
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from deeplearning4j_tpu.common import telemetry
from deeplearning4j_tpu.ops import kernel_select
from deeplearning4j_tpu.ops.bn_pallas import (BN_TPU_READING, _block_rows,
                                              auto_rung)

#: activations the epilogue kernels stream (relu as a max against the
#: zero of the accumulator dtype; identity as a pure FMA)
STREAMABLE_ACTIVATIONS = ("relu", "identity")
#: what the chip said of whole programs with the family on
#: (``scripts/probe_conv_epilogue.py``; PERF.md section 6, PR 33):
#: ResNet-50 forward-only at b256 13714.9 img/s without it and 3070.1
#: with it, a VGG16 ``fit`` step at b32 728.2 and 433.7 samples/s, an
#: AlexNet one at b256 15818.8 and 7716.5
CONV_TPU_READING = ("XLA's lowering 4.47x the kernel's on ResNet-50 b256 "
                    "forward, 1.68x on a VGG16 fit step, PERF.md §6 PR 33")
#: MXU lane width — the pointwise-matmul path requires both contracted
#: and output channels to tile it exactly
MXU_LANE = 128

_fused_steps = telemetry.counter(
    "dl4j_conv_fused_steps_total",
    "fused conv-family kernel sites traced into compiled programs, "
    "by site (conv / conv_matmul / bn_train / bn_infer); counts "
    "dispatches at trace time, not per executed step")


# ---------------------------------------------------------------------------
# selection (structural gate -> override -> auto, via kernel_select)
# ---------------------------------------------------------------------------
def _family_structural(shape, dtype, platform) -> Optional[str]:
    """The structural gate shared by every conv-family kernel: a
    demotion reason, or None when the site is admissible."""
    if len(shape) < 2:
        return f"rank {len(shape)} not supported"
    dt = jnp.dtype(dtype)
    if not jnp.issubdtype(dt, jnp.floating):
        return f"dtype {dt.name} is not floating"
    if dt == jnp.dtype(jnp.float64) and platform == "tpu":
        return "f64 is not supported on tpu"
    c = int(shape[-1])
    if c % 8 != 0:
        return f"channels {c} not sublane-aligned (C % 8 != 0)"
    return None


def select_conv_epilogue(out_shape, dtype, act_name: str, *,
                         has_epilogue: bool = True,
                         platform: Optional[str] = None,
                         override=None, use_env_override: bool = True,
                         record: bool = True) -> kernel_select.Selection:
    """Ladder decision for a conv-epilogue site (conv bias+activation,
    or inference-mode BN's folded scale/shift+activation).
    ``platform``/``override`` exist for tests — they default to the
    live device and the DL4J_TPU_FUSED_CONV tri-state."""
    if platform is None:
        platform = kernel_select.platform()
    if not has_epilogue:
        structural = "no epilogue to fuse (no bias, identity activation)"
    elif act_name not in STREAMABLE_ACTIVATIONS:
        structural = f"activation '{act_name}' is not streamable"
    else:
        structural = _family_structural(out_shape, dtype, platform)
    if override is None and use_env_override:
        override = kernel_select.gate_override("conv_epilogue")
    return kernel_select.select(
        "conv_epilogue", structural=structural,
        auto=lambda: auto_rung(platform, CONV_TPU_READING),
        override=override, use_env_override=False, record=record)


def select_bn_forward(shape, dtype, *, training: bool,
                      platform: Optional[str] = None,
                      override=None, use_env_override: bool = True,
                      record: bool = True) -> kernel_select.Selection:
    """Ladder decision for the training-mode BN forward (one-pass
    channel stats + fused normalize). Inference-mode BN has no
    batch-stats pass — it is an epilogue site — so asking for the
    stats kernel outside training is a structural demotion."""
    if platform is None:
        platform = kernel_select.platform()
    if not training:
        structural = ("inference-mode BN folds into the epilogue "
                      "(no batch-stats pass)")
    else:
        structural = _family_structural(shape, dtype, platform)
    if override is None and use_env_override:
        override = kernel_select.gate_override("bn_fwd")
    return kernel_select.select(
        "bn_fwd", structural=structural,
        auto=lambda: auto_rung(platform, BN_TPU_READING),
        override=override, use_env_override=False, record=record)


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------
def _epilogue_kernel(x_ref, coef_ref, y_ref, *, act, acc_t):
    x = x_ref[...].astype(acc_t)
    y = x * coef_ref[0:1, :] + coef_ref[1:2, :]
    if act == "relu":
        y = jnp.maximum(y, 0)
    y_ref[...] = y.astype(y_ref.dtype)


def _epilogue_bwd_kernel(x_ref, dy_ref, coef_ref, dx_ref, acc_ref, *,
                         act, M, bm, acc_t):
    i = pl.program_id(0)
    x = x_ref[...].astype(acc_t)
    dy = dy_ref[...].astype(acc_t)
    a = coef_ref[0:1, :]
    b = coef_ref[1:2, :]
    if act == "relu":
        g = jnp.where((x * a + b) > 0, dy, 0)
    else:
        g = dy
    rows = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    valid = (i * bm + rows) < M
    g = jnp.where(valid, g, 0)
    dx_ref[...] = (g * a).astype(dx_ref.dtype)
    # mask the PRODUCT too: padded x rows hold garbage (0·NaN = NaN)
    part = jnp.concatenate(
        [jnp.sum(g, axis=0, keepdims=True),
         jnp.sum(jnp.where(valid, g * x, 0), axis=0, keepdims=True)],
        axis=0)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += part


def _stats_kernel(x_ref, acc_ref, *, M, bm, acc_t):
    i = pl.program_id(0)
    x = x_ref[...].astype(acc_t)
    rows = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    valid = (i * bm + rows) < M
    part = jnp.concatenate(
        [jnp.sum(jnp.where(valid, x, 0), axis=0, keepdims=True),
         jnp.sum(jnp.where(valid, x * x, 0), axis=0, keepdims=True)],
        axis=0)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += part


def _matmul_epilogue_kernel(x_ref, w_ref, bias_ref, y_ref, *, act,
                            acc_t):
    z = jnp.dot(x_ref[...], w_ref[...], preferred_element_type=acc_t)
    y = z + bias_ref[...].astype(acc_t)
    if act == "relu":
        y = jnp.maximum(y, 0)
    y_ref[...] = y.astype(y_ref.dtype)


# ---------------------------------------------------------------------------
# raw launchers (shared by the custom_vjp forward/backward rules)
# ---------------------------------------------------------------------------
def _acc_type(x):
    return jnp.promote_types(x.dtype, jnp.float32)


def _epilogue_apply(x, scale, shift, act):
    # kernel-site annotation: non-dl4j prefix so the tag nests inside
    # the enclosing layer's dl4j.<layer> attribution scope
    with jax.named_scope("pallas.conv_epilogue"):
        return _epilogue_apply_raw(x, scale, shift, act)


def _epilogue_apply_raw(x, scale, shift, act):
    acc_t = _acc_type(x)
    C = x.shape[-1]
    M = x.size // C
    bm = _block_rows(M, C)
    coef = jnp.stack([jnp.broadcast_to(scale, (C,)).astype(acc_t),
                      jnp.broadcast_to(shift, (C,)).astype(acc_t)])
    y2d = pl.pallas_call(
        partial(_epilogue_kernel, act=act, acc_t=acc_t),
        grid=(pl.cdiv(M, bm),),
        in_specs=[pl.BlockSpec((bm, C), lambda i: (i, 0)),
                  pl.BlockSpec((2, C), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((bm, C), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((M, C), x.dtype),
        interpret=kernel_select.interpret_mode(),
    )(x.reshape(M, C), coef)
    return y2d.reshape(x.shape)


def _epilogue_backward(x, dy, scale, shift, act):
    acc_t = _acc_type(x)
    C = x.shape[-1]
    M = x.size // C
    bm = _block_rows(M, C)
    coef = jnp.stack([jnp.broadcast_to(scale, (C,)).astype(acc_t),
                      jnp.broadcast_to(shift, (C,)).astype(acc_t)])
    dx2d, acc = pl.pallas_call(
        partial(_epilogue_bwd_kernel, act=act, M=M, bm=bm, acc_t=acc_t),
        grid=(pl.cdiv(M, bm),),
        in_specs=[pl.BlockSpec((bm, C), lambda i: (i, 0)),
                  pl.BlockSpec((bm, C), lambda i: (i, 0)),
                  pl.BlockSpec((2, C), lambda i: (0, 0))],
        out_specs=[pl.BlockSpec((bm, C), lambda i: (i, 0)),
                   pl.BlockSpec((2, C), lambda i: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((M, C), x.dtype),
                   jax.ShapeDtypeStruct((2, C), acc_t)],
        interpret=kernel_select.interpret_mode(),
    )(x.reshape(M, C), dy.reshape(M, C), coef)
    # acc[0] = Σ dy·act′ (dshift), acc[1] = Σ dy·act′·x (dscale)
    return dx2d.reshape(x.shape), acc[1], acc[0]


def _channel_sums(x2d, acc_t):
    M, C = x2d.shape
    bm = _block_rows(M, C)
    # kernel-site annotation, as on its neighbours pallas.conv_epilogue
    # and pallas.bn_bwd: the forward's statistics pass is one name in a
    # trace, not one per enclosing layer
    with jax.named_scope("pallas.bn_stats"):
        return pl.pallas_call(
            partial(_stats_kernel, M=M, bm=bm, acc_t=acc_t),
            grid=(pl.cdiv(M, bm),),
            in_specs=[pl.BlockSpec((bm, C), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((2, C), lambda i: (0, 0)),
            out_shape=jax.ShapeDtypeStruct((2, C), acc_t),
            interpret=kernel_select.interpret_mode(),
        )(x2d)


def _matmul_epilogue(x2d, w2d, bias, act):
    acc_t = _acc_type(x2d)
    M, K = x2d.shape
    N = w2d.shape[-1]
    bm = min(128, max(8, ((M + 7) // 8) * 8))
    bn = MXU_LANE
    bias2d = jnp.broadcast_to(bias, (N,)).reshape(1, N)
    return pl.pallas_call(
        partial(_matmul_epilogue_kernel, act=act, acc_t=acc_t),
        grid=(pl.cdiv(M, bm), pl.cdiv(N, bn)),
        in_specs=[pl.BlockSpec((bm, K), lambda i, j: (i, 0)),
                  pl.BlockSpec((K, bn), lambda i, j: (0, j)),
                  pl.BlockSpec((1, bn), lambda i, j: (0, j))],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), x2d.dtype),
        interpret=kernel_select.interpret_mode(),
    )(x2d, w2d, bias2d)


# ---------------------------------------------------------------------------
# differentiable building blocks
# ---------------------------------------------------------------------------
@partial(jax.custom_vjp, nondiff_argnums=(3,))
def scale_shift_act(x, scale, shift, act: str):
    """``y = act(x·scale + shift)`` with per-channel (last-axis)
    coefficients, one fused read/write pass.  The epilogue shared by
    conv bias+activation, inference-mode BN, and the training-mode BN
    normalize.  Backward is the matching one-pass kernel:
    ``dx = dy·act′·scale`` plus the dscale/dshift reductions."""
    return _epilogue_apply(x, scale, shift, act)


def _ssa_fwd(x, scale, shift, act):
    return _epilogue_apply(x, scale, shift, act), (x, scale, shift)


def _ssa_bwd(act, res, dy):
    x, scale, shift = res
    dx, dscale, dshift = _epilogue_backward(x, dy, scale, shift, act)
    return (dx, dscale.astype(scale.dtype), dshift.astype(shift.dtype))


scale_shift_act.defvjp(_ssa_fwd, _ssa_bwd)


@jax.custom_vjp
def channel_stats(x):
    """Per-channel ``(mean, var)`` over every leading axis in ONE pass
    — Σx and Σx² accumulate in the same read (f32 accumulation for
    sub-f32 inputs), so training-mode BN stops re-reading the conv
    output for its statistics.  Differentiable: the backward is the
    per-channel FMA ``dx = x·(2·dvar/M) + (dmean − 2·mean·dvar)/M``,
    lowered through the same epilogue kernel."""
    return _channel_stats_impl(x)


def _channel_stats_impl(x):
    acc_t = _acc_type(x)
    C = x.shape[-1]
    M = x.size // C
    acc = _channel_sums(x.reshape(M, C), acc_t)
    mean = acc[0] / M
    var = jnp.maximum(acc[1] / M - jax.lax.square(mean), 0.0)
    return mean, var


def _cs_fwd(x):
    mean, var = _channel_stats_impl(x)
    return (mean, var), (x, mean)


def _cs_bwd(res, cts):
    dmean, dvar = cts
    x, mean = res
    acc_t = _acc_type(x)
    inv_m = 1.0 / (x.size // x.shape[-1])
    dv = dvar.astype(acc_t)
    scale = 2.0 * dv * inv_m
    shift = (dmean.astype(acc_t) - 2.0 * mean * dv) * inv_m
    return (_epilogue_apply(x, scale, shift, "identity"),)


channel_stats.defvjp(_cs_fwd, _cs_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def matmul_bias_act(x2d, w2d, bias, act: str):
    """``y = act(x @ w + bias)`` with the epilogue applied in the MXU
    output tile before it reaches HBM — the pointwise-conv lowering.
    Backward recovers the relu mask from the saved OUTPUT (``y > 0``
    ⟺ pre-activation > 0 when scale ≡ 1), so the pre-activation is
    never written to HBM."""
    return _matmul_epilogue(x2d, w2d, bias, act)


def _mba_fwd(x2d, w2d, bias, act):
    y = _matmul_epilogue(x2d, w2d, bias, act)
    return y, (x2d, w2d, bias, y)


def _mba_bwd(act, res, dy):
    x2d, w2d, bias, y = res
    acc_t = _acc_type(x2d)
    g = jnp.where(y > 0, dy, 0) if act == "relu" else dy
    dx = jnp.dot(g, w2d.T,
                 preferred_element_type=acc_t).astype(x2d.dtype)
    dw = jnp.dot(x2d.T, g,
                 preferred_element_type=acc_t).astype(w2d.dtype)
    db = jnp.sum(g.astype(acc_t), axis=0).astype(bias.dtype)
    return dx, dw, db


matmul_bias_act.defvjp(_mba_fwd, _mba_bwd)


# ---------------------------------------------------------------------------
# layer entry points
# ---------------------------------------------------------------------------
def _is_pointwise(w_shape, window_strides, rhs_dilation, padding):
    spatial = w_shape[:-2]
    if any(int(k) != 1 for k in spatial):
        return False
    if any(int(s) != 1 for s in window_strides):
        return False
    if any(int(d) != 1 for d in rhs_dilation):
        return False
    if isinstance(padding, str):
        return True              # SAME == VALID == no pad for 1×…×1
    return all(int(lo) == 0 and int(hi) == 0 for lo, hi in padding)


def conv_forward(x, w, *, window_strides, padding, rhs_dilation,
                 dimension_numbers, bias=None, activation=None):
    """THE conv-family call site: ``conv_general_dilated`` plus its
    bias/activation epilogue, with the epilogue emitted inside Pallas
    output tiles when the ``conv_epilogue`` ladder admits the site —
    otherwise the exact dense lowering the layers always used.
    Conv1D/2D/3D all route here (channels-last dimension numbers), so
    the dispatch logic lives in one place instead of per-rank copies."""
    from deeplearning4j_tpu.activations import Activation
    act = activation if activation is not None else Activation.IDENTITY
    act_name = act.value
    n_out = int(w.shape[-1])
    out_shape = tuple(x.shape[:-1]) + (n_out,)

    def dense():
        z = jax.lax.conv_general_dilated(
            x, w, window_strides=window_strides, padding=padding,
            rhs_dilation=rhs_dilation,
            dimension_numbers=dimension_numbers)
        if bias is not None:
            z = z + bias
        return act(z)

    has_epilogue = bias is not None or act_name != "identity"
    sel = select_conv_epilogue(out_shape, x.dtype, act_name,
                               has_epilogue=has_epilogue)
    if not sel.fused:
        return dense()
    acc_t = _acc_type(x)
    shift = bias if bias is not None else jnp.zeros((n_out,), acc_t)
    c_in = int(w.shape[-2])
    if _is_pointwise(w.shape, window_strides, rhs_dilation, padding) \
            and c_in % MXU_LANE == 0 and n_out % MXU_LANE == 0:
        # a 1×…×1 stride-1 conv IS a [M, C_in] × [C_in, C_out] matmul:
        # run it on the MXU kernel and apply the epilogue in the
        # output tile, before the result ever reaches HBM
        _fused_steps.inc(site="conv_matmul")
        y2d = matmul_bias_act(x.reshape(-1, c_in),
                              w.reshape(c_in, n_out), shift, act_name)
        return y2d.reshape(out_shape)
    _fused_steps.inc(site="conv")
    z = jax.lax.conv_general_dilated(
        x, w, window_strides=window_strides, padding=padding,
        rhs_dilation=rhs_dilation, dimension_numbers=dimension_numbers)
    return scale_shift_act(z, jnp.ones((n_out,), acc_t), shift,
                           act_name)


def maybe_fused_bn_train(x, gamma, beta, eps, activation):
    """Training-mode BN forward on the conv-family kernels: one-pass
    channel stats, then the fused normalize(+activation) epilogue.
    Returns ``(y, mean, var)`` with the activation already applied, or
    None when the ``bn_fwd`` ladder demotes the site (the caller runs
    the dense math).  Used on the non-fused-backward path; the
    fused-backward path gets the same stats kernel via
    ``bn_forward_math`` inside ``bn_train_normalize``."""
    sel = select_bn_forward(x.shape, x.dtype, training=True)
    if not sel.fused:
        return None
    _fused_steps.inc(site="bn_train")
    acc_t = _acc_type(x)
    mean, var = channel_stats(x)
    rstd = jax.lax.rsqrt(var + eps)
    scale = gamma.astype(acc_t) * rstd
    shift = beta.astype(acc_t) - mean * scale
    act_name = activation.value
    if act_name in STREAMABLE_ACTIVATIONS:
        y = scale_shift_act(x, scale, shift, act_name)
    else:
        y = activation(scale_shift_act(x, scale, shift, "identity"))
    return y, mean, var


def maybe_bn_inference_epilogue(x, scale, shift, activation):
    """Inference-mode BN as ONE epilogue pass: the running stats fold
    into per-channel scale/shift and the activation streams behind
    them.  Returns the activated output, or None when the
    ``conv_epilogue`` ladder demotes the site."""
    act_name = activation.value
    sel = select_conv_epilogue(x.shape, x.dtype, act_name)
    if not sel.fused:
        return None
    _fused_steps.inc(site="bn_infer")
    return scale_shift_act(x, scale, shift, act_name)
