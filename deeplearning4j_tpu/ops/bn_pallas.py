"""Fused batch-norm backward — Pallas TPU kernel.

Reference parity: ``CudnnBatchNormalizationHelper.backprop`` (SURVEY.md
D9/N8 — the helper seam exists precisely to hand-tune where the
stock lowering falls short).  The XLA autodiff of the BN normalize
splits the backward into separate reduction and elementwise fusions
that each re-read the activation and its cotangent from HBM; on a
ResNet-50 step the profiler attributes ~21 ms to those re-reads
(BENCH_notes_r02.md).  The ResNet-50 train step sits at ~94% of the
HBM roofline, so bytes ARE the step time.

This kernel pair caps BN-backward traffic at the provable minimum of
two passes:

  pass 1 (reduce):  read x, dy  → Σdy, Σdy·x̂  (= dβ, dγ)
  pass 2 (dx):      read x, dy  → dx = A·dy + D·x + E

with A/D/E per-channel f32 coefficients folded OUTSIDE the kernel
from the sums (the algebra: dx = γr(dy − Σdy/M − x̂·Σdyx̂/M) plus the
running-stat cotangent terms, rearranged into one FMA form so the
inner loop is two mul-adds per element).

What the profile of one site promised did not show in the whole
step: on the chip the ResNet-50 b256 step runs 2.58 times faster
without the kernels (PERF.md section 6, PR 33: each ``pallas_call``
costs a relayout to ``[M, C]`` and back, and cuts the ReLU masks and
residual adds out of XLA's fusions), so the ladder's auto rung picks
XLA's autodiff everywhere and the pair runs only when forced:
``DL4J_TPU_FUSED_BN_BWD=1`` (Environment
``extra["fused_bn_bwd"]``).  Off-TPU the kernels run in Pallas
interpret mode (``kernel_select.interpret_mode`` — the platform alone
decides), so the f64 gradient checks exercise the SAME code path the
chip runs.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from deeplearning4j_tpu.ops import kernel_select


def auto_rung(platform: str, tpu_reading: str):
    """The auto rung of the conv/BN families (``bn_bwd`` here,
    ``bn_fwd`` and ``conv_epilogue`` in ops/conv_pallas.py), by the
    ladder's rule: on for the TPU only where a chip run recorded in
    PERF.md shows the whole program faster with the kernels. None
    does, so every platform gets the dense lowering and the reason
    names the reading (``tpu_reading``) that says so."""
    if platform != "tpu":
        return False, f"auto: platform '{platform}' is not tpu"
    return False, f"auto: dense: {tpu_reading}"


#: the four corners of the two gates on ``resnet50.train-1chip``
#: (PERF.md section 6, PR 33): 2701.5 samples/s with both families
#: off, 1047.2 with both on, 1366.8 with this backward alone, 1008.4
#: with the forward kernels alone
BN_TPU_READING = ("XLA's lowering 2.58x the kernels' on ResNet-50 b256, "
                  "PERF.md §6 PR 33")


def fused_bn_bwd_enabled() -> bool:
    """Whether training-mode BN runs under the ``custom_vjp`` whose
    backward is the Pallas kernel pair: family ``bn_bwd`` of the
    ``ops/kernel_select.py`` ladder, counted in
    ``dl4j_kernel_select_total``. Off by default on every platform:
    on the chip the whole ResNet-50 step is 2.58 times faster under
    XLA's autodiff of ``bn_forward_math`` (:data:`BN_TPU_READING`),
    and elsewhere interpret mode would crawl.
    ``DL4J_TPU_FUSED_BN_BWD=1`` (or Environment
    ``extra["fused_bn_bwd"]``, which overrides the env var) forces
    the kernels on anywhere, ``=0`` is the kill switch."""
    return kernel_select.select(
        "bn_bwd", auto=lambda: auto_rung(kernel_select.platform(),
                                         BN_TPU_READING)).fused


def _block_rows(M: int, C: int) -> int:
    """~512KB f32 working set per operand block, sublane-aligned."""
    bm = max(8, min(4096, (512 * 1024) // (4 * max(C, 128))))
    bm = (bm // 8) * 8
    return min(bm, max(8, ((M + 7) // 8) * 8))


def _reduce_kernel(x_ref, dy_ref, stat_ref, acc_ref, *, M, bm, acc_t):
    i = pl.program_id(0)
    x = x_ref[...].astype(acc_t)
    dy = dy_ref[...].astype(acc_t)
    rows = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    valid = (i * bm + rows) < M
    dy = jnp.where(valid, dy, 0)
    xhat = (x - stat_ref[0:1, :]) * stat_ref[1:2, :]
    # mask the PRODUCT too: padded x rows hold garbage (0·NaN = NaN)
    part = jnp.concatenate(
        [jnp.sum(dy, axis=0, keepdims=True),
         jnp.sum(jnp.where(valid, dy * xhat, 0), axis=0,
                 keepdims=True)], axis=0)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += part


def _dx_kernel(x_ref, dy_ref, coef_ref, dx_ref, *, acc_t):
    x = x_ref[...].astype(acc_t)
    dy = dy_ref[...].astype(acc_t)
    a = coef_ref[0:1, :]
    d = coef_ref[1:2, :]
    e = coef_ref[2:3, :]
    dx_ref[...] = (a * dy + d * x + e).astype(dx_ref.dtype)


def _bn_bwd_sums(x2d, dy2d, mean, rstd, acc_t):
    """Pass 1: Σdy and Σdy·x̂ per channel, one read of x and dy."""
    M, C = x2d.shape
    bm = _block_rows(M, C)
    grid = (pl.cdiv(M, bm),)
    stat = jnp.stack([mean, rstd]).astype(acc_t)      # [2, C]
    acc = pl.pallas_call(
        partial(_reduce_kernel, M=M, bm=bm, acc_t=acc_t),
        grid=grid,
        in_specs=[pl.BlockSpec((bm, C), lambda i: (i, 0)),
                  pl.BlockSpec((bm, C), lambda i: (i, 0)),
                  pl.BlockSpec((2, C), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((2, C), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((2, C), acc_t),
        interpret=kernel_select.interpret_mode(),
    )(x2d, dy2d, stat)
    return acc[0], acc[1]


def _bn_bwd_dx(x2d, dy2d, a, d, e, acc_t):
    """Pass 2: dx = A·dy + D·x + E (pure per-channel FMA)."""
    M, C = x2d.shape
    bm = _block_rows(M, C)
    grid = (pl.cdiv(M, bm),)
    coef = jnp.stack([a, d, e]).astype(acc_t)         # [3, C]
    return pl.pallas_call(
        partial(_dx_kernel, acc_t=acc_t),
        grid=grid,
        in_specs=[pl.BlockSpec((bm, C), lambda i: (i, 0)),
                  pl.BlockSpec((bm, C), lambda i: (i, 0)),
                  pl.BlockSpec((3, C), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((bm, C), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((M, C), x2d.dtype),
        interpret=kernel_select.interpret_mode(),
    )(x2d, dy2d, coef)


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def bn_train_normalize(x, gamma, beta, eps):
    """Training-mode BN normalize with batch statistics, returning
    ``(y, mean, var)`` — the fused-backward drop-in for the layer's
    inline math (one-pass E[x]/E[x²] statistics, f32 accumulation)."""
    y, mean, var, _ = bn_forward_math(x, gamma, beta, eps)
    return y, mean, var


def bn_forward_math(x, gamma, beta, eps):
    """THE training-mode BN forward — single source of truth shared by
    the inline layer path and the fused-backward custom_vjp.

    Statistics policy: for bf16/f16 activations, one-pass E[x]/E[x²]
    with f32 accumulation (one fused HBM read; the f32 accumulator's
    ~16 extra mantissa bits make the cancellation benign — the
    cuDNN/TF fused-BN formulation).  For f32+ activations that margin
    does not exist, so the accurate two-pass mean-then-var form is
    used.  When the ``bn_fwd`` kernel-select ladder admits the site
    (DL4J_TPU_FUSED_CONV family), the statistics and the normalize
    each run as ONE Pallas pass (ops/conv_pallas.py) — this is how the
    forward reduction kernel composes with the fused backward: the
    same custom_vjp, hand kernels on both sides.  Returns
    (y, mean, var, rstd)."""
    from deeplearning4j_tpu.ops import conv_pallas
    axes = tuple(range(x.ndim - 1))
    acc_t = jnp.promote_types(x.dtype, jnp.float32)
    fwd_sel = conv_pallas.select_bn_forward(x.shape, x.dtype,
                                           training=True)
    if fwd_sel.fused:
        mean, var = conv_pallas.channel_stats(x)
    elif x.dtype in (jnp.bfloat16, jnp.float16):
        xf = x.astype(acc_t)
        n = x.size // x.shape[-1]
        mean = jnp.sum(xf, axis=axes) / n
        var = jnp.maximum(
            jnp.sum(jax.lax.square(xf), axis=axes) / n
            - jax.lax.square(mean), 0.0)
    else:
        mean = jnp.mean(x, axis=axes)
        var = jnp.var(x, axis=axes)
    rstd = jax.lax.rsqrt(var + eps)
    scale = gamma.astype(acc_t) * rstd
    bias = beta.astype(acc_t) - mean * scale
    if fwd_sel.fused:
        y = conv_pallas.scale_shift_act(x, scale, bias, "identity")
    else:
        # x·scale + bias: one fused multiply-add over the tensor
        # instead of subtract/divide chains
        y = x * scale.astype(x.dtype) + bias.astype(x.dtype)
    return y, mean, var, rstd


def _bn_fwd(x, gamma, beta, eps):
    y, mean, var, rstd = bn_forward_math(x, gamma, beta, eps)
    return (y, mean, var), (x, gamma, mean, rstd)


def _bn_bwd(eps, res, cts):
    # kernel-site annotation: non-dl4j prefix so the tag nests inside
    # the enclosing layer's dl4j.<layer> attribution scope (custom_vjp
    # backward rules inherit the primal trace's scope in HLO metadata;
    # this marks the hand kernel itself)
    with jax.named_scope("pallas.bn_bwd"):
        return _bn_bwd_raw(eps, res, cts)


def _bn_bwd_raw(eps, res, cts):
    dy, dmean_ct, dvar_ct = cts
    x, gamma, mean, rstd = res
    acc_t = jnp.promote_types(x.dtype, jnp.float32)
    C = x.shape[-1]
    M = x.size // C
    x2d = x.reshape(M, C)
    dy2d = dy.reshape(M, C)

    sdy, sdyx = _bn_bwd_sums(x2d, dy2d, mean.astype(acc_t),
                             rstd.astype(acc_t), acc_t)
    g = gamma.astype(acc_t)
    r = rstd.astype(acc_t)
    mu = mean.astype(acc_t)
    inv_m = 1.0 / M
    # dx = γr·dy − γr·Σdy/M − γr²·x̂-coefficient... rearranged into
    # dx = A·dy + D·x + E with the mean/var cotangent terms folded in
    a_coef = g * r
    d_coef = -g * r * r * (sdyx * inv_m) \
        + 2.0 * dvar_ct.astype(acc_t) * inv_m
    e_coef = (-a_coef * (sdy * inv_m)
              + dmean_ct.astype(acc_t) * inv_m
              - d_coef * mu)
    dx = _bn_bwd_dx(x2d, dy2d, a_coef, d_coef, e_coef,
                    acc_t).reshape(x.shape)
    dgamma = sdyx.astype(gamma.dtype)
    dbeta = sdy.astype(gamma.dtype)
    return dx, dgamma, dbeta


bn_train_normalize.defvjp(_bn_fwd, _bn_bwd)
