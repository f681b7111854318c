"""Sequence/context parallelism (SURVEY.md §2.6 P9, §5.7).

The reference has NO sequence parallelism — long sequences are handled
only by truncated BPTT (SURVEY.md 5.7). This module is the TPU-native
extension that makes long-context first-class:

- :func:`blockwise_attention` — memory-efficient attention: online
  softmax over key/value blocks (`lax.scan`), O(t) activation memory
  instead of O(t^2); exact same function as dense softmax attention.
- :func:`flash_attention` — the same computation as a Pallas TPU
  kernel (tiled into VMEM, MXU matmuls, fp32 accumulators); backward
  is a pair of Pallas dq / dk+dv kernels recomputing probabilities
  from the saved log-sum-exp (flash-style recompute trades FLOPs for
  HBM, the standard TPU tradeoff).
- :func:`ring_attention` — context parallelism over a mesh ``seq``
  axis: Q/K/V sharded along time; K/V blocks rotate around the ring
  via ``lax.ppermute`` (ICI neighbor exchange) while each device
  accumulates online-softmax partials. Memory per chip: O(t/n_sp).
- :func:`ulysses_attention` — all-to-all sequence parallelism: swap
  the sharded axis from time to heads (``lax.all_to_all``), run local
  full-sequence attention on h/n heads, swap back.

All forms compute the identical function as dense attention (up to
float associativity), so tests compare against
:func:`deeplearning4j_tpu.ops.attention.dot_product_attention`.

Conventions: activations [batch, heads, time, head_dim]; causal masks
use *global* positions, so sharded forms mask correctly across shards.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

NEG_INF = -1e30


from .mesh import shard_map as _shard_map  # public seam, re-exported


# ---------------------------------------------------------------------------
# blockwise (online-softmax) attention — pure JAX, differentiable
# ---------------------------------------------------------------------------
def _block_update(carry, qb, kb, vb, mask_b, scale):
    """One online-softmax step: fold K/V block into (o, l, m)."""
    o, l, m = carry                      # o:[...,tq,d] l,m:[...,tq]
    s = jnp.einsum("...qd,...kd->...qk", qb, kb) * scale
    if mask_b is not None:
        s = jnp.where(mask_b, s, NEG_INF)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    # renormalize previous accumulator, fold in this block. exp() of
    # masked scores must be EXACTLY 0 (not exp(NEG_INF - NEG_INF) = 1)
    # so fully-masked rows accumulate l = 0 and finalize to zeros,
    # matching the dense reference's fully-masked-row semantics.
    corr = jnp.exp(m - m_new)
    p = jnp.where(s <= NEG_INF / 2, 0.0, jnp.exp(s - m_new[..., None]))
    l_new = l * corr + jnp.sum(p, axis=-1)
    o_new = o * corr[..., None] + jnp.einsum("...qk,...kd->...qd", p, vb)
    return (o_new, l_new, m_new)


def _finalize(o, l):
    return o / jnp.maximum(l, 1e-30)[..., None]


def blockwise_attention(q, k, v, *, causal: bool = False,
                        block_k: int = 256,
                        q_offset=0, k_offset=0,
                        key_mask: Optional[jax.Array] = None):
    """Exact attention with O(t) memory via online softmax.

    q: [..., tq, d]; k/v: [..., tk, d]; key_mask: [..., tk] (0=masked).
    ``q_offset``/``k_offset`` are the global positions of element 0 —
    the hook ring attention uses for cross-shard causal masking.
    """
    tq, d = q.shape[-2], q.shape[-1]
    tk = k.shape[-2]
    scale = 1.0 / (d ** 0.5)
    block_k = min(block_k, tk)
    n_blocks = -(-tk // block_k)
    pad = n_blocks * block_k - tk
    if pad:
        kp = jnp.pad(k, [(0, 0)] * (k.ndim - 2) + [(0, pad), (0, 0)])
        vp = jnp.pad(v, [(0, 0)] * (v.ndim - 2) + [(0, pad), (0, 0)])
        km = jnp.pad(key_mask if key_mask is not None else
                     jnp.ones(k.shape[:-1], bool),
                     [(0, 0)] * (k.ndim - 2) + [(0, pad)])
    else:
        kp, vp, km = k, v, key_mask

    q_pos = q_offset + jnp.arange(tq)

    def scan_body(carry, i):
        s = i * block_k
        kb = lax.dynamic_slice_in_dim(kp, s, block_k, axis=-2)
        vb = lax.dynamic_slice_in_dim(vp, s, block_k, axis=-2)
        k_pos = k_offset + s + jnp.arange(block_k)
        mask_b = None
        if causal:
            mask_b = q_pos[:, None] >= k_pos[None, :]
        if km is not None:
            kmb = lax.dynamic_slice_in_dim(km, s, block_k, axis=-1)
            kmb = kmb[..., None, :]
            mask_b = kmb if mask_b is None else (mask_b & (kmb > 0))
        return _block_update(carry, q, kb, vb, mask_b, scale), None

    # carry derived from q so it inherits q's varying-manual-axes when
    # called inside shard_map (e.g. the Ulysses local attention)
    o0 = (q * 0).astype(jnp.promote_types(q.dtype, jnp.float32))
    l0 = o0[..., 0]
    m0 = l0 + NEG_INF
    (o, l, _), _ = lax.scan(scan_body, (o0, l0, m0),
                            jnp.arange(n_blocks))
    return _finalize(o, l).astype(q.dtype)


# ---------------------------------------------------------------------------
# Pallas flash-attention kernel (TPU)
# ---------------------------------------------------------------------------
def _masked_scores(q_ref, k_ref, mask_ref, iq, jk, causal: bool,
                   scale: float):
    """The score block shared by forward and both backward kernels:
    q @ k^T * scale with the causal iota mask and the key mask
    applied as NEG_INF — ONE definition, so the masked-score
    semantics (incl. the exact-zero invariant downstream) can never
    desynchronize between passes."""
    block_q = q_ref.shape[0]
    block_k = k_ref.shape[0]
    s = jax.lax.dot_general(q_ref[:], k_ref[:],
                            (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    s = s * scale
    if causal:
        q_pos = iq * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        k_pos = jk * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        s = jnp.where(q_pos >= k_pos, s, NEG_INF)
    if mask_ref is not None:
        s = jnp.where(mask_ref[:1, :] > 0, s, NEG_INF)
    return s


#: lane-replication width for the lse/delta residuals ((block_q, REP)
#: slabs whose lane dim equals the full array dim — the same sub-128
#: shape rule the key-mask slab uses on its sublane)
_RESID_REP = 8


def _sds_like(shape, dtype, like):
    """ShapeDtypeStruct carrying ``like``'s varying-manual-axes, so
    pallas_call outputs type-check under shard_map (ring attention
    runs the kernels inside the ``seq`` manual axis)."""
    return jax.ShapeDtypeStruct(shape, dtype, vma=jax.typeof(like).vma)


def _flash_kernel(q_ref, k_ref, v_ref, *rest, n_kb: int, causal: bool,
                  scale: float, has_mask: bool,
                  want_lse: bool = False):
    """One (bh, iq, jk) grid cell: fold K/V block jk into the online-
    softmax accumulator for query block iq. Only [block, d] slabs are
    VMEM-resident — K/V stream through the grid (O(block) VMEM).
    Accumulators live in VMEM scratch, which persists across the
    innermost (jk) grid dimension; l/m are stored lane-replicated
    (block_q, 128) to respect the (8, 128) VPU tile. Optional key
    mask streams as a (1, block_k) slab per key block."""
    import jax.experimental.pallas as pl

    lse_ref = None
    if has_mask and want_lse:
        mask_ref, o_ref, lse_ref, o_acc, l_acc, m_acc = rest
    elif has_mask:
        mask_ref, o_ref, o_acc, l_acc, m_acc = rest
    elif want_lse:
        o_ref, lse_ref, o_acc, l_acc, m_acc = rest
        mask_ref = None
    else:
        o_ref, o_acc, l_acc, m_acc = rest
        mask_ref = None
    block_q, d = q_ref.shape
    block_k = k_ref.shape[0]
    iq = pl.program_id(1)
    jk = pl.program_id(2)

    @pl.when(jk == 0)
    def _init():
        o_acc[:] = jnp.zeros_like(o_acc)
        l_acc[:] = jnp.zeros_like(l_acc)
        m_acc[:] = jnp.full_like(m_acc, NEG_INF)

    def _update():
        # operands stay bf16 — the MXU runs bf16×bf16→f32 natively at
        # 2x the f32 rate; accumulation is f32 via
        # preferred_element_type (casting inputs to f32 halves
        # matmul throughput for zero accuracy gain)
        s = _masked_scores(q_ref, k_ref, mask_ref, iq, jk, causal,
                           scale)
        m_prev = m_acc[:, :1]
        l_prev = l_acc[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.where(s <= NEG_INF / 2, 0.0, jnp.exp(s - m_new))
        l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        o_acc[:] = o_acc[:] * corr + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[:], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_acc[:] = m_new + jnp.zeros_like(m_acc)
        l_acc[:] = l_new + jnp.zeros_like(l_acc)

    if causal:
        # skip key blocks entirely in the masked future (~2x FLOPs)
        @pl.when((iq + 1) * block_q > jk * block_k)
        def _():
            _update()
    else:
        _update()

    @pl.when(jk == n_kb - 1)
    def _finalize_out():
        l = jnp.maximum(l_acc[:, :1], 1e-30)
        o_ref[:] = (o_acc[:] / l).astype(o_ref.dtype)
        if lse_ref is not None:
            # per-row log-sum-exp of the SCALED scores, the flash
            # backward's softmax residual; replicated only _RESID_REP
            # lanes wide (128-wide residuals held fwd->bwd cost 128x
            # the HBM of the data present)
            lse_ref[:] = (m_acc[:, :_RESID_REP]
                          + jnp.log(jnp.maximum(
                              l_acc[:, :_RESID_REP], 1e-30)))


def _fit_block(block, t):
    # largest divisor of t that is <= the requested block (halve
    # until it divides): a 1536-long sequence runs with 512-blocks
    # rather than erroring on the 1024 default
    block = min(block, t)
    while t % block:
        block //= 2
    return max(block, 1)


def _flash_forward(q, k, v, key_mask, causal: bool, block_q: int,
                   block_k: int, want_lse: bool = False):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from deeplearning4j_tpu.ops.kernel_select import interpret_mode

    b, h, tq, d = q.shape
    tk = k.shape[2]
    scale = 1.0 / (d ** 0.5)

    block_q = _fit_block(block_q, tq)
    block_k = _fit_block(block_k, tk)
    n_kb = tk // block_k
    qr = q.reshape(b * h, tq, d)
    kr = k.reshape(b * h, tk, d)
    vr = v.reshape(b * h, tk, d)
    has_mask = key_mask is not None

    kernel = functools.partial(_flash_kernel, n_kb=n_kb, causal=causal,
                               scale=scale, has_mask=has_mask,
                               want_lse=want_lse)
    in_specs = [
        pl.BlockSpec((None, block_q, d),
                     lambda bh, iq, jk: (bh, iq, 0)),
        pl.BlockSpec((None, block_k, d),
                     lambda bh, iq, jk: (bh, jk, 0)),
        pl.BlockSpec((None, block_k, d),
                     lambda bh, iq, jk: (bh, jk, 0)),
    ]
    inputs = [qr, kr, vr]
    if has_mask:
        # [b, tk] key mask broadcast to (b*h, 1, tk): a (1, block_k)
        # VMEM slab per key block (sublane dim 1 == full array dim, the
        # only sub-8 block shape Mosaic accepts); XLA materializes the
        # broadcast lazily so HBM cost stays ~b*tk
        km = jnp.broadcast_to(
            key_mask.astype(jnp.float32)[:, None, None, :],
            (b, h, 1, tk)).reshape(b * h, 1, tk)
        inputs.append(km)
        in_specs.append(pl.BlockSpec((None, 1, block_k),
                                     lambda bh, iq, jk: (bh, 0, jk)))
    out_specs = pl.BlockSpec((None, block_q, d),
                             lambda bh, iq, jk: (bh, iq, 0))
    out_shape = _sds_like((b * h, tq, d), q.dtype, qr)
    if want_lse:
        out_specs = [out_specs,
                     pl.BlockSpec((None, block_q, _RESID_REP),
                                  lambda bh, iq, jk: (bh, iq, 0))]
        out_shape = [out_shape,
                     _sds_like((b * h, tq, _RESID_REP), jnp.float32,
                               qr)]
    res = pl.pallas_call(
        kernel,
        grid=(b * h, tq // block_q, n_kb),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
        interpret=interpret_mode(),
    )(*inputs)
    if want_lse:
        out, lse = res
        return out.reshape(b, h, tq, d), lse
    return res.reshape(b, h, tq, d)


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref,
                         delta_ref, *rest, n_kb: int, causal: bool,
                         scale: float, has_mask: bool):
    """dq for one (bh, iq, jk) grid cell: recompute the probability
    block from the saved log-sum-exp (the flash residual), form
    ds = p * (do.v^T - delta), accumulate dq += ds @ k * scale.  Only
    [block, d] slabs + one (block_q, block_k) f32 score block are
    VMEM-resident."""
    import jax.experimental.pallas as pl

    if has_mask:
        mask_ref, dq_ref, dq_acc = rest
    else:
        dq_ref, dq_acc = rest
        mask_ref = None
    block_q = q_ref.shape[0]
    block_k = k_ref.shape[0]
    iq = pl.program_id(1)
    jk = pl.program_id(2)

    @pl.when(jk == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    def _update():
        s = _masked_scores(q_ref, k_ref, mask_ref, iq, jk, causal,
                           scale)
        p = jnp.where(s <= NEG_INF / 2, 0.0,
                      jnp.exp(s - lse_ref[:, :1]))
        dp = jax.lax.dot_general(
            do_ref[:], v_ref[:], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[:, :1])
        dq_acc[:] += jax.lax.dot_general(
            ds.astype(k_ref.dtype), k_ref[:],
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    if causal:
        @pl.when((iq + 1) * block_q > jk * block_k)
        def _():
            _update()
    else:
        _update()

    @pl.when(jk == n_kb - 1)
    def _finalize():
        dq_ref[:] = dq_acc[:].astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref,
                          delta_ref, *rest, n_qb: int, causal: bool,
                          scale: float, has_mask: bool):
    """dk/dv for one (bh, jk, iq) grid cell (q blocks innermost so
    the [block_k, d] accumulators persist per key block):
    dv += p^T @ do,  dk += ds^T @ q * scale."""
    import jax.experimental.pallas as pl

    if has_mask:
        mask_ref, dk_ref, dv_ref, dk_acc, dv_acc = rest
    else:
        dk_ref, dv_ref, dk_acc, dv_acc = rest
        mask_ref = None
    block_q = q_ref.shape[0]
    block_k = k_ref.shape[0]
    jk = pl.program_id(1)
    iq = pl.program_id(2)

    @pl.when(iq == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def _update():
        s = _masked_scores(q_ref, k_ref, mask_ref, iq, jk, causal,
                           scale)
        p = jnp.where(s <= NEG_INF / 2, 0.0,
                      jnp.exp(s - lse_ref[:, :1]))
        # dv += p^T @ do
        dv_acc[:] += jax.lax.dot_general(
            p.astype(do_ref.dtype), do_ref[:],
            (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do_ref[:], v_ref[:], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[:, :1])
        # dk += ds^T @ q * scale
        dk_acc[:] += jax.lax.dot_general(
            ds.astype(q_ref.dtype), q_ref[:],
            (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    if causal:
        @pl.when((iq + 1) * block_q > jk * block_k)
        def _():
            _update()
    else:
        _update()

    @pl.when(iq == n_qb - 1)
    def _finalize():
        dk_ref[:] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[:] = dv_acc[:].astype(dv_ref.dtype)


def _flash_backward(q, k, v, key_mask, out, lse, g, causal: bool,
                    block_q: int, block_k: int, g_lse=None):
    """Pallas flash backward: dq via a (bh, iq, jk) sweep, dk/dv via a
    (bh, jk, iq) sweep, probabilities recomputed from the saved
    log-sum-exp.  Replaces the r3 jax.vjp-through-blockwise backward,
    whose differentiated lax.scan both lost 2.4x to XLA dense at seq
    8k AND failed to compile beyond [4, 8, 8192, 128] on the v5e
    compile helper (BENCH_notes_r04.md)."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from deeplearning4j_tpu.ops.kernel_select import interpret_mode

    interpret = interpret_mode()
    b, h, tq, d = q.shape
    tk = k.shape[2]
    scale = 1.0 / (d ** 0.5)
    block_q = _fit_block(block_q, tq)
    block_k = _fit_block(block_k, tk)
    n_qb, n_kb = tq // block_q, tk // block_k
    qr = q.reshape(b * h, tq, d)
    kr = k.reshape(b * h, tk, d)
    vr = v.reshape(b * h, tk, d)
    gr = g.reshape(b * h, tq, d)
    # delta_i = sum_d dO_i . O_i — the softmax-jacobian row term;
    # cheap elementwise+reduce, lane-replicated like lse.  An lse
    # cotangent folds in EXACTLY here: d lse_i / d s_ij = p_ij, so
    # ds = p*(dp - delta + g_lse) — i.e. delta' = delta - g_lse
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1).reshape(b * h, tq)
    if g_lse is not None:
        delta = delta - g_lse.astype(jnp.float32).reshape(b * h, tq)
    delta = jnp.broadcast_to(delta[:, :, None],
                             (b * h, tq, _RESID_REP))
    has_mask = key_mask is not None

    qkv_specs = [
        pl.BlockSpec((None, block_q, d), lambda bh, iq, jk: (bh, iq, 0)),
        pl.BlockSpec((None, block_k, d), lambda bh, iq, jk: (bh, jk, 0)),
        pl.BlockSpec((None, block_k, d), lambda bh, iq, jk: (bh, jk, 0)),
        pl.BlockSpec((None, block_q, d), lambda bh, iq, jk: (bh, iq, 0)),
        pl.BlockSpec((None, block_q, _RESID_REP),
                     lambda bh, iq, jk: (bh, iq, 0)),
        pl.BlockSpec((None, block_q, _RESID_REP),
                     lambda bh, iq, jk: (bh, iq, 0)),
    ]
    inputs = [qr, kr, vr, gr, lse, delta]
    if has_mask:
        km = jnp.broadcast_to(
            key_mask.astype(jnp.float32)[:, None, None, :],
            (b, h, 1, tk)).reshape(b * h, 1, tk)
        inputs.append(km)
        qkv_specs.append(pl.BlockSpec((None, 1, block_k),
                                      lambda bh, iq, jk: (bh, 0, jk)))
    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, n_kb=n_kb,
                          causal=causal, scale=scale,
                          has_mask=has_mask),
        grid=(b * h, n_qb, n_kb),
        in_specs=qkv_specs,
        out_specs=pl.BlockSpec((None, block_q, d),
                               lambda bh, iq, jk: (bh, iq, 0)),
        out_shape=_sds_like((b * h, tq, d), q.dtype, qr),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
    )(*inputs)

    # same inputs, (bh, jk, iq) grid — index maps swap the roles
    kv_specs = [
        pl.BlockSpec((None, block_q, d), lambda bh, jk, iq: (bh, iq, 0)),
        pl.BlockSpec((None, block_k, d), lambda bh, jk, iq: (bh, jk, 0)),
        pl.BlockSpec((None, block_k, d), lambda bh, jk, iq: (bh, jk, 0)),
        pl.BlockSpec((None, block_q, d), lambda bh, jk, iq: (bh, iq, 0)),
        pl.BlockSpec((None, block_q, _RESID_REP),
                     lambda bh, jk, iq: (bh, iq, 0)),
        pl.BlockSpec((None, block_q, _RESID_REP),
                     lambda bh, jk, iq: (bh, iq, 0)),
    ]
    if has_mask:
        kv_specs.append(pl.BlockSpec((None, 1, block_k),
                                     lambda bh, jk, iq: (bh, 0, jk)))
    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, n_qb=n_qb,
                          causal=causal, scale=scale,
                          has_mask=has_mask),
        grid=(b * h, n_kb, n_qb),
        in_specs=kv_specs,
        out_specs=[
            pl.BlockSpec((None, block_k, d),
                         lambda bh, jk, iq: (bh, jk, 0)),
            pl.BlockSpec((None, block_k, d),
                         lambda bh, jk, iq: (bh, jk, 0)),
        ],
        out_shape=[
            _sds_like((b * h, tk, d), k.dtype, kr),
            _sds_like((b * h, tk, d), v.dtype, vr),
        ],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        interpret=interpret,
    )(*inputs)
    return (dq.reshape(b, h, tq, d), dk.reshape(b, h, tk, d),
            dv.reshape(b, h, tk, d))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention(q, k, v, causal: bool = False, block_q: int = 1024,
                    block_k: int = 1024, key_mask=None):
    """Fused attention kernel, [b, h, t, d]. Equals dense softmax
    attention; O(block) VMEM. ``key_mask``: [b, tk], 0 = masked.
    Backward = Pallas dq/dk/dv kernels recomputing probabilities from
    the saved log-sum-exp (r4; the r3 jax.vjp-through-blockwise
    backward lost 2.4x to XLA dense at seq 8k and failed to compile
    beyond [4, 8, 8192, 128] — BENCH_notes_r04.md). Measured train
    step (fwd+bwd, v5e): 1.55-1.6x FASTER than XLA dense at seq
    8k-16k, and runs at 32k where dense attention cannot materialize
    the score matrix at all.

    Default 1024x1024 forward blocks measured 4.2x faster than
    128x256 at seq 8192 on v5e (fewer grid steps amortize the
    per-block overhead; the f32 score block is 4 MB of VMEM) —
    BENCH_notes_r03.md; the backward caps blocks at 512 (it keeps
    score + dp + ds f32 blocks live). Blocks clamp to the sequence
    length, so short sequences still work; below ~4k prefer plain
    XLA attention, which wins outright there.

    Compiled by Mosaic on a TPU backend, interpreted everywhere else
    (``ops.kernel_select.interpret_mode`` — the platform alone
    decides, no argument can)."""
    return _flash_forward(q, k, v, key_mask, causal, block_q, block_k)


def _flash_fwd(q, k, v, causal, block_q, block_k, key_mask=None):
    out, lse = _flash_forward(q, k, v, key_mask, causal, block_q,
                              block_k, want_lse=True)
    return out, (q, k, v, key_mask, out, lse)


def _flash_bwd(causal, block_q, block_k, res, g):
    q, k, v, key_mask, out, lse = res
    # backward blocks default to 512: the bwd keeps an extra f32
    # score block + dp/ds live, so the fwd's 1024x1024 tuning would
    # overflow VMEM
    dq, dk, dv = _flash_backward(
        q, k, v, key_mask, out, lse, g, causal,
        min(block_q, 512), min(block_k, 512))
    return dq, dk, dv, None      # no cotangent for the mask


flash_attention.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention_with_lse(q, k, v, causal: bool = False,
                             block_q: int = 1024, block_k: int = 1024,
                             key_mask=None):
    """:func:`flash_attention` that ALSO returns the per-row
    log-sum-exp of the scaled scores, [b, h, t] f32 — the residual
    that lets partial attentions over different key sets be merged
    exactly (ring attention's per-step form).  Differentiable in the
    lse output too: its cotangent folds into the backward's delta
    term (d lse/d s = p)."""
    out, lse = _flash_forward(q, k, v, key_mask, causal, block_q,
                              block_k, want_lse=True)
    b, h, tq, _ = q.shape
    return out, lse[:, :, 0].reshape(b, h, tq)


def _flash_lse_fwd(q, k, v, causal, block_q, block_k, key_mask=None):
    out, lse = _flash_forward(q, k, v, key_mask, causal, block_q,
                              block_k, want_lse=True)
    b, h, tq, _ = q.shape
    return ((out, lse[:, :, 0].reshape(b, h, tq)),
            (q, k, v, key_mask, out, lse))


def _flash_lse_bwd(causal, block_q, block_k, res, g):
    q, k, v, key_mask, out, lse = res
    g_out, g_lse = g
    dq, dk, dv = _flash_backward(
        q, k, v, key_mask, out, lse, g_out, causal,
        min(block_q, 512), min(block_k, 512), g_lse=g_lse)
    return dq, dk, dv, None


flash_attention_with_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)


# ---------------------------------------------------------------------------
# ring attention — context parallelism over a mesh axis
# ---------------------------------------------------------------------------
def _ref_attention_with_lse(q, k, v, causal: bool, scale: float):
    """Dense attention returning (out, lse) — the non-kernel twin of
    :func:`flash_attention_with_lse` for backends without Mosaic."""
    s = jnp.einsum("...qd,...kd->...qk", q, k) * scale
    if causal:
        t_q, t_k = s.shape[-2], s.shape[-1]
        i = jnp.arange(t_q)[:, None]
        j = jnp.arange(t_k)[None, :]
        s = jnp.where(i >= j, s, NEG_INF)
    lse = jax.scipy.special.logsumexp(s, axis=-1)
    p = jnp.where(s <= NEG_INF / 2, 0.0,
                  jnp.exp(s - lse[..., None]))
    return jnp.einsum("...qk,...kd->...qd", p, v), lse


def ring_attention(q, k, v, axis_name: str, *, causal: bool = False,
                   block_k: int = 256, use_flash: bool = False,
                   flash_block_q: int = 1024,
                   flash_block_k: int = 1024):
    """Attention with Q/K/V sharded along time over ``axis_name``.

    Call INSIDE ``shard_map``: q/k/v are the local shards
    [b, h, t_local, d]. K/V shards rotate around the ring with
    ``lax.ppermute`` (neighbor ICI hop per step) while each device
    folds the visiting block into its accumulator — t_local^2 compute
    per step, O(t_local) memory, n_sp steps.  Causal masking uses
    global positions so the result equals dense causal attention on
    the gathered sequence.

    ``use_flash=True`` (r4): each ring step runs the Pallas
    :func:`flash_attention_with_lse` kernel on the visiting shard and
    the normalized partials are merged EXACTLY via their
    log-sum-exps; the causal diagonal decomposes per the standard
    ring recipe (earlier shards fully visible, own shard locally
    causal, later shards skipped).  Backward rides the Pallas dq/dkv
    kernels per step through the scan.  Needs [b, h, t, d] inputs
    (the kernel's layout); the default path accepts any [..., t, d].
    """
    n_sp = lax.psum(1, axis_name)
    my = lax.axis_index(axis_name)
    t_local = q.shape[-2]
    scale = 1.0 / (q.shape[-1] ** 0.5)
    q_pos = my * t_local + jnp.arange(t_local)

    # derive the carry from q so it carries q's varying-manual-axes
    # (shard_map type-checks vma through scan carries)
    acc_dt = jnp.promote_types(q.dtype, jnp.float32)
    o0 = (q * 0).astype(acc_dt)
    l0 = o0[..., 0]
    m0 = l0 + NEG_INF
    perm = None  # built per step below

    def rotate(kb, vb):
        p = [(i, (i + 1) % n_sp) for i in range(n_sp)]
        return (lax.ppermute(kb, axis_name, p),
                lax.ppermute(vb, axis_name, p))

    if use_flash:
        from deeplearning4j_tpu.ops.kernel_select import interpret_mode

        # nothing but the platform takes the reference branch: on a
        # TPU backend interpret_mode() is False and the kernels run
        kernels = not interpret_mode()

        def partial_fn(causal_local):
            def f(q, kb, vb):
                if kernels:
                    o_s, lse_s = flash_attention_with_lse(
                        q, kb, vb, causal_local, flash_block_q,
                        flash_block_k)
                else:
                    # interpret-mode pallas does not propagate
                    # varying-manual-axes through the kernel body, so
                    # the CPU mesh runs the exact dense-with-lse
                    # reference (the MERGE algebra — the part ring
                    # adds — is still fully exercised; the kernels
                    # themselves are interpret-tested standalone)
                    o_s, lse_s = _ref_attention_with_lse(
                        q, kb, vb, causal_local, scale)
                return o_s.astype(acc_dt), lse_s
            return f

        def skip_fn(q, kb, vb):
            # derive from q so the outputs carry q's varying-manual-
            # axes (lax.switch requires matching branch types)
            return ((q * 0).astype(acc_dt),
                    (q[..., 0] * 0 + NEG_INF).astype(jnp.float32))

        def step(carry, s):
            (o, l, m), (kb, vb) = carry
            src = (my - s) % n_sp          # who produced this block
            if causal:
                # ring-causal decomposition: src < my fully visible,
                # src == my locally causal, src > my fully masked
                idx = jnp.where(src == my, 1,
                                jnp.where(src < my, 0, 2))
                o_s, lse_s = lax.switch(
                    idx, (partial_fn(False), partial_fn(True),
                          skip_fn), q, kb, vb)
            else:
                o_s, lse_s = partial_fn(False)(q, kb, vb)
            # exact merge of normalized partials via log-sum-exps;
            # fully-masked rows (lse == -inf) contribute zero weight
            m_new = jnp.maximum(m, lse_s)
            c_old = jnp.where(m <= NEG_INF / 2, 0.0,
                              jnp.exp(m - m_new))
            c_new = jnp.where(lse_s <= NEG_INF / 2, 0.0,
                              jnp.exp(lse_s - m_new))
            o = o * c_old[..., None] + o_s * c_new[..., None]
            l = l * c_old + c_new
            return ((o, l, m_new), rotate(kb, vb)), None
    else:
        def step(carry, s):
            (o, l, m), (kb, vb) = carry
            src = (my - s) % n_sp          # who produced this block
            mask = None
            if causal:
                k_pos = src * t_local + jnp.arange(t_local)
                mask = q_pos[:, None] >= k_pos[None, :]
            acc = _block_update((o, l, m), q, kb, vb, mask, scale)
            return (acc, rotate(kb, vb)), None

    (acc, _), _ = lax.scan(step, ((o0, l0, m0), (k, v)),
                           jnp.arange(n_sp))
    o, l, _ = acc
    return _finalize(o, l).astype(q.dtype)


def _seq_sharded_call(local_fn, mesh, q, k, v, seq_axis, causal,
                      **kw):
    """Common shard_map plumbing: q/k/v are GLOBAL [b, h, t, d] arrays;
    time sharded over ``seq_axis``, batch over ``data`` when present."""
    from jax.sharding import PartitionSpec as P

    data = "data" if "data" in mesh.axis_names else None
    spec = P(data, None, seq_axis, None)
    # check_vma=False: the causal ring's lax.switch (fully-visible /
    # locally-causal / skipped branches) makes jax's static
    # replication checker raise "branches of cond produced mismatched
    # replication types" (jax suggests exactly this workaround).  It
    # is safe here: every input and output is seq-sharded — nothing
    # is claimed replicated, so no transpose psum depends on the
    # check — and test_sequence_parallel pins the gradients against
    # dense attention.
    fn = _shard_map(
        functools.partial(local_fn, axis_name=seq_axis, causal=causal,
                          **kw),
        mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)
    return fn(q, k, v)


def ring_self_attention(mesh, q, k, v, *, seq_axis: str = "seq",
                        causal: bool = False, use_flash: bool = False):
    return _seq_sharded_call(ring_attention, mesh, q, k, v, seq_axis,
                             causal, use_flash=use_flash)


# ---------------------------------------------------------------------------
# Ulysses — all-to-all sequence parallelism
# ---------------------------------------------------------------------------
def ulysses_attention(q, k, v, axis_name: str, *, causal: bool = False,
                      block_k: int = 256, use_flash: bool = False):
    """DeepSpeed-Ulysses-style SP. Call INSIDE shard_map with
    [b, h, t_local, d] shards, h divisible by the axis size: all-to-all
    re-shards time->heads, local attention sees the FULL sequence for
    h/n heads, then all-to-all back. Two collectives total; better
    ICI utilisation than a ring when h >= n_sp.

    ``use_flash=True`` (r4): the local full-sequence attention runs
    the Pallas flash kernels (fwd + the dq/dkv backward) on TPU; CPU
    backends keep the blockwise form (interpret-mode pallas cannot
    propagate varying-manual-axes under shard_map)."""
    # [b, h, t/n, d] -> [b, h/n, t, d]
    qh = lax.all_to_all(q, axis_name, split_axis=1, concat_axis=2,
                        tiled=True)
    kh = lax.all_to_all(k, axis_name, split_axis=1, concat_axis=2,
                        tiled=True)
    vh = lax.all_to_all(v, axis_name, split_axis=1, concat_axis=2,
                        tiled=True)
    from deeplearning4j_tpu.ops.kernel_select import interpret_mode
    if use_flash and not interpret_mode():
        o = flash_attention(qh, kh, vh, causal)
    else:
        o = blockwise_attention(qh, kh, vh, causal=causal,
                                block_k=block_k)
    # [b, h/n, t, d] -> [b, h, t/n, d]
    return lax.all_to_all(o, axis_name, split_axis=2, concat_axis=1,
                          tiled=True)


def ulysses_self_attention(mesh, q, k, v, *, seq_axis: str = "seq",
                           causal: bool = False,
                           use_flash: bool = False):
    return _seq_sharded_call(ulysses_attention, mesh, q, k, v, seq_axis,
                             causal, use_flash=use_flash)
