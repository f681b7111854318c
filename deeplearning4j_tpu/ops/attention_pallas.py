"""Pallas flash-attention backend for the ``sdpa_core`` op.

The dense einsum attention (ops/attention.py) materializes the
``[b, h, t_q, t_k]`` scores tensor in HBM — at long sequence lengths
those bytes dominate the memory floor and the step time (BENCH_r05:
bytes, not FLOPs, are the lever). This backend routes ``sdpa_core``
sites onto the blocked online-softmax Pallas kernel
(parallel/sequence.py — forward + LSE-recomputing backward, measured
1.55-1.6x faster than XLA dense attention at seq 8k-16k on v5e and
able to run 32k where dense cannot allocate the score matrix at all),
which keeps only O(block_q x block_k) scores in VMEM and never writes
them to HBM.

Adaptation to the ``sdpa_core`` contract:

  * arbitrary ``scale``: the kernel hardcodes the 1/sqrt(d) scaling of
    natively-authored attention, so q is pre-multiplied by
    ``scale * sqrt(d)`` (a single elementwise op; exact for the
    default scale, where the factor is 1.0 and the multiply is
    skipped);
  * key masks: ``mask_mode="key"`` sites (the GraphOptimizer's
    strength-reduced exporter masks) stream a ``[b, t_k]`` key mask
    through the kernel — dense ADDITIVE biases are not streamable and
    fall back to the einsum path;
  * rank: [b, h, t, d] natively, [b, t, d] via a unit heads axis.

Backend selection (``select_attention_backend``): the
``DL4J_TPU_FLASH_ATTENTION`` env var forces the kernel on (``1``) or
off (``0``); unset, the kernel auto-engages on TPU when t_k reaches
``FLASH_MIN_SEQ`` (below ~4k the XLA dense lowering wins outright —
BENCH_notes_r03) OR when the would-be scores tensor alone would eat
more than ``HBM_HEADROOM_FRACTION`` of the device's free HBM.
Off-TPU the kernel runs in Pallas interpret mode
(``kernel_select.interpret_mode`` — the platform alone decides), so
CPU tests exercise the SAME code path the chip runs.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

#: below this key length the XLA dense lowering beats the kernel
#: outright on TPU (BENCH_notes_r03); auto-selection starts here
FLASH_MIN_SEQ = 4096
#: auto-select flash below FLASH_MIN_SEQ once the dense scores tensor
#: alone would consume this fraction of the device's free HBM
HBM_HEADROOM_FRACTION = 0.25


def flash_attention_override() -> Optional[bool]:
    """Tri-state DL4J_TPU_FLASH_ATTENTION gate: True (force on) /
    False (kill switch) / None (auto heuristic). Environment
    ``extra["flash_attention"]`` overrides the env var.  Since the
    ISSUE-13 unification this is the ``attention`` family row of the
    shared ``ops/kernel_select.py`` ladder."""
    from deeplearning4j_tpu.ops import kernel_select
    return kernel_select.gate_override("attention")


def _free_hbm_bytes() -> Optional[int]:
    try:
        st = jax.local_devices()[0].memory_stats()
        return int(st["bytes_limit"]) - int(st["bytes_in_use"])
    except Exception:           # CPU backend has no memory_stats
        return None


def as_key_mask(mask, batch: int, t_k: int, rank: int):
    """Reduce a mask broadcastable against [b, (h,) t_q, t_k] scores
    to the [b, t_k] key-mask form the kernel streams, or None when
    the mask varies per query/head (right-aligned numpy broadcasting
    — exactly the dense path's semantics)."""
    if mask.ndim == 0 or mask.ndim > rank:
        return None
    ms = tuple(mask.shape)
    if ms[-1] != t_k:
        return None
    if mask.ndim >= 2 and ms[-2] != 1:
        return None             # per-query mask: not streamable
    lead = 1
    for i, dim in enumerate(ms[:-2] if mask.ndim >= 2 else ()):
        axis_from_right = mask.ndim - i
        if axis_from_right == rank:          # the batch axis
            if dim not in (1, batch):
                return None
            lead = dim
        elif dim != 1:                       # a head/query axis
            return None
    flat = jnp.reshape(mask, (lead, t_k))
    return jnp.broadcast_to(flat, (batch, t_k))


def select_attention_backend(q_shape: Tuple[int, ...],
                             k_shape: Tuple[int, ...], *,
                             mask_ok: bool = True,
                             has_bias: bool = False,
                             platform: Optional[str] = None,
                             free_hbm: Optional[int] = None,
                             override=None,
                             use_env_override: bool = True):
    """Pick ("flash" | "dense", reason) for an sdpa_core site.

    Structural requirements dominate everything (a dense additive
    bias or per-query mask cannot stream through the kernel); then
    the DL4J_TPU_FLASH_ATTENTION override; then the auto heuristic
    (TPU + long sequence, or scores tensor vs free-HBM headroom).
    ``platform``/``free_hbm``/``override`` exist for tests — they
    default to the live device.  The ladder itself lives in
    ``ops/kernel_select.py`` (family ``attention``), so every decision
    lands in ``dl4j_kernel_select_total{kernel="attention"}``."""
    from deeplearning4j_tpu.ops import kernel_select

    structural = None
    if has_bias:
        structural = "additive bias is not streamable"
    elif len(q_shape) not in (3, 4) or len(k_shape) != len(q_shape):
        structural = f"rank {len(q_shape)} not supported"
    elif q_shape[-1] != k_shape[-1]:
        structural = "q/k head-dim mismatch"
    elif not mask_ok:
        structural = "mask is not a key mask"
    if override is None and use_env_override:
        override = flash_attention_override()

    def _auto():
        plat = platform
        if plat is None:
            plat = kernel_select.platform()
        if plat != "tpu":
            return False, f"auto: platform '{plat}' is not tpu"
        t_k = k_shape[-2]
        if t_k >= FLASH_MIN_SEQ:
            return True, f"auto: t_k={t_k} >= {FLASH_MIN_SEQ}"
        scores_bytes = 4        # f32 scores
        for d in q_shape[:-1]:
            scores_bytes *= int(d)
        scores_bytes *= int(t_k)
        fh = free_hbm if free_hbm is not None else _free_hbm_bytes()
        if fh is not None and fh > 0 \
                and scores_bytes > HBM_HEADROOM_FRACTION * fh:
            return True, (f"auto: scores tensor {scores_bytes >> 20} MB"
                          f" > {HBM_HEADROOM_FRACTION:.0%} of free HBM"
                          f" ({fh >> 20} MB)")
        return False, f"auto: t_k={t_k} fits the dense lowering"

    sel = kernel_select.select("attention", structural=structural,
                               auto=_auto, override=override,
                               use_env_override=False)
    return ("flash" if sel.fused else "dense"), sel.reason


def flash_sdpa(q, k, v, scale: Optional[float] = None, key_mask=None,
               block_q: int = 1024, block_k: int = 1024):
    """Run sdpa_core semantics on the Pallas kernel:
    softmax(q k^T * scale, masked) v. q/k/v [b, h, t, d] or
    [b, t, d]; key_mask [b, t_k] (0 = masked) or None. Differentiable
    (the kernel carries its own custom VJP; the scale pre-multiply
    composes). Off-TPU the kernel runs in interpret mode, so gradient
    checks exercise the chip's code path."""
    from deeplearning4j_tpu.parallel.sequence import flash_attention
    squeeze_heads = q.ndim == 3
    if squeeze_heads:
        q, k, v = q[:, None], k[:, None], v[:, None]
    d = q.shape[-1]
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    factor = float(scale) * math.sqrt(d)
    if abs(factor - 1.0) > 1e-9:
        # the kernel scales scores by 1/sqrt(d); fold the requested
        # scale into q so q'k^T/sqrt(d) == q k^T * scale
        q = q * jnp.asarray(factor, q.dtype)
    if key_mask is not None and key_mask.dtype == jnp.bool_:
        key_mask = key_mask.astype(jnp.float32)
    # kernel-site annotation: a non-dl4j prefix so the kernel tag
    # nests INSIDE the enclosing layer's dl4j.<layer> scope in HLO
    # metadata without stealing the attribution match
    with jax.named_scope("pallas.flash_attention"):
        out = flash_attention(q, k, v, False, block_q, block_k,
                              key_mask)
    return out[:, 0] if squeeze_heads else out


# ---------------------------------------------------------------------------
# paged decode attention (ISSUE 16, rewritten in ISSUE 27): one query
# token per sequence attending over a block-paged KV pool through a
# per-sequence block table — the decode half of the generative serving
# engine. The kernel's work follows ``lengths``: it fetches and
# multiplies the blocks a sequence's context fills and nothing of the
# bucket's padding, many blocks behind one wait, in bf16 MXU products
# accumulated in float32.
# ---------------------------------------------------------------------------

#: masked-score value — matches parallel/sequence.py's NEG_INF so the
#: exp-zeroing trick (exp of masked == exactly 0) carries over
_PAGED_NEG_INF = -1e30


def _paged_gather(pool, layer, block_tables, d):
    """One layer's K or V at every table position, ``[b, t, h_kv, d]``:
    a gather of the tables' blocks out of the stacked lane-dense pool
    (no slice of the layer), its lanes split into heads."""
    b, max_blocks = block_tables.shape
    block, hd = pool.shape[2:]
    return jnp.reshape(pool[layer, block_tables],
                       (b, max_blocks * block, hd // d, d))


def paged_attention_reference(q, k_pool, v_pool, block_tables,
                              lengths, layer=0,
                              scale: Optional[float] = None,
                              v_group: int = 1, sink=None):
    """Dense-gather fallback AND numerical reference for paged decode
    attention.

    ``q`` [b, h, d] (the single new token per sequence); ``k_pool`` /
    ``v_pool`` [layers, num_blocks, block, h_kv * d] (the whole paged
    KV as ``KVBlockPool`` stores it: a token's heads side by side down
    the lanes) of which ``layer`` is read; ``block_tables``
    [b, max_blocks] int32 (scratch-block-0 padded); ``lengths`` [b]
    int32 — valid KV tokens per sequence (>= 1, the current token's KV
    already written). Returns [b, h, d].

    The V pool may hold heads of another width than K's (``d_v`` lanes
    a head where K has ``d``: ``h_kv * d_v`` lanes a token): the
    result is then [b, h, d_v]. ``sink`` [h] is a logit a query head
    that joins the softmax's denominator and adds no value (a learned
    attention sink): ``p_j = exp(s_j - m) / (exp(sink - m) + sum_j
    exp(s_j - m))``.

    ``v_group`` KV heads side by side share their values (differential
    attention: K heads of ``d``, a V of ``v_group * d`` for the pair):
    query head ``r``, which scores against KV head ``k = r // (h /
    h_kv)``, then reads the values of heads ``[k - k % v_group, ... +
    v_group)`` and the result is [b, h, v_group * d].

    The gather materializes [b, max_blocks*block, h_kv, d] whatever
    the lengths are — the bytes the Pallas kernel does not move — but
    runs everywhere and defines the semantics the kernel must match
    bit-for-tolerance."""
    b, h, d = q.shape
    k = _paged_gather(k_pool, layer, block_tables, d)
    d_v = v_pool.shape[3] // k.shape[2]
    v = _paged_gather(v_pool, layer, block_tables, d_v)
    t = k.shape[1]
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    if h != k.shape[2] or v_group != 1 or d_v != d or sink is not None:
        return _paged_reference_grouped(q, k, v, lengths, scale, v_group,
                                        sink)
    s = jnp.einsum("bhd,bthd->bht", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    valid = (jnp.arange(t, dtype=jnp.int32)[None, :]
             < lengths[:, None])                      # [b, t]
    s = jnp.where(valid[:, None, :], s, _PAGED_NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.where(s <= _PAGED_NEG_INF / 2, 0.0, jnp.exp(s - m))
    w = p / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    out = jnp.einsum("bht,bthd->bhd", w, v.astype(jnp.float32))
    return out.astype(q.dtype)


def _paged_reference_grouped(q, k, v, lengths, scale, v_group=1,
                             sink=None):
    """:func:`paged_attention_reference` where the gathered ``k`` [b,
    t, h_kv, d] / ``v`` [b, t, h_kv, d_v] hold fewer KV heads than
    ``q`` has query heads (grouped-query attention): query head ``i``
    reads KV head ``i // (h / h_kv)``, and the values of that head's
    ``v_group``; with ``sink`` [h], a logit a head in the
    denominator."""
    f32 = jnp.float32
    b, h, d = q.shape
    t, h_kv = k.shape[1:3]
    d_v = v.shape[3]
    if v_group != 1:
        # every KV head gets its group's values, v_group * d wide
        v = jnp.repeat(v.reshape(b, t, h_kv // v_group, v_group * d),
                       v_group, axis=2)
    qg = q.astype(f32).reshape(b, h_kv, h // h_kv, d)
    s = jnp.einsum("bkgd,btkd->bkgt", qg, k.astype(f32)) * scale
    valid = jnp.arange(t, dtype=jnp.int32)[None, :] < lengths[:, None]
    s = jnp.where(valid[:, None, None, :], s, _PAGED_NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    extra = 0.0
    if sink is not None:
        sk = sink.astype(f32).reshape(1, h_kv, h // h_kv, 1)
        m = jnp.maximum(m, sk)
        extra = jnp.exp(sk - m)
    p = jnp.where(s <= _PAGED_NEG_INF / 2, 0.0, jnp.exp(s - m))
    w = p / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True) + extra, 1e-30)
    out = jnp.einsum("bkgt,btkd->bkgd", w, v.astype(f32))
    return out.reshape(b, h, v_group * d_v).astype(q.dtype)


#: KV tokens one inner step of the paged kernel multiplies at once:
#: two lane tiles of scores, and 16 of the engine's 16-token blocks
#: behind one wait
_PAGED_STEP_TOKENS = 256
#: ceiling on the kernel's K/V slabs in VMEM (two slots each), well
#: inside Mosaic's default scoped limit
_PAGED_SLAB_BYTES = 8 << 20


def _paged_blocks_per_step(block: int, hd: int, itemsize: int,
                           max_blocks: int) -> int:
    """KV blocks one inner step fetches and multiplies — derived from
    the shapes, not a knob: ``_PAGED_STEP_TOKENS`` tokens, fewer where
    four ``[tokens, h*d]`` slabs would pass ``_PAGED_SLAB_BYTES`` or
    the table is shorter."""
    tokens = min(_PAGED_STEP_TOKENS,
                 _PAGED_SLAB_BYTES // (4 * hd * itemsize))
    return max(1, min(tokens // block, max_blocks))


def _paged_decode_kernel(tables_ref, lens_ref, layer_ref, q_ref, k_hbm,
                         v_hbm, *rest, head_dim: int,
                         block: int, blocks_per_step: int, scale: float,
                         group: int = 1, v_group: int = 1,
                         v_head_dim: Optional[int] = None,
                         sink: bool = False):
    """One sequence a grid step; inside it, a loop over the sequence's
    own ``ceil(length / block)`` blocks, ``blocks_per_step`` at a time.

    The stacked pools ``[layers, blocks, block, h*d]`` stay in HBM
    whole: no layer is cut out of them. A step's blocks are fetched by
    one DMA each (``layer_ref`` and the table name them) into one slot
    of a two-slot ``[T, h*d]`` slab, ``T = blocks_per_step * block``,
    and the next step's — the next sequence's first, after a
    sequence's last — are started before this step's are waited for,
    so a dead row of the bucket (length 1) costs one block and nothing
    past a row's length is fetched, multiplied or waited for.

    The math keeps the pool's own lane-dense order ``[T, h*d]`` and
    has no head axis: with ``Qbd [H, h*d]`` holding q's head ``r`` in
    row ``r`` on its KV head's lanes and zeros elsewhere (``H`` = heads
    padded to a bf16 sublane tile), scores are ``Qbd @ K^T -> [H, T]``
    and the output ``P @ V -> [H, h*d]``, of which row ``r`` keeps its
    KV head's lanes when the row is finished. Both products take bf16
    operands into float32 (the model's default precision); the softmax
    statistics and the accumulator are float32.

    Nothing that is the same for every sequence is rebuilt for one.
    ``Qbd`` is block-diagonal: KV head ``k``'s ``group`` query heads
    are rows ``[k * group, (k + 1) * group)`` on lanes ``[k * d, (k +
    1) * d)``, which is ``q_ref``'s rows on those lanes as they come
    (``q_ref`` / ``out_ref`` row ``j`` hold, on KV head ``k``'s lanes,
    query head ``k * group + j``). Its zeros are written once a launch
    (``qbd_ref``, under ``i == 0``: the grid is sequential) and a
    sequence writes only the ``h_kv`` diagonal blocks, ``H x d``
    elements whatever ``group`` is; the finished row reads the same
    blocks of the accumulator, each times its rows' ``1 / l``, so an
    output element is one accumulator element times one reciprocal.
    No lane mask exists.

    With ``v_group > 1`` (differential attention) a row scores against
    its own KV head's lanes as ever and keeps, of ``P @ V``, the lanes
    of its head's whole group of ``v_group``: ``out_ref`` then has
    ``group * v_group`` rows, row ``c * group + j`` holding on group
    ``G``'s lanes query head ``(G * v_group + c) * group + j``.

    With ``v_head_dim`` (V heads of another width than K's; ``v_group``
    1) the V slab and the accumulator are ``h_kv * v_head_dim`` lanes
    wide and a row keeps its KV head's ``v_head_dim`` lanes of them.
    With ``sink`` an operand ``[H, 1]`` follows the pools: query head
    ``r``'s sink logit, which the row starts from (``m = sink``, ``l =
    1``, ``acc = 0``: exactly the extra term of the denominator)."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f32, bf16 = jnp.float32, jnp.bfloat16
    sink_ref = None
    if sink:
        sink_ref, *rest = rest
    (out_ref, kbuf, vbuf, sem, slot_ref, m_ref, l_ref, acc_ref,
     qbd_ref) = rest
    i = pl.program_id(0)
    n_rows = pl.num_programs(0)
    layer = layer_ref[0]
    max_blocks = tables_ref.shape[1]
    step_tokens = blocks_per_step * block
    h_kv = kbuf.shape[2] // head_dim
    # the lanes of the accumulator that a KV head's rows keep
    wide = v_group * (v_head_dim or head_dim)

    def n_blocks(row):
        return jnp.clip(pl.cdiv(lens_ref[row], block), 1, max_blocks)

    def block_copies(row, first, g, slot):
        blk = tables_ref[row, first + g]
        dst = pl.ds(pl.multiple_of(g * block, block), block)
        return (pltpu.make_async_copy(k_hbm.at[layer, blk],
                                      kbuf.at[slot, dst], sem.at[0, slot]),
                pltpu.make_async_copy(v_hbm.at[layer, blk],
                                      vbuf.at[slot, dst], sem.at[1, slot]))

    def fetch(row, step, slot, wait):
        """Start (or wait for) the DMAs of ``row``'s blocks
        ``[step * G, ...)`` that exist, into ``slot``."""
        first = step * blocks_per_step
        live = jnp.minimum(blocks_per_step, n_blocks(row) - first)

        def one(g, carry):
            for dma in block_copies(row, first, g, slot):
                if wait:
                    dma.wait()
                else:
                    dma.start()
            return carry
        jax.lax.fori_loop(0, live, one, 0)

    @pl.when(i == 0)
    def _first():                                 # noqa: ANN202
        slot_ref[0] = 0
        fetch(0, 0, 0, wait=False)
        qbd_ref[...] = jnp.zeros_like(qbd_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = lens_ref[i]
    n_steps = pl.cdiv(n_blocks(i), blocks_per_step)
    for kv in range(h_kv):
        lanes = slice(kv * head_dim, (kv + 1) * head_dim)
        qbd_ref[kv * group:(kv + 1) * group, lanes] = \
            q_ref[:, lanes].astype(f32)
    if sink_ref is None:
        m_ref[...] = jnp.full_like(m_ref, _PAGED_NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
    else:
        m_ref[...] = sink_ref[...]
        l_ref[...] = jnp.ones_like(l_ref)

    def step(s, carry):
        slot = slot_ref[0]

        @pl.when(s + 1 < n_steps)
        def _same_row():                          # noqa: ANN202
            fetch(i, s + 1, 1 - slot, wait=False)

        @pl.when((s + 1 == n_steps) & (i + 1 < n_rows))
        def _next_row():                          # noqa: ANN202
            fetch(i + 1, 0, 1 - slot, wait=False)

        fetch(i, s, slot, wait=True)
        base = s * step_tokens
        k = kbuf[slot].astype(bf16)               # [T, h*d]
        sc = jax.lax.dot_general(
            qbd_ref[...].astype(bf16), k, (((1,), (1,)), ((), ())),
            preferred_element_type=f32) * scale   # [H, T]
        tok = base + jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1)
        sc = jnp.where(tok < length, sc, _PAGED_NEG_INF)
        # whatever the slab holds past the length (a block's unwritten
        # slots, an earlier step's blocks) must not meet p = 0 as NaN
        row_tok = base + jax.lax.broadcasted_iota(
            jnp.int32, (step_tokens, 1), 0)
        v = vbuf[slot]
        v = jnp.where(row_tok < length, v, jnp.zeros_like(v)).astype(bf16)

        m_prev = m_ref[...]                       # [H, 1]
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(sc - m_new)                   # masked: exactly 0
        l_ref[...] = corr * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        m_ref[...] = m_new
        # a sequence's first step starts from 0 times what the one
        # before left (finite: zeros since ``i == 0``), not from a
        # pass of zeros over the accumulator
        acc_ref[...] = jnp.where(s == 0, 0.0, corr) * acc_ref[...] \
            + jnp.dot(p.astype(bf16), v, preferred_element_type=f32)
        slot_ref[0] = 1 - slot
        return carry

    jax.lax.fori_loop(0, n_steps, step, 0)
    l_ref[...] = 1.0 / jnp.maximum(l_ref[...], 1e-30)
    for kv in range(h_kv):
        rows = slice(kv * group, (kv + 1) * group)
        vg, c = divmod(kv, v_group)
        lanes = slice(vg * wide, (vg + 1) * wide)
        out_ref[c * group:(c + 1) * group, lanes] = \
            (acc_ref[rows, lanes] * l_ref[rows, :]).astype(out_ref.dtype)


def paged_decode_attention(q, k_pool, v_pool, block_tables, lengths,
                           layer=0, scale: Optional[float] = None,
                           v_group: int = 1, sink=None):
    """Pallas paged decode attention — same contract as
    :func:`paged_attention_reference`, at the model's default product
    precision (bf16 operands, float32 accumulation and softmax). The
    stacked pools stay in HBM as they are stored; the kernel reads
    ``lengths`` and fetches only the ``ceil(length / block)`` blocks of
    ``layer`` that each sequence's table names, so its work goes with
    the live context and not with the bucket
    (:func:`_paged_decode_kernel`). Compiled by Mosaic on a TPU
    backend, interpreted everywhere else
    (``kernel_select.interpret_mode``), so CPU conformance tests run
    the chip's code path."""
    from deeplearning4j_tpu.ops import kernel_select

    d = q.shape[2]
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    per_step = _paged_blocks_per_step(
        int(k_pool.shape[2]), int(k_pool.shape[3]),
        k_pool.dtype.itemsize, int(block_tables.shape[1]))
    extra = () if sink is None else (sink,)
    return _paged_call(q, k_pool, v_pool, block_tables, lengths,
                       jnp.asarray(layer, jnp.int32).reshape(1), *extra,
                       scale=float(scale), per_step=per_step,
                       interpret=kernel_select.interpret_mode(),
                       v_group=int(v_group))


@functools.partial(jax.jit, static_argnames=("scale", "per_step",
                                             "interpret", "v_group"))
def _paged_call(q, k_pool, v_pool, block_tables, lengths, layer, *sink,
                scale, per_step, interpret, v_group=1):
    """The ``pallas_call``, jitted on its own so that a model's layers
    share one trace and one lowering of the kernel (the layer index is
    an operand): traced layer by layer, GPT-2 large's 36 added 3.5 s
    to a 30 s set-up (PR 27)."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, d = q.shape
    block, hd = k_pool.shape[2:]          # hd: the pool's lanes a token
    h_kv = hd // d
    g = h // h_kv                         # query heads a KV head
    hp = -(-h // 16) * 16                 # heads, a bf16 sublane tile up
    hdv = v_pool.shape[3]                 # the V pool's lanes a token
    dv = hdv // h_kv
    if dv != d and v_group != 1:
        raise ValueError("a shared V group takes V heads as wide as K's")

    def row(i, tables, lens, layer):              # one sequence's q/out
        return (i, 0, 0)

    operands, specs = (), []
    if sink:
        # a sink logit a query head, in the kernel's row order (row r
        # is query head r), padded to the sublane tile
        operands = (jnp.pad(sink[0].astype(jnp.float32).reshape(h, 1),
                            ((0, hp - h), (0, 0))),)
        specs = [pl.BlockSpec((hp, 1), lambda i, *_: (0, 0))]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,          # block_tables, lengths, layer
        grid=(b,),
        in_specs=[
            pl.BlockSpec((None, g, hd), row),
            pl.BlockSpec(memory_space=pl.ANY),    # k pool: stays in HBM
            pl.BlockSpec(memory_space=pl.ANY),    # v pool
        ] + specs,
        out_specs=pl.BlockSpec((None, g * v_group, hdv), row),
        scratch_shapes=[
            pltpu.VMEM((2, per_step * block, hd), k_pool.dtype),
            pltpu.VMEM((2, per_step * block, hdv), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),      # [k | v, slot]
            pltpu.SMEM((1,), jnp.int32),          # slot being filled
            pltpu.VMEM((hp, 1), jnp.float32),     # running max
            pltpu.VMEM((hp, 1), jnp.float32),     # running sum
            pltpu.VMEM((hp, hdv), jnp.float32),   # output accumulator
            pltpu.VMEM((hp, hd), jnp.float32),    # block-diagonal q
        ],
    )
    more = {}
    if dv != d:
        more["v_head_dim"] = dv
    if sink:
        more["sink"] = True
    kernel = functools.partial(_paged_decode_kernel, head_dim=d,
                               block=block, blocks_per_step=per_step,
                               scale=scale, group=g, v_group=v_group,
                               **more)

    def by_kv_head(a):        # [b, h, d] -> [b, g, h_kv * d]
        if g == 1:
            return a.reshape(b, 1, hd)
        return jnp.swapaxes(a.reshape(b, h_kv, g, d), 1, 2).reshape(b, g, hd)

    with jax.named_scope("pallas.paged_decode_attention"):
        out = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((b, g * v_group, hdv), q.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
            interpret=interpret,
        )(block_tables.astype(jnp.int32), lengths.astype(jnp.int32),
          layer, by_kv_head(q), k_pool, v_pool, *operands)
    if g == 1 and v_group == 1:
        return out.reshape(b, h, dv)
    if v_group == 1:
        return jnp.swapaxes(out.reshape(b, g, h_kv, dv), 1, 2).reshape(b, h, dv)
    # rows [c, j], lanes [G, v_group * d] -> query head (G, c, j)
    out = out.reshape(b, v_group, g, h_kv // v_group, v_group * d)
    return jnp.transpose(out, (0, 3, 1, 2, 4)).reshape(b, h, v_group * d)


def select_paged_backend(batch: int, max_blocks: int, *,
                         platform: Optional[str] = None,
                         override=None,
                         use_env_override: bool = True):
    """Pick ("paged" | "dense", reason) for a decode-attention site
    through the shared kernel-select ladder (family
    ``paged_attention``, env ``DL4J_TPU_PAGED_ATTENTION``). Auto rung:
    the Pallas kernel on TPU, where it was timed against the dense
    gather (PR 27, one v5e chip, GPT-2 large, 32 rows of which 11-15
    live, contexts to 672 of 1024, bf16 pool): a decode step of
    26.7 ms through the kernel against 134.4 ms through the gather,
    which moves the whole bucket's 32 x 1024 tokens whatever lives in
    it; the dense gather elsewhere (interpret mode is a conformance
    vehicle, not a fast path)."""
    from deeplearning4j_tpu.ops import kernel_select

    structural = None
    if batch < 1 or max_blocks < 1:
        structural = f"degenerate decode shape b={batch} " \
                     f"blocks={max_blocks}"
    if override is None and use_env_override:
        override = kernel_select.gate_override("paged_attention")

    def _auto():
        plat = platform
        if plat is None:
            plat = kernel_select.platform()
        if plat == "tpu":
            return True, "auto: paged kernel on tpu"
        return False, f"auto: platform '{plat}' is not tpu"

    sel = kernel_select.select("paged_attention", structural=structural,
                               auto=_auto, override=override,
                               use_env_override=False)
    return ("paged" if sel.fused else "dense"), sel.reason


def maybe_flash_sdpa(q, k, v, scale: Optional[float] = None,
                     mask=None, bias=None, block_q: int = 1024,
                     block_k: int = 1024):
    """Backend dispatch for an sdpa_core site: the flash result when
    the selection heuristic (or override) takes it, else None — the
    caller falls back to the dense einsum path."""
    km, mask_ok = None, True
    if mask is not None:
        km = as_key_mask(mask, int(q.shape[0]), int(k.shape[-2]),
                         q.ndim)
        mask_ok = km is not None
    backend, _reason = select_attention_backend(
        tuple(q.shape), tuple(k.shape), mask_ok=mask_ok,
        has_bias=bias is not None)
    if backend != "flash":
        return None
    return flash_sdpa(q, k, v, scale, key_mask=km, block_q=block_q,
                      block_k=block_k)
