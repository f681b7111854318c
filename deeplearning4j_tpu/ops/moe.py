"""The served expert layer: one chip's share of an expert-parallel
mixture of experts, dropless.

A deployment divides a layer's routed experts over chips; this chip
holds experts ``[first, first + count)`` of ``router.shape[1]``. The
router keeps its published width and its experts per token: every row
is scored against **all** experts (sigmoid scores, the top ``top_k`` of
``score + bias`` chosen, the chosen scores renormalised to gates,
DeepSeek-V3's ``noaux_tc``), and the layer computes the part of
``sum_e g_e E_e(h)`` that its own experts give. What the other experts
would add is another chip's; on one chip the layer runs without its
exchange. **No row is dropped**: there is no capacity, whatever the
routing (``parallel/expert.py``'s GShard gating drops at a capacity and
is the trained layer's; this one serves).

The products run over the ``(row, expert)`` pairs that fall on held
experts, sorted by expert, a chunk of ``rows`` pairs at a time in a
loop whose trip count follows the pairs routed here (one trip where
routing is even: a row sends ``top_k * count / experts`` of its pairs
here), so neither a buffer nor the arithmetic is sized for the worst
routing. Two lowerings of the products, one ``kernel_select`` family
(``moe_grouped``):

- ``dense`` (XLA): every held expert over every row, weighted by the
  gate (0 where the row did not choose it). No sort, no gather: right
  where the experts' weights, read once either way, bound the layer and
  rows are few, and the lowering of every platform but the TPU;
- ``gmm``: the grouped matrix product in Pallas that jax ships
  (``jax.experimental.pallas.ops.tpu.megablox``: a grid over the
  ``(row tile, expert)`` pairs that hold rows, its size a traced
  number) over the sorted pairs, under the scope
  ``pallas.moe_grouped_matmul``.

Every call also returns what the routing did, :data:`COUNTS`, as five
int32 (the host does not know the routing: the engine pulls them with
the step's ids).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

#: what :func:`held_expert_layer` counts, in the order of its vector:
#: pairs on held experts; pairs in all (rows x top_k); held experts
#: with a row; held experts; the fullest held expert's rows
COUNTS = ("moe_rows", "moe_rows_all", "moe_experts_hit",
          "moe_experts_held", "moe_rows_max")
RUNGS = ("dense", "gmm")

#: rows up to which the ``dense`` rung is the auto choice on the TPU: a
#: decode bucket's. At 128 rows no chip run shows the served program
#: faster with the Pallas product (4551.7 tokens/s without against
#: 4529.9 with; two alternating traced pairs: a step of 15.05 ms
#: against 15.12-15.18 and 247-249 requests finished against 243; one
#: layer alone 2.04 against 2.03 ms), so by the ladder's rule XLA's
#: lowering stays; at 512 rows a layer took 3.28 ms against 2.37 (one
#: v5e chip, MiMo-V2.5's widths; PERF.md section 6, PR 34)
DENSE_MAX_ROWS = 128
#: rows one trip of the grouped loop takes at most, and the row tile of
#: the Pallas product: an expert's rows rarely fill a larger one
ROW_TILE = 128


def route(h, router, bias, top_k: int):
    """``(experts [n, top_k], gates [n, top_k])`` of rows ``h [n, d]``:
    sigmoid scores over every expert in float32 (the one product the
    model computes at ``highest``: a near tie at rank ``top_k`` decides
    which expert runs), the ``top_k`` largest of ``score + bias``, the
    chosen scores over their sum."""
    f32 = jnp.float32
    s = jax.nn.sigmoid(jnp.dot(h.astype(f32), router.astype(f32),
                               precision=jax.lax.Precision.HIGHEST))
    _, idx = jax.lax.top_k(s + bias.astype(f32), top_k)
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    return idx, chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)


def select_moe_backend(rows: int, count: int, *, platform=None,
                       override=None, use_env_override: bool = True):
    """Pick (``dense`` | ``gmm``, reason) for an expert layer over
    ``rows`` rows and ``count`` held experts through the shared ladder
    (family ``moe_grouped``, env ``DL4J_TPU_MOE_GROUPED``: ``=1`` forces
    the Pallas grouped product anywhere, ``=0`` kills it). Auto: on the
    TPU the ``dense`` rung up to :data:`DENSE_MAX_ROWS` rows (no Mosaic
    call where no chip run shows the whole program faster with one)
    and the Pallas grouped product beyond, where a layer alone took
    2.37 ms against 3.28 at 512 rows and ``dense`` does 32 times the
    needed products at any size; elsewhere ``dense`` (interpret
    mode is a conformance vehicle, not a fast path)."""
    from deeplearning4j_tpu.ops import kernel_select
    if override is None and use_env_override:
        override = kernel_select.gate_override("moe_grouped")

    def _auto():
        plat = platform if platform is not None else kernel_select.platform()
        if plat != "tpu":
            return False, f"auto: platform '{plat}' is not tpu"
        if rows <= DENSE_MAX_ROWS:
            return False, (f"auto: {rows} rows over {count} experts: the "
                           "weights' read bounds either lowering and the "
                           "served program is no faster with the kernel "
                           "(PERF.md section 6, PR 34)")
        return True, ("auto: grouped product on tpu: its products follow "
                      "the rows routed here, the dense rung's every row "
                      "(PERF.md section 6, PR 34)")

    sel = kernel_select.select("moe_grouped", auto=_auto, override=override,
                               use_env_override=False)
    return ("gmm" if sel.fused else "dense"), sel.reason


def _gmm_tiling(m: int, k: int, n: int):
    """Tiles of the Pallas grouped product: a row tile no larger than
    the rows, weight blocks of at most 2 MB."""
    return (min(m, ROW_TILE), min(k, 2048), min(n, 512))


def _grouped(x, w, sizes):
    """``x[rows of group e] @ w[e]`` for every group, ``x [m, k]``
    sorted by group, ``w [groups, k, n]``, ``sizes [groups]``: float32
    ``[m, n]``, rows past ``sum(sizes)`` undefined."""
    x = x.astype(w.dtype)
    from jax.experimental.pallas.ops.tpu.megablox import gmm
    from deeplearning4j_tpu.ops import kernel_select
    with jax.named_scope("pallas.moe_grouped_matmul"):
        return gmm(x, w, sizes, preferred_element_type=jnp.float32,
                   tiling=_gmm_tiling(x.shape[0], w.shape[1], w.shape[2]),
                   interpret=kernel_select.interpret_mode())


def held_expert_layer(h, router, bias, experts, first: int, count: int, *,
                      top_k: int, valid=None, rung: Optional[str] = None):
    """This chip's part of the expert layer for rows ``h [n, d]``.

    ``router [d, experts]``, ``bias [experts]`` (float32);
    ``experts = (gate, up, down)`` the held experts' SwiGLU weights
    ``[count, d, f]``, ``[count, d, f]``, ``[count, f, d]``; ``valid
    [n]`` (optional) says which rows are live: a padded row of a bucket
    is routed nowhere and counted nowhere. Returns ``(out [n, d]
    float32, counts int32 [5])`` with ``counts`` as :data:`COUNTS`."""
    f32, i32 = jnp.float32, jnp.int32
    n, d = h.shape
    w_gate, w_up, w_down = experts
    if rung is None:
        rung, _ = select_moe_backend(n, count)
    if rung not in RUNGS:
        raise ValueError(f"rung {rung!r} is none of {RUNGS}")
    idx, gates = route(h, router, bias, top_k)
    local = idx - first
    held = (local >= 0) & (local < count)
    live = jnp.ones((n,), bool) if valid is None else valid
    held = held & live[:, None]
    # [n, top_k, count]: pair (row, j) falls on held expert e
    on = held[..., None] & (local[..., None] == jnp.arange(count))
    sizes = jnp.sum(on, axis=(0, 1), dtype=i32)
    total = jnp.sum(sizes)
    counts = jnp.stack([total, jnp.sum(live, dtype=i32) * top_k,
                        jnp.sum(sizes > 0, dtype=i32), jnp.asarray(count, i32),
                        jnp.max(sizes)])
    if rung == "dense":
        with jax.named_scope("moe.dense"):
            w = jnp.sum(jnp.where(on, gates[..., None], 0.0), axis=1)  # [n, e]
            x = h.astype(w_gate.dtype)
            g = jnp.einsum("nd,edf->enf", x, w_gate,
                           preferred_element_type=f32)
            u = jnp.einsum("nd,edf->enf", x, w_up,
                           preferred_element_type=f32)
            a = jax.nn.silu(g) * u * jnp.transpose(w)[:, :, None]
            out = jnp.einsum("enf,efd->nd", a.astype(w_down.dtype), w_down,
                             preferred_element_type=f32)
        return out, counts

    k = top_k
    tile = min(n, ROW_TILE)
    m = -(-n // tile) * tile                # pairs a trip of the loop takes
    # pairs sorted by held expert, the others (key ``count``) behind
    key = jnp.where(held, local, count).reshape(-1)
    order = jnp.argsort(key, stable=True).astype(i32)
    order = jnp.concatenate([order, jnp.zeros((m,), i32)])
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    flat_gates = gates.reshape(-1)
    x_all = h.astype(w_gate.dtype)

    def trip(c, out):
        lo = c * m
        take = jax.lax.dynamic_slice(order, (lo,), (m,))
        rows = take // k
        inside = jnp.arange(m, dtype=i32) < total - lo
        here = jnp.clip(jnp.minimum(ends, lo + m) - jnp.maximum(starts, lo),
                        0, m).astype(i32)
        x = x_all[rows]
        a = jax.nn.silu(_grouped(x, w_gate, here)) * _grouped(x, w_up, here)
        y = _grouped(a, w_down, here)
        y = jnp.where(inside[:, None], y * flat_gates[take][:, None], 0.0)
        return out.at[rows].add(y)

    with jax.named_scope("moe.grouped"):
        out = jax.lax.fori_loop(0, -(-total // m), trip,
                                jnp.zeros((n, d), f32))
    return out, counts


def expert_reference(h, router, bias, experts, first: int, count: int, *,
                     top_k: int):
    """The same share by the definition, an expert at a time over every
    row: small sizes only (the tests')."""
    def mm(x, w):
        return jnp.dot(x.astype(w.dtype), w,
                       preferred_element_type=jnp.float32)
    idx, gates = route(h, router, bias, top_k)
    out = jnp.zeros(h.shape, jnp.float32)
    for e in range(count):
        g = jnp.sum(jnp.where(idx == first + e, gates, 0.0), axis=-1)
        y = mm(jax.nn.silu(mm(h, experts[0][e])) * mm(h, experts[1][e]),
               experts[2][e])
        out = out + g[:, None] * y
    return out
