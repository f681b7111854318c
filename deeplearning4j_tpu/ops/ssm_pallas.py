"""Selective state-space recurrences (Mamba-2 / SSD and Mamba-1): the
prefill scans and the one-token decode updates of a slotted state pool.

The recurrence, per head with state ``S [p, n]`` (``p`` the head's
channels, ``n`` the state size), decay ``a_t = exp(dt_t * A)``::

    S_t = a_t * S_{t-1} + (dt_t * x_t) (x) B_t        y_t = S_t C_t

(``D * x`` and the gate are the model's). Heads share ``B``/``C`` in
groups: head ``i`` reads group ``i // (heads / groups)``.

- :func:`ssd_chunked_scan` — the prefill form: the sequence cut into
  chunks, inside a chunk the masked ``C B^T`` matrix form, the state
  carried chunk to chunk. Plain ``jax.numpy`` (XLA-lowered; no
  backward pass is written for it).
- :func:`ssm_state_update` — the decode form over a **slotted state
  pool** ``[layers, slots, heads, p, n]``: each live row of the decode
  bucket names its slot, and the Pallas kernel (scope
  ``pallas.ssm_state_update``) reads that slot's state, applies one
  step and writes it back in place. It moves ``live x 2 x heads*p*n``
  floats and nothing else of the pool. Slot 0 is scratch (dead rows
  name it, as KV block 0 is): a dead row's turn costs no read and no
  arithmetic. :func:`ssm_state_update_reference` is its ``jax.numpy``
  form — the fallback everywhere the ladder does not take the kernel,
  and the kernel's numerical reference.

Mamba-1 has one decay for every channel and state, ``exp(dt_t[c] *
A[c, n])``, so nothing of it is a matrix product::

    S_t[n, c] = exp(dt_t[c] A[n, c]) S_{t-1}[n, c] + dt_t[c] B_t[n] x_t[c]
    y_t[c] = sum_n C_t[n] S_t[n, c]

Its state is held ``[n, c]``: the ``n`` (16) states down the sublanes
and the channels along the lanes, whole float32 tiles (``[c, n]``
would pad 16 lanes to 128, eight times the bytes on the device).

- :func:`selective_state_update` — decode over the slotted pool
  ``[layers, slots, n, c]``, in place on the rows' slots (kernel scope
  ``pallas.selective_state_update``; :func:`selective_state_update_
  reference` its ``jax.numpy`` form).
- :func:`selective_scan` — the prefill form: the kernel (scope
  ``pallas.selective_scan``) walks the sequence ``_SCAN_TOKENS`` a
  grid step with the state resident in on-chip memory, so the
  ``[t, n, c]`` states are never in HBM; :func:`selective_scan_
  reference` is the ``lax.scan`` over tokens.
Both go through the ``ssm_state`` ladder (:func:`select_ssm_backend`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def ssd_chunked_scan(x, dt, a_log_decay, b, c, *, chunk: int,
                     initial_state=None):
    """Chunked SSD scan over one batch of sequences.

    ``x [bt, t, h, p]``, ``dt [bt, t, h]`` (already softplus'd; 0 at a
    padded position leaves the state as it is), ``a_log_decay [h]``
    (``A``, negative), ``b``/``c`` ``[bt, t, g, n]``. Returns ``(y
    [bt, t, h, p], final_state [bt, h, p, n])``, float32. ``t`` is
    padded up to a multiple of ``chunk`` with ``dt = 0``."""
    f32 = jnp.float32
    bt, t, h, p = x.shape
    g, n = b.shape[2:]
    q = int(chunk)
    pad = -t % q
    if pad:
        x, dt, b, c = (jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
                       for a in (x, dt, b, c))
    nc = (t + pad) // q
    rep = h // g
    x = x.astype(f32).reshape(bt, nc, q, h, p)
    dt = dt.astype(f32).reshape(bt, nc, q, h)
    b = b.astype(f32).reshape(bt, nc, q, g, n)
    c = c.astype(f32).reshape(bt, nc, q, g, n)
    cum = jnp.cumsum(dt * a_log_decay.astype(f32), axis=2)   # [bt,nc,q,h]
    xdt = x * dt[..., None]
    # inside a chunk: y_i += sum_{j<=i} exp(cum_i - cum_j) (C_i.B_j) dt_j x_j
    cb = jnp.repeat(jnp.einsum("zcign,zcjgn->zcgij", c, b), rep, axis=2)
    cum_h = jnp.moveaxis(cum, 3, 2)                          # [bt,nc,h,q]
    diff = cum_h[..., :, None] - cum_h[..., None, :]         # [bt,nc,h,i,j]
    decay = jnp.exp(jnp.where(jnp.tril(jnp.ones((q, q), bool)), diff,
                              -jnp.inf))
    y = jnp.einsum("zchij,zcjhp->zcihp", decay * cb, xdt)
    # each chunk's own contribution to the state at its end
    to_end = jnp.exp(cum[:, :, -1:, :] - cum)                # [bt,nc,q,h]
    local = jnp.einsum("zcjhp,zcjhn->zchpn", xdt * to_end[..., None],
                       jnp.repeat(b, rep, axis=3))
    total = jnp.exp(cum[:, :, -1, :])                        # [bt,nc,h]

    def carry(s, xs):
        loc, tot = xs
        return tot[..., None, None] * s + loc, s             # emit state *entering*

    s0 = (jnp.zeros((bt, h, p, n), f32) if initial_state is None
          else initial_state.astype(f32))
    final, entering = jax.lax.scan(
        carry, s0, (jnp.moveaxis(local, 1, 0), jnp.moveaxis(total, 1, 0)))
    entering = jnp.moveaxis(entering, 0, 1)                  # [bt,nc,h,p,n]
    y = y + jnp.exp(cum)[..., None] * jnp.einsum(
        "zcihn,zchpn->zcihp", jnp.repeat(c, rep, axis=3), entering)
    return y.reshape(bt, nc * q, h, p)[:, :t], final


def ssm_state_update_reference(state, layer: int, slots, x, dt, decay,
                               b, c):
    """``jax.numpy`` form of one decode step on the state pool.

    ``state [layers, slots, h, p, n]`` float32; ``slots [rows]`` int32
    (0 = scratch); ``x [rows, h, p]``, ``dt``/``decay`` ``[rows, h]``
    (``decay = exp(dt * A)``), ``b``/``c`` ``[rows, g, n]``. Returns
    ``(state, y [rows, h, p])`` with rows' slots of ``state[layer]``
    replaced; every other slot is left as it was."""
    f32 = jnp.float32
    h, g = x.shape[1], b.shape[1]
    bh = jnp.repeat(b.astype(f32), h // g, axis=1)
    ch = jnp.repeat(c.astype(f32), h // g, axis=1)
    s = state[layer, slots]
    s = (decay.astype(f32)[..., None, None] * s
         + (dt.astype(f32)[..., None] * x.astype(f32))[..., None]
         * bh[:, :, None, :])
    y = jnp.einsum("rhpn,rhn->rhp", s, ch)
    return state.at[layer, slots].set(s), y


def _state_update_kernel(slots_ref, layer_ref, decay_ref, dt_ref, x_ref,
                         b_ref, c_ref, s_ref, so_ref, y_ref):
    """One (group, row) a grid step: the ``heads / groups`` heads of
    the row's slot that share this group's ``B``/``C``. ``s_ref`` /
    ``so_ref`` are the same HBM block ``[hb, p, n]`` (aliased);
    ``x_ref``/``y_ref`` hold the row's channels down the sublanes
    ``[p, hb]`` so that a head's ``x`` is a column to spread along the
    state's lanes."""
    import jax.experimental.pallas as pl

    grp, row = pl.program_id(0), pl.program_id(1)
    hb = s_ref.shape[0]

    @pl.when(slots_ref[row] != 0)
    def _live():                                  # noqa: ANN202
        brow, crow = b_ref[...], c_ref[...]       # [1, n]
        lane = jax.lax.broadcasted_iota(jnp.int32, y_ref.shape, 1)
        y = jnp.zeros(y_ref.shape, jnp.float32)
        for i in range(hb):
            head = grp * hb + i
            xcol = x_ref[:, i:i + 1] * dt_ref[row, head]      # [p, 1]
            s = s_ref[i] * decay_ref[row, head] + xcol * brow
            so_ref[i] = s
            ycol = jnp.sum(s * crow, axis=1, keepdims=True)   # [p, 1]
            y = jnp.where(lane == i, ycol, y)
        y_ref[...] = y

    @pl.when(slots_ref[row] == 0)
    def _dead():                                  # noqa: ANN202
        y_ref[...] = jnp.zeros_like(y_ref)


def ssm_state_update_pallas(state, layer: int, slots, x, dt, decay, b, c):
    """The Pallas kernel — same contract as
    :func:`ssm_state_update_reference`. Compiled by Mosaic on a TPU
    backend, interpreted everywhere else."""
    from deeplearning4j_tpu.ops import kernel_select
    return _state_update_call(
        state, jnp.asarray([layer], jnp.int32), slots, x, dt, decay, b, c,
        interpret=kernel_select.interpret_mode())


@functools.partial(jax.jit, static_argnames=("interpret",))
def _state_update_call(state, layer, slots, x, dt, decay, b, c, *,
                       interpret):
    """The ``pallas_call``, jitted on its own so a model's layers
    share one trace and one lowering (the layer index is an operand)."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    rows, h, p = x.shape
    g, n = b.shape[1:]
    hb = h // g
    # a head's channels down the sublanes, the group's heads across the
    # lanes: [rows, g, p, hb] (small: rows * h * p floats)
    xt = jnp.transpose(x.astype(f32).reshape(rows, g, hb, p), (0, 1, 3, 2))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    slot_block = pl.BlockSpec(
        (None, None, hb, p, n),
        lambda gi, r, slots, layer: (layer[0], slots[r], gi, 0, 0))
    row_cols = pl.BlockSpec((None, None, p, hb),
                            lambda gi, r, slots, layer: (r, gi, 0, 0))
    row_group = pl.BlockSpec((None, None, 1, n),
                             lambda gi, r, slots, layer: (r, gi, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                    # slots, layer
        grid=(g, rows),
        in_specs=[smem, smem, row_cols, row_group, row_group, slot_block],
        out_specs=[slot_block, row_cols],
    )
    with jax.named_scope("pallas.ssm_state_update"):
        state, yt = pl.pallas_call(
            _state_update_kernel,
            grid_spec=grid_spec,
            out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                       jax.ShapeDtypeStruct(xt.shape, f32)],
            # operand 7 (after the two prefetched scalars): the pool,
            # updated in place
            input_output_aliases={7: 0},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary"),
                vmem_limit_bytes=32 << 20),
            interpret=interpret,
        )(slots.astype(jnp.int32), layer, decay.astype(f32),
          dt.astype(f32), xt, b.astype(f32)[:, :, None, :],
          c.astype(f32)[:, :, None, :], state)
    y = jnp.transpose(yt, (0, 1, 3, 2)).reshape(rows, h, p)
    return state, y


def select_ssm_backend(p: int, n: int, *, platform=None, override=None,
                       use_env_override: bool = True):
    """Pick ("kernel" | "dense", reason) for a decode state-update
    site through the shared ladder (family ``ssm_state``, env
    ``DL4J_TPU_SSM_STATE``). Structural gate: Mosaic wants a head's
    state ``[p, n]`` in whole float32 tiles. Auto rung: the kernel on
    TPU, the ``jax.numpy`` gather/scatter elsewhere (interpret mode is
    a conformance vehicle, not a fast path)."""
    from deeplearning4j_tpu.ops import kernel_select

    structural = None
    if p % 8 or n % 128:
        structural = f"head state [{p}, {n}] is not whole (8, 128) tiles"
    if override is None and use_env_override:
        override = kernel_select.gate_override("ssm_state")

    def _auto():
        plat = platform if platform is not None else kernel_select.platform()
        if plat == "tpu":
            return True, "auto: state-update kernel on tpu"
        return False, f"auto: platform '{plat}' is not tpu"

    sel = kernel_select.select("ssm_state", structural=structural,
                               auto=_auto, override=override,
                               use_env_override=False)
    return ("kernel" if sel.fused else "dense"), sel.reason


def ssm_state_update(state, layer: int, slots, x, dt, decay, b, c):
    """One decode step on the state pool through the ladder (decided
    at trace time, like every ``kernel_select`` family)."""
    backend, _ = select_ssm_backend(int(x.shape[2]), int(b.shape[2]))
    fn = (ssm_state_update_pallas if backend == "kernel"
          else ssm_state_update_reference)
    return fn(state, layer, slots, x, dt, decay, b, c)


# ---------------------------------------------------------------------------
# Mamba-1: a decay for every channel and state
# ---------------------------------------------------------------------------
#: tokens one grid step of the prefill scan walks: a float32 sublane tile
_SCAN_TOKENS = 8


def _selective_step(s, dt, x, a, b, c):
    """One token: ``s``/``a`` ``[n, c]``, ``dt``/``x`` ``[1, c]``,
    ``b``/``c`` ``[n, 1]``. Returns ``(s', y [1, c])``."""
    s = s * jnp.exp(dt * a) + (dt * x) * b
    return s, jnp.sum(s * c, axis=0, keepdims=True)


def selective_state_update_reference(state, layer: int, slots, x, dt, a,
                                     b, c):
    """``jax.numpy`` form of one Mamba-1 decode step on the state pool.

    ``state [layers, slots, n, ch]`` float32; ``slots [rows]`` int32 (0
    = scratch); ``x``/``dt`` ``[rows, ch]`` (``dt`` softplus'd), ``a
    [n, ch]`` (negative), ``b``/``c`` ``[rows, n]``. Returns ``(state,
    y [rows, ch])`` with the rows' slots of ``state[layer]`` replaced."""
    f32 = jnp.float32
    x, dt, b, c = (v.astype(f32) for v in (x, dt, b, c))
    s = (state[layer, slots] * jnp.exp(dt[:, None, :] * a.astype(f32))
         + (dt * x)[:, None, :] * b[:, :, None])
    return state.at[layer, slots].set(s), jnp.einsum("rnc,rn->rc", s, c)


def _selective_update_kernel(slots_ref, layer_ref, x_ref, dt_ref, b_ref,
                             c_ref, a_ref, s_ref, so_ref, y_ref):
    """One row a grid step. ``s_ref``/``so_ref`` are the same HBM block
    ``[n, ch]`` of the row's slot (aliased)."""
    import jax.experimental.pallas as pl

    row = pl.program_id(0)

    @pl.when(slots_ref[row] != 0)
    def _live():                                  # noqa: ANN202
        so_ref[...], y_ref[...] = _selective_step(
            s_ref[...], dt_ref[...], x_ref[...], a_ref[...], b_ref[...],
            c_ref[...])

    @pl.when(slots_ref[row] == 0)
    def _dead():                                  # noqa: ANN202
        y_ref[...] = jnp.zeros_like(y_ref)


def selective_state_update_pallas(state, layer: int, slots, x, dt, a, b,
                                  c):
    """The Pallas kernel — same contract as
    :func:`selective_state_update_reference`."""
    from deeplearning4j_tpu.ops import kernel_select
    return _selective_update_call(
        state, jnp.asarray([layer], jnp.int32), slots, x, dt, a, b, c,
        interpret=kernel_select.interpret_mode())


@functools.partial(jax.jit, static_argnames=("interpret",))
def _selective_update_call(state, layer, slots, x, dt, a, b, c, *,
                           interpret):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    rows, ch = x.shape
    n = b.shape[1]
    row_lanes = pl.BlockSpec((None, 1, ch),
                             lambda r, slots, layer: (r, 0, 0))
    row_col = pl.BlockSpec((None, n, 1),
                           lambda r, slots, layer: (r, 0, 0))
    slot_block = pl.BlockSpec(
        (None, None, n, ch),
        lambda r, slots, layer: (layer[0], slots[r], 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                    # slots, layer
        grid=(rows,),
        in_specs=[row_lanes, row_lanes, row_col, row_col,
                  pl.BlockSpec((n, ch), lambda r, slots, layer: (0, 0)),
                  slot_block],
        out_specs=[slot_block, row_lanes],
    )
    with jax.named_scope("pallas.selective_state_update"):
        state, y = pl.pallas_call(
            _selective_update_kernel,
            grid_spec=grid_spec,
            out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                       jax.ShapeDtypeStruct((rows, 1, ch), f32)],
            # operand 7 (after the two prefetched scalars): the pool,
            # updated in place
            input_output_aliases={7: 0},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
            interpret=interpret,
        )(slots.astype(jnp.int32), layer, x.astype(f32)[:, None, :],
          dt.astype(f32)[:, None, :], b.astype(f32)[:, :, None],
          c.astype(f32)[:, :, None], a.astype(f32), state)
    return state, y[:, 0]


def selective_state_update(state, layer: int, slots, x, dt, a, b, c):
    """One Mamba-1 decode step on the state pool through the ladder."""
    backend, _ = select_ssm_backend(int(b.shape[1]), int(x.shape[1]))
    fn = (selective_state_update_pallas if backend == "kernel"
          else selective_state_update_reference)
    return fn(state, layer, slots, x, dt, a, b, c)


def selective_scan_reference(x, dt, a, b, c):
    """The Mamba-1 recurrence token by token (``lax.scan``).

    ``x``/``dt`` ``[bt, t, ch]`` (``dt`` softplus'd; 0 at a padded
    position leaves the state as it is), ``a [n, ch]``, ``b``/``c``
    ``[bt, t, n]``. Returns ``(y [bt, t, ch], final_state [bt, n,
    ch])``, float32."""
    f32 = jnp.float32
    x, dt, a, b, c = (v.astype(f32) for v in (x, dt, a, b, c))

    def step(s, xs):
        x_t, dt_t, b_t, c_t = xs
        s = (s * jnp.exp(dt_t[:, None, :] * a)
             + (dt_t * x_t)[:, None, :] * b_t[:, :, None])
        return s, jnp.einsum("znc,zn->zc", s, c_t)

    s0 = jnp.zeros((x.shape[0], a.shape[0], x.shape[2]), f32)
    final, y = jax.lax.scan(step, s0, tuple(
        jnp.moveaxis(v, 1, 0) for v in (x, dt, b, c)))
    return jnp.moveaxis(y, 0, 1), final


def _selective_scan_kernel(x_ref, dt_ref, b_ref, c_ref, a_ref, y_ref,
                           s_ref):
    """``_SCAN_TOKENS`` tokens of one sequence a grid step, in order:
    ``s_ref [n, ch]`` is the sequence's one output block, resident
    across its steps, so the state is carried in on-chip memory."""
    import jax.experimental.pallas as pl

    @pl.when(pl.program_id(1) == 0)
    def _first():                                 # noqa: ANN202
        s_ref[...] = jnp.zeros_like(s_ref)

    s, a = s_ref[...], a_ref[...]
    for i in range(x_ref.shape[0]):
        s, y_ref[i:i + 1, :] = _selective_step(
            s, dt_ref[i:i + 1, :], x_ref[i:i + 1, :], a, b_ref[i],
            c_ref[i])
    s_ref[...] = s


def selective_scan_pallas(x, dt, a, b, c):
    """The Pallas kernel — same contract as
    :func:`selective_scan_reference`."""
    from deeplearning4j_tpu.ops import kernel_select
    return _selective_scan_call(x, dt, a, b, c,
                                interpret=kernel_select.interpret_mode())


@functools.partial(jax.jit, static_argnames=("interpret",))
def _selective_scan_call(x, dt, a, b, c, *, interpret):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    bt, t, ch = x.shape
    n = a.shape[0]
    q = _SCAN_TOKENS
    pad = -t % q
    if pad:                             # dt = 0: the state stands still
        x, dt, b, c = (jnp.pad(v, ((0, 0), (0, pad), (0, 0)))
                       for v in (x, dt, b, c))
    nq = (t + pad) // q
    lanes = pl.BlockSpec((None, None, q, ch), lambda z, j: (z, j, 0, 0))
    cols = pl.BlockSpec((None, None, q, n, 1),
                        lambda z, j: (z, j, 0, 0, 0))
    with jax.named_scope("pallas.selective_scan"):
        y, final = pl.pallas_call(
            _selective_scan_kernel,
            grid=(bt, nq),
            in_specs=[lanes, lanes, cols, cols,
                      pl.BlockSpec((n, ch), lambda z, j: (0, 0))],
            out_specs=[lanes,
                       pl.BlockSpec((None, n, ch), lambda z, j: (z, 0, 0))],
            out_shape=[jax.ShapeDtypeStruct((bt, nq, q, ch), f32),
                       jax.ShapeDtypeStruct((bt, n, ch), f32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")),
            interpret=interpret,
        )(x.astype(f32).reshape(bt, nq, q, ch),
          dt.astype(f32).reshape(bt, nq, q, ch),
          b.astype(f32).reshape(bt, nq, q, n, 1),
          c.astype(f32).reshape(bt, nq, q, n, 1), a.astype(f32))
    return y.reshape(bt, nq * q, ch)[:, :t], final


def selective_scan(x, dt, a, b, c):
    """The Mamba-1 prefill scan through the ladder."""
    backend, _ = select_ssm_backend(int(a.shape[0]), int(x.shape[2]))
    fn = (selective_scan_pallas if backend == "kernel"
          else selective_scan_reference)
    return fn(x, dt, a, b, c)
