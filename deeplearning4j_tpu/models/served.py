"""What the served decoder classes share below the block: the product
that reads bfloat16 weights as they are stored, the SwiGLU MLP, the
prompt's last valid position (the only one the head runs on), and the
one-row-a-sequence write into the paged pool. ``models/falcon_h1.py``
``models/phi4_flash.py`` and ``models/mimo_v2.py`` import these; none
keeps a copy."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def mm(x, w):
    """``x @ w`` with ``x`` rounded to the weights' type and a float32
    result: what the TPU's default precision does to a float32 product
    anyway, said outright so that bfloat16 weights are read as they
    are stored and never widened in memory."""
    return jnp.dot(x.astype(w.dtype), w,
                   preferred_element_type=jnp.float32)


def swiglu(h, gate, up, down, gate_scale=1.0):
    """``(silu(gate_scale * h W_gate) * h W_up) W_down``."""
    return mm(jax.nn.silu(mm(h, gate) * gate_scale) * mm(h, up), down)


def last_position(x, length):
    """Row ``length - 1`` of every sequence of ``x [b, t, ...]``."""
    return x[jnp.arange(x.shape[0]), jnp.asarray(length) - 1]


def pool_rows(block_tables, positions, block_size: int):
    """Where position ``positions [b]`` of each row lives in a paged
    pool: ``(block id, offset in the block)``, both ``[b]``."""
    rows = jnp.arange(positions.shape[0])
    return (block_tables[rows, positions // block_size],
            positions % block_size)


def greedy_by_reforward(model, params, prompt, max_tokens: int, eos_id=None):
    """Greedy decode by full re-forward each step (no cache, no ring, no
    state carried): what cached decode must match token for token. The
    ids are padded to a multiple of 32 under ``length``, so
    ``model.forward`` (jitted once, kept as ``model._forward_jit``)
    compiles once a size and not once a token."""
    eos = model.conf.eos_id if eos_id is None else eos_id
    ids = list(np.asarray(prompt, np.int32))
    if model._forward_jit is None:
        model._forward_jit = jax.jit(model.forward)
    out = []
    for _ in range(max_tokens):
        n = len(ids)
        padded = np.zeros((1, -(-n // 32) * 32), np.int32)
        padded[0, :n] = ids
        logits = model._forward_jit(params, padded, np.asarray([n], np.int32))
        nxt = int(jnp.argmax(logits[0, n - 1]))
        out.append(nxt)
        ids.append(nxt)
        if nxt == eos:
            break
    return out


def write_rows(pool, layer, blk, off, new):
    """One token a row into ``pool [layers, blocks, block, lanes]`` at
    ``[layer, blk, off]`` (``new [b, ...]``, its heads side by side, in
    the pool's type). The layer is named, never taken whole: a scatter
    whose window spans the layers makes XLA relay the pool."""
    return pool.at[layer, blk, off].set(
        jnp.reshape(new, (new.shape[0], -1)).astype(pool.dtype))
