"""Expert parallelism — Mixture-of-Experts with all_to_all dispatch.

The reference has NO MoE (SURVEY.md §2.6 P10: ABSENT). TPU-native
extension, GShard/Switch style:

- **gating** is dense one-hot dispatch/combine einsums (MXU-friendly;
  no dynamic shapes, so XLA can tile it);
- **expert parallelism** shards the expert dimension over a mesh axis
  (canonically aliased to the ``data`` axis, DeepSpeed-style: expert
  weights replace the DP replication for expert params);
- tokens move to their experts and back via TWO ``lax.all_to_all``
  collectives (ICI), the canonical EP exchange.

Capacity model: each expert processes at most
``C = ceil(k * tokens/E * capacity_factor)`` tokens per shard;
overflow tokens are dropped (their combine weight is 0 and the
residual connection carries them through — standard Switch behavior).

All functions run inside ``shard_map``. Gradients flow through
dispatch/combine einsums and all_to_all transposes automatically.

This layer drops at its capacity and trains (the dry-run step of
``models/transformer.py``). The layer that serves is ``ops/moe.py``:
dropless, told which experts it holds, no exchange on one chip.
"""
from __future__ import annotations

import math
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

EXPERT_AXIS = "expert"


def topk_gating(logits, k: int = 2, capacity: Optional[int] = None,
                capacity_factor: float = 1.25,
                rng: Optional[jax.Array] = None,
                noise_std: float = 0.0):
    """Top-k gating with capacity (GShard §3.2 / Switch top-1).

    logits: [n, E]. Returns (combine [n, E, C], dispatch [n, E, C]
    bool, aux_loss scalar, C).
    """
    n, e = logits.shape
    if capacity is None:
        capacity = max(4, math.ceil(k * n / e * capacity_factor))
    c = capacity
    if rng is not None and noise_std > 0.0:
        logits = logits + noise_std * jax.random.normal(
            rng, logits.shape, logits.dtype)
    probs = jax.nn.softmax(logits, axis=-1)          # [n, E]

    combine = jnp.zeros((n, e, c), logits.dtype)
    dispatch = jnp.zeros((n, e, c), bool)
    # running per-expert fill count, updated between the k passes
    fill = jnp.zeros((e,), jnp.int32)
    masked = probs
    gate_sum = jnp.zeros((n,), logits.dtype)
    picks = []
    for _ in range(k):
        idx = jnp.argmax(masked, axis=-1)            # [n]
        onehot = jax.nn.one_hot(idx, e, dtype=jnp.int32)   # [n, E]
        pos = fill[None, :] + jnp.cumsum(onehot, axis=0) - onehot
        pos = jnp.sum(pos * onehot, axis=-1)         # [n] queue slot
        keep = pos < c
        gate = jnp.take_along_axis(probs, idx[:, None], -1)[:, 0]
        gate = jnp.where(keep, gate, 0.0)
        gate_sum = gate_sum + gate
        picks.append((idx, pos, keep, gate))
        fill = fill + jnp.sum(onehot * keep[:, None], axis=0)
        masked = masked * (1 - onehot)               # exclude chosen

    # renormalize the kept gates so they sum to 1 per token (GShard)
    denom = jnp.maximum(gate_sum, 1e-9)
    for idx, pos, keep, gate in picks:
        w = (gate / denom)[:, None, None]
        hot = (jax.nn.one_hot(idx, e, dtype=logits.dtype)[:, :, None]
               * jax.nn.one_hot(pos, c, dtype=logits.dtype)[:, None, :])
        hot = hot * keep[:, None, None]
        combine = combine + w * hot
        dispatch = dispatch | (hot > 0)

    # load-balancing aux loss (Switch eq. 4): E * sum_e f_e * p_e
    top1 = jax.nn.one_hot(jnp.argmax(probs, -1), e, dtype=probs.dtype)
    f = jnp.mean(top1, axis=0)
    p = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(f * p)
    return combine, dispatch, aux, c


def moe_ffn(x, params, axis: Optional[str] = EXPERT_AXIS, k: int = 2,
            capacity_factor: float = 1.25,
            capacity: Optional[int] = None,
            activation: Callable = jax.nn.gelu,
            rng: Optional[jax.Array] = None,
            noise_std: float = 0.0) -> Tuple[jax.Array, jax.Array]:
    """MoE feed-forward over [b, t, d] activations.

    params: ``Wg [d, E]`` gate (replicated), ``Wi [E_local, d, ff]``,
    ``Wo [E_local, ff, d]`` expert weights (sharded over ``axis``).
    ``axis=None`` runs all experts locally (no EP — the tp=1 path).
    Returns (out [b, t, d], aux_loss).
    """
    b, t, d = x.shape
    xf = x.reshape(b * t, d)
    ep = _axis_size(axis)
    e_local = params["Wi"].shape[0]
    e = e_local * ep

    logits = xf @ params["Wg"]                        # [n, E]
    combine, dispatch, aux, c = topk_gating(
        logits, k=k, capacity=capacity,
        capacity_factor=capacity_factor, rng=rng, noise_std=noise_std)

    # dispatch tokens into per-expert slots: [E, C, d]
    slots = jnp.einsum("nec,nd->ecd", dispatch.astype(x.dtype), xf)
    if ep > 1:
        # [E, C, d] -> exchange expert dim for slot dim:
        # each device keeps its E/ep experts, receives every shard's
        # slots for them -> [E/ep, C*ep, d]
        slots = lax.all_to_all(slots, axis, split_axis=0,
                               concat_axis=1, tiled=True)

    h = activation(jnp.einsum("ecd,edf->ecf", slots, params["Wi"]))
    out = jnp.einsum("ecf,efd->ecd", h, params["Wo"])

    if ep > 1:
        out = lax.all_to_all(out, axis, split_axis=1, concat_axis=0,
                             tiled=True)
    y = jnp.einsum("nec,ecd->nd", combine, out)
    return y.reshape(b, t, d), aux


def _axis_size(axis: Optional[str]) -> int:
    from .mesh import axis_size
    return 1 if axis is None else axis_size(axis)


def init_moe_params(key, d_model: int, d_ff: int, n_experts: int,
                    ep: int, ep_rank, dtype=jnp.float32):
    """One EP-shard of MoE params, sliced from globally-initialized
    weights so (ep=k) == (ep=1) numerically. ``ep_rank`` may be traced."""
    kg, ki, ko = jax.random.split(key, 3)
    wg = jax.random.normal(kg, (d_model, n_experts), dtype) \
        * (d_model ** -0.5)
    wi = jax.random.normal(
        ki, (n_experts, d_model, d_ff), dtype) * (d_model ** -0.5)
    wo = jax.random.normal(
        ko, (n_experts, d_ff, d_model), dtype) * (d_ff ** -0.5)
    el = n_experts // ep
    return {
        "Wg": wg,
        "Wi": lax.dynamic_slice_in_dim(wi, ep_rank * el, el, axis=0),
        "Wo": lax.dynamic_slice_in_dim(wo, ep_rank * el, el, axis=0),
    }
