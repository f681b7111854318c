"""MiMo-V2 decoder (the MiMo-V2-Flash family; ``model_type`` ``mimo_v2``)
behind the paged-decode serving contract, as **one chip's share of an
expert-parallel deployment**.

Every layer is ``x += Attn(RMSNorm(x)); x += FFN(RMSNorm(x))``. Two
published per-layer patterns pick the kinds:

- ``layer_pattern[l]`` 0: **full** causal attention, ``n_kv_heads`` KV
  heads, rotary base ``rope_theta``; 1: **sliding-window** attention
  over the last ``window`` positions, ``swa_n_kv_heads`` KV heads,
  rotary base ``swa_rope_theta``, and a **learned sink**: one logit a
  query head that joins the softmax's denominator and adds no value.
  Keys (and queries) are ``head_dim`` wide, values ``v_head_dim``;
  rotary positions (rotate-half) on the first ``rotary_dim`` dimensions
  of every q and k head; V is scaled by ``value_scale`` before it is
  cached.
- ``moe_pattern[l]`` 0: a dense SwiGLU ``d_ff`` wide; 1: **sparse
  experts**: a router ``n_experts`` wide (sigmoid scores, a selection
  bias, top ``top_k``, gates renormalised, no shared expert), of which
  this chip holds experts ``[experts_first, experts_first +
  experts_held)`` and computes their part of the result
  (``ops/moe.py``; dropless). What the other experts would add is left
  out and that partial result goes on to the next layer: on one chip
  the layer runs without its exchange.

A live sequence keeps **two kinds of state**: blocks of the full
layers' K/V that grow with the context (the pool's K/V arrays,
``kv_layers`` = the number of full layers, K ``n_kv_heads * head_dim``
lanes a token and V ``n_kv_heads * v_head_dim``), and a **ring of
``window`` positions** in each window layer (slot kinds ``ring_k`` /
``ring_v`` with lane widths of their own; position ``p`` at ``p mod
window``: rotary positions are applied before the write, so the order
in a ring does not matter). The paged kernel reads all of it
(``ops.attention_pallas``: a V head width of its own, the sink).

:meth:`MiMoV2LM.prefill` attends the window layers as a **band**
(blocks of ``window`` queries over two blocks of keys, never a ``t x t``
mask), the full layers a block of queries at a time over the keys up to
it, wraps the rings, and runs the head on the last valid position.
``prefill`` and ``decode_step`` return last what the routing did
(:attr:`MiMoV2LM.step_counts`).

Weights are whatever type ``params`` holds (bfloat16 in the benchmark;
the router and its bias float32); the residual stream and activations
are float32, products at the backend's default precision, the router's
product and its top-k at ``highest``.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.models.served import (
    greedy_by_reforward, last_position, mm as _mm, pool_rows, swiglu,
    write_rows)
from deeplearning4j_tpu.ops import moe

_NEG = -1e30
#: query rows a block of the prefill's full attention takes at once
_Q_BLOCK = 512


@dataclass
class MiMoV2Config:
    """Hyperparameters. The defaults are a test size with every kind of
    layer: full and window attention, a dense and expert FFNs, K heads
    wider than V heads, a quarter of the experts held."""

    vocab_size: int = 96
    d_model: int = 64
    n_heads: int = 8
    n_kv_heads: int = 2                 # full layers
    swa_n_kv_heads: int = 4             # window layers
    head_dim: int = 24                  # q and k
    v_head_dim: int = 16
    d_ff: int = 128                     # the dense layers' SwiGLU
    moe_d_ff: int = 32                  # an expert's
    n_experts: int = 16                 # the router's width
    top_k: int = 4
    experts_first: int = 4              # this chip's share
    experts_held: int = 4
    window: int = 8
    layer_pattern: Tuple[int, ...] = (0, 1, 1, 1)     # 0 full, 1 window
    moe_pattern: Tuple[int, ...] = (0, 1, 1, 1)       # 0 dense, 1 experts
    rope_theta: float = 1e7
    swa_rope_theta: float = 1e4
    partial_rotary_factor: float = 0.334
    value_scale: float = 0.707
    rms_eps: float = 1e-5
    max_len: int = 512
    eos_id: int = 1
    seed: int = 0
    n_layers: int = field(init=False)

    def __post_init__(self):
        self.layer_pattern = tuple(int(k) for k in self.layer_pattern)
        self.moe_pattern = tuple(int(k) for k in self.moe_pattern)
        self.n_layers = len(self.layer_pattern)
        if len(self.moe_pattern) != self.n_layers:
            raise ValueError("one entry a layer in both patterns")
        if self.n_heads % self.n_kv_heads or self.n_heads % self.swa_n_kv_heads:
            raise ValueError("query heads divide over the KV heads")
        if not 0 <= self.experts_first <= self.n_experts - self.experts_held:
            raise ValueError("the held experts lie inside the router's")
        if self.rotary_dim % 2:
            raise ValueError("rotary dimensions come in pairs")

    @staticmethod
    def from_published(cfg: dict, **kw) -> "MiMoV2Config":
        """From a ``config.json``-shaped dict cut to a chip's share:
        ``num_hidden_layers`` leading entries of the two patterns,
        ``n_routed_experts`` the experts held here (from
        ``experts_first``; the router keeps ``router_experts``, the
        published count), ``vocab_size`` the slice held."""
        n = cfg["num_hidden_layers"]
        return MiMoV2Config(
            vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
            n_heads=cfg["num_attention_heads"],
            n_kv_heads=cfg["num_key_value_heads"],
            swa_n_kv_heads=cfg["swa_num_key_value_heads"],
            head_dim=cfg["head_dim"], v_head_dim=cfg["v_head_dim"],
            d_ff=cfg["intermediate_size"],
            moe_d_ff=cfg["moe_intermediate_size"],
            n_experts=cfg.get("router_experts", cfg["n_routed_experts"]),
            top_k=cfg["num_experts_per_tok"],
            experts_first=cfg.get("experts_first", 0),
            experts_held=cfg["n_routed_experts"],
            window=cfg["sliding_window"],
            layer_pattern=tuple(cfg["hybrid_layer_pattern"][:n]),
            moe_pattern=tuple(cfg["moe_layer_freq"][:n]),
            rope_theta=cfg["rope_theta"],
            swa_rope_theta=cfg["swa_rope_theta"],
            partial_rotary_factor=cfg["partial_rotary_factor"],
            value_scale=cfg["attention_value_scale"],
            rms_eps=cfg["layernorm_epsilon"], **kw)

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def kinds(self) -> tuple:
        return tuple("window" if k else "full" for k in self.layer_pattern)

    def kv_heads(self, kind: str) -> int:
        return self.swa_n_kv_heads if kind == "window" else self.n_kv_heads


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1,
                                      keepdims=True) + eps) * g


class MiMoV2LM:
    """Full and sliding-window attention with a learned sink, keys wider
    than values, a dense layer and layers of sparse experts of which
    this chip holds a share, over token ids."""

    #: what ``prefill`` and ``decode_step`` return last, int32 each,
    #: summed over the expert layers (``ops.moe.COUNTS``)
    step_counts = moe.COUNTS

    def __init__(self, conf: Optional[MiMoV2Config] = None, **kw):
        self.conf = conf if conf is not None else MiMoV2Config(**kw)
        self.params = None
        self._forward_jit = None
        kinds = self.conf.kinds
        #: a layer's row in its kind's cache arrays
        self._ordinal = [kinds[:l].count(k) for l, k in enumerate(kinds)]

    # -- what the cache manager holds for a sequence --------------------
    @property
    def kv_layers(self) -> int:
        """K/V layers whose blocks grow with the context: the full ones."""
        return self.conf.kinds.count("full")

    def state_shapes(self) -> dict:
        """The window layers' K and V rings, each with its own lanes."""
        c = self.conf
        n = c.kinds.count("window")
        ring = {"dtype": None, "layers": n, "window": c.window}
        return {"ring_k": dict(ring, shape=(c.window,
                                            c.swa_n_kv_heads * c.head_dim)),
                "ring_v": dict(ring, shape=(c.window,
                                            c.swa_n_kv_heads * c.v_head_dim))}

    def cache_reads(self) -> dict:
        """Who reads what in one decode step: each full layer reads its
        own growing K/V layer, each window layer a ring; and a token's
        bytes in each (bfloat16 K and V side by side)."""
        c = self.conf
        wide = c.head_dim + c.v_head_dim
        return {"kv_readers": self.kv_layers,
                "window_layers": c.kinds.count("window"),
                "window": c.window,
                "kv_token_bytes": 2 * c.n_kv_heads * wide,
                "window_token_bytes": 2 * c.swa_n_kv_heads * wide}

    # -- init -----------------------------------------------------------
    def init(self, key=None) -> dict:
        """Seeded float32 weights in the serving layout ``{entry: {leaf:
        array}}``: projections ``[in, out]``, ``wqkv`` fused ``[q | k |
        v]``, the held experts stacked ``[held, in, out]``."""
        c = self.conf
        if key is None:
            key = jax.random.PRNGKey(c.seed)
        d, f, e = c.d_model, c.moe_d_ff, c.experts_held
        keys = iter(jax.random.split(key, 4 + 12 * c.n_layers))

        def dense(shape, std=0.02):
            return jax.random.normal(next(keys), shape, jnp.float32) * std

        params = {"embed": {"tok": dense((c.vocab_size, d))}}
        for l, kind in enumerate(c.kinds):
            hkv = c.kv_heads(kind)
            p = {"ln1": jnp.ones((d,)), "ln2": jnp.ones((d,)),
                 "wqkv": dense((d, c.n_heads * c.head_dim
                                + hkv * (c.head_dim + c.v_head_dim))),
                 "wo": dense((c.n_heads * c.v_head_dim, d))}
            if kind == "window":
                p["sink"] = dense((c.n_heads,), 1.0)
            if c.moe_pattern[l]:
                p.update(router=dense((d, c.n_experts)),
                         bias=dense((c.n_experts,)),
                         e_gate=dense((e, d, f)), e_up=dense((e, d, f)),
                         e_down=dense((e, f, d)))
            else:
                p.update(gate=dense((d, c.d_ff)), up=dense((d, c.d_ff)),
                         down=dense((c.d_ff, d)))
            params[f"layer_{l}"] = p
        params["head"] = {"norm": jnp.ones((d,)),
                          "w": dense((d, c.vocab_size))}
        self.params = params
        return params

    # -- shared pieces --------------------------------------------------
    def _rotate(self, x, positions, theta):
        """Rotary positions (rotate-half) on the first ``rotary_dim``
        dimensions of ``x [..., heads, head_dim]``, ``positions``
        shaped like ``x``'s leading axes."""
        r = self.conf.rotary_dim
        inv = 1.0 / (theta ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r))
        ang = positions.astype(jnp.float32)[..., None, None] * inv
        cos, sin = jnp.cos(ang), jnp.sin(ang)
        x1, x2 = x[..., :r // 2], x[..., r // 2:r]
        return jnp.concatenate(
            [x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., r:]], axis=-1)

    def _qkv(self, p, kind, h, positions):
        """``q [.., H, dk]``, ``k [.., Hkv, dk]`` (both rotated), ``v
        [.., Hkv, dv]`` (scaled) of ``h [.., d]``."""
        c = self.conf
        hkv, dk, dv = c.kv_heads(kind), c.head_dim, c.v_head_dim
        theta = c.swa_rope_theta if kind == "window" else c.rope_theta
        qkv = _mm(h, p["wqkv"])
        nq, nk = c.n_heads * dk, hkv * dk
        lead = h.shape[:-1]
        q = qkv[..., :nq].reshape(lead + (c.n_heads, dk))
        k = qkv[..., nq:nq + nk].reshape(lead + (hkv, dk))
        v = qkv[..., nq + nk:].reshape(lead + (hkv, dv)) * c.value_scale
        return (self._rotate(q, positions, theta),
                self._rotate(k, positions, theta), v)

    def _ffn(self, p, l, x, valid):
        """The layer's second half on rows ``x [n, d]``: ``(x + FFN,
        counts or None)``."""
        c = self.conf
        h = _rms(x, p["ln2"], c.rms_eps)
        if not c.moe_pattern[l]:
            return x + swiglu(h, p["gate"], p["up"], p["down"]), None
        with jax.named_scope("ffn.experts"):
            out, counts = moe.held_expert_layer(
                h, p["router"], p["bias"],
                (p["e_gate"], p["e_up"], p["e_down"]),
                c.experts_first, c.experts_held, top_k=c.top_k,
                valid=valid)
        return x + out, counts

    def _logits(self, params, x):
        hp = params["head"]
        return _mm(_rms(x, hp["norm"], self.conf.rms_eps), hp["w"])

    @staticmethod
    def _softmax_sink(sc, sink):
        """Softmax over the last axis of ``sc [b, .., H.., q, k]`` with
        ``sink`` (broadcastable to ``sc[..., :1]``, or None) in the
        denominator."""
        m = jnp.max(sc, axis=-1, keepdims=True)
        extra = 0.0
        if sink is not None:
            m = jnp.maximum(m, sink)
            extra = jnp.exp(sink - m)
        w = jnp.exp(sc - m)
        return w / (jnp.sum(w, axis=-1, keepdims=True) + extra)

    def _attend_full(self, q, k, v, valid):
        """Causal attention of ``q [b, t, H, dk]`` over ``k [b, t, Hkv,
        dk]`` / ``v [b, t, Hkv, dv]``, ``_Q_BLOCK`` queries at a time
        over the keys up to the block's end: ``[b, t, H * dv]``."""
        c = self.conf
        b, t = q.shape[:2]
        hkv = k.shape[2]
        scale = 1.0 / math.sqrt(c.head_dim)
        qg = q.reshape(b, t, hkv, -1, c.head_dim)
        out = []
        for lo in range(0, t, _Q_BLOCK):
            hi = min(t, lo + _Q_BLOCK)
            sc = jnp.einsum("bqkgd,bskd->bkgqs", qg[:, lo:hi], k[:, :hi],
                            preferred_element_type=jnp.float32)
            ok = (jnp.arange(hi)[None, :] <= jnp.arange(lo, hi)[:, None]) \
                & valid[:, None, :hi]                        # [b, q, s]
            sc = jnp.where(ok[:, None, None], sc * scale, _NEG)
            w = self._softmax_sink(sc, None)
            out.append(jnp.einsum("bkgqs,bske->bqkge", w, v[:, :hi]))
        return jnp.concatenate(out, axis=1).reshape(b, t, -1)

    def _attend_window(self, q, k, v, valid, sink):
        """The last ``window`` positions as a band: block ``i`` of
        ``window`` queries over key blocks ``i - 1`` and ``i``."""
        c = self.conf
        b, t = q.shape[:2]
        w_, hkv = c.window, k.shape[2]
        pad = -t % w_
        if pad:
            q, k, v = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
                       for a in (q, k, v))
            valid = jnp.pad(valid, ((0, 0), (0, pad)))
        nb = (t + pad) // w_

        def two(a):         # [b, nb * w, ...] -> [b, nb, 2 w, ...]
            a = a.reshape((b, nb, w_) + a.shape[2:])
            before = jnp.concatenate([jnp.zeros_like(a[:, :1]), a[:, :-1]], 1)
            return jnp.concatenate([before, a], axis=2)
        qg = q.reshape(b, nb, w_, hkv, -1, c.head_dim)
        kk, vv = two(k), two(v)
        sc = jnp.einsum("bnqkgd,bnskd->bnkgqs", qg, kk,
                        preferred_element_type=jnp.float32)
        sc = sc / math.sqrt(c.head_dim)
        i = jnp.arange(w_)[:, None] + w_                # query, in the pair
        j = jnp.arange(2 * w_)[None, :]
        near = (j <= i) & (i - j < w_)                          # [q, s]
        live = two(valid[..., None])[..., 0]                    # [b, nb, s]
        first = (jnp.arange(nb)[:, None] > 0) | (j[0] >= w_)    # [nb, s]
        ok = near[None, None] & (live & first[None])[:, :, None, :]
        sc = jnp.where(ok[:, :, None, None], sc, _NEG)
        sk = sink.astype(jnp.float32).reshape(1, 1, hkv, -1, 1, 1)
        w = self._softmax_sink(sc, sk)
        o = jnp.einsum("bnkgqs,bnske->bnqkge", w, vv)
        return o.reshape(b, nb * w_, -1)[:, :t]

    def _length(self, tokens, length):
        b, t = tokens.shape
        return (jnp.full((b,), t, jnp.int32) if length is None
                else jnp.asarray(length, jnp.int32))

    def _body(self, params, tokens, length):
        """Every layer over ``tokens [b, t]``: the stream ``[b, t, d]``,
        every layer's rotated K and scaled V, the routing counts."""
        c = self.conf
        b, t = tokens.shape
        pos = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))
        valid = pos < length[:, None]
        x = params["embed"]["tok"][tokens].astype(jnp.float32)
        ks, vs = [], []
        counts = jnp.zeros((len(self.step_counts),), jnp.int32)
        for l, kind in enumerate(c.kinds):
            p = params[f"layer_{l}"]
            h = _rms(x, p["ln1"], c.rms_eps)
            with jax.named_scope(f"mixer.{kind}"):
                q, k, v = self._qkv(p, kind, h, pos)
                if kind == "window":
                    o = self._attend_window(q, k, v, valid, p["sink"])
                else:
                    o = self._attend_full(q, k, v, valid)
                x = x + _mm(o, p["wo"])
            ks.append(k)
            vs.append(v)
            rows, got = self._ffn(p, l, x.reshape(b * t, -1),
                                  valid.reshape(-1))
            x = rows.reshape(b, t, -1)
            if got is not None:
                counts = counts + got
        return x, ks, vs, counts

    def forward(self, params, tokens, length=None):
        """Logits ``[b, t, vocab]`` at every position (the tests' full
        forward; small sizes only)."""
        tokens = jnp.asarray(tokens)
        x, *_ = self._body(params, tokens, self._length(tokens, length))
        return self._logits(params, x)

    def output(self, tokens):
        """Full-sequence logits (the generic serving surface)."""
        if self.params is None:
            self.init()
        return self.forward(self.params, tokens)

    def prefill(self, params, tokens, length):
        """Prompt pass: ``(last_logits [b, vocab], k [full layers, b, t,
        Hkv, dk], v [full layers, b, t, Hkv, dv], ring_k, ring_v [window
        layers, b, window, lanes], counts)``. A ring holds position ``p``
        of the last ``min(length, window)`` at ``p mod window``."""
        c = self.conf
        tokens = jnp.asarray(tokens)
        length = self._length(tokens, length)
        x, ks, vs, counts = self._body(params, tokens, length)
        # ring index r holds the newest position p < length, p = r mod W
        r = jnp.arange(c.window, dtype=jnp.int32)[None, :]
        last = length[:, None] - 1
        at = jnp.where(r <= last, r + c.window * ((last - r) // c.window), 0)

        def ring(a):                    # [b, t, Hkv, d] -> [b, W, lanes]
            a = a.reshape(a.shape[0], a.shape[1], -1)
            return jnp.take_along_axis(a, at[:, :, None], axis=1)

        def of(kind, arrays, fn=lambda a: a):
            return jnp.stack([fn(a) for a, k_ in zip(arrays, c.kinds)
                              if k_ == kind])
        return (self._logits(params, last_position(x, length)),
                of("full", ks), of("full", vs),
                of("window", ks, ring), of("window", vs, ring), counts)

    # -- one fused decode step over the cache ---------------------------
    def decode_step(self, params, tokens, positions, k_pool, v_pool,
                    ring_k, ring_v, block_tables, state_slots, *,
                    paged: bool = False):
        """One token for every row of the decode batch.

        ``k_pool [full layers, blocks, block, Hkv * dk]``, ``v_pool [..,
        Hkv * dv]``; ``ring_k`` / ``ring_v`` ``[window layers, slots,
        window / block, block, lanes]``; ``block_tables [b, max_blocks]``;
        ``state_slots [b]`` (0, the scratch slot, for a dead row, which
        is routed to no expert). Returns ``(logits [b, vocab], k_pool,
        v_pool, ring_k, ring_v, counts)``."""
        from deeplearning4j_tpu.ops.attention_pallas import (
            paged_attention_reference, paged_decode_attention)
        c = self.conf
        bs = k_pool.shape[2]
        kernel = paged_decode_attention if paged \
            else paged_attention_reference
        blk, off = pool_rows(block_tables, positions, bs)
        lengths = positions + 1
        # the rings as pools: slot s is blocks [s * per, (s + 1) * per)
        k_shape, v_shape = ring_k.shape, ring_v.shape
        per = k_shape[2]
        ring_k = ring_k.reshape(k_shape[0], -1, bs, k_shape[-1])
        ring_v = ring_v.reshape(v_shape[0], -1, bs, v_shape[-1])
        at = positions % c.window
        ring_blk, ring_off = state_slots * per + at // bs, at % bs
        ring_tables = (state_slots[:, None] * per
                       + jnp.arange(per, dtype=state_slots.dtype)[None, :])
        ring_len = jnp.minimum(lengths, c.window)
        live = state_slots != 0

        x = params["embed"]["tok"][tokens].astype(jnp.float32)     # [b, d]
        counts = jnp.zeros((len(self.step_counts),), jnp.int32)
        for l, kind in enumerate(c.kinds):
            p = params[f"layer_{l}"]
            i = self._ordinal[l]
            h = _rms(x, p["ln1"], c.rms_eps)
            with jax.named_scope(f"mixer.{kind}"):
                q, k, v = self._qkv(p, kind, h, positions)
                if kind == "window":
                    ring_k = write_rows(ring_k, i, ring_blk, ring_off, k)
                    ring_v = write_rows(ring_v, i, ring_blk, ring_off, v)
                    o = kernel(q, ring_k, ring_v, ring_tables, ring_len, i,
                               sink=p["sink"])
                else:
                    k_pool = write_rows(k_pool, i, blk, off, k)
                    v_pool = write_rows(v_pool, i, blk, off, v)
                    o = kernel(q, k_pool, v_pool, block_tables, lengths, i)
                x = x + _mm(o.reshape(o.shape[0], -1), p["wo"])
            x, got = self._ffn(p, l, x, live)
            if got is not None:
                counts = counts + got
        return (self._logits(params, x), k_pool, v_pool,
                ring_k.reshape(k_shape), ring_v.reshape(v_shape), counts)

    # -- reference decode (conformance gate) ----------------------------
    def reference_decode(self, params, prompt, max_tokens: int,
                         eos_id: Optional[int] = None):
        """Greedy decode by full re-forward each step: what cached
        decode must match token for token."""
        return greedy_by_reforward(self, params, prompt, max_tokens, eos_id)
