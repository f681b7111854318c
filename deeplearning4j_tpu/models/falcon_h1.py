"""Falcon-H1 decoder: a parallel hybrid block behind the paged-decode
serving contract.

Every block reads one RMS-normed input with **two mixers side by side**
— a Mamba-2 (SSD) state-space mixer and grouped-query attention with
rotary positions — adds both to the residual stream in one step, then
a SwiGLU MLP. Fourteen fixed scalar multipliers (muP) sit on the
embedding, each mixer's input and output, the keys, the five segments
of the state-space input projection, the MLP's gate and output, and
the logits (``FalconH1Config``; forms as in the public ``transformers``
``modeling_falcon_h1.py``).

The serving contract is :class:`~deeplearning4j_tpu.models.decoder.
DecoderLM`'s, with a second kind of per-sequence state beside the
paged K/V:

- :meth:`FalconH1LM.prefill` — the padded prompt through full causal
  attention and the **chunked SSD scan** (``ops.ssm_pallas.
  ssd_chunked_scan``), returning the logits of position ``length - 1``
  only (the head never runs on the whole bucket), every layer's K/V,
  and every layer's recurrent state and convolution tail **as they
  stand after position ``length - 1``**: ``dt`` is masked to 0 past
  the length, which leaves the state untouched there, and the tail is
  the last ``d_conv - 1`` valid inputs of the convolution.
- :meth:`FalconH1LM.decode_step` — one token a row: its K/V rows
  written into the paged pool (``n_kv_heads * head_dim`` lanes a
  token) and the stacked pool read as it is stored by the
  grouped-query paged kernel; the row's state slot updated in place by
  ``ops.ssm_pallas.ssm_state_update``. Rows of the bucket that hold no
  sequence name block 0 and slot 0, the scratch ones.

``state_shapes()`` tells the cache manager what a slot holds. The
engine donates the cache's arrays to the commit and decode programs
of every model, so the in-place state update is in place across the
program boundary too (the pools are never held twice).
Weights are whatever type ``params`` holds (bfloat16 in the
benchmark); the residual stream and every activation are float32, the
products run at the backend's default precision, the recurrent state
and its decay are float32.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.models.served import (
    last_position, mm as _mm, pool_rows, swiglu, write_rows)


@dataclass
class FalconH1Config:
    """Hyperparameters under the names of the published ``config.json``
    where the serving stack has no name of its own. The defaults are a
    test size that keeps every published ratio (5 query heads a KV
    head, 2 state groups, a convolution of width 4)."""

    vocab_size: int = 96
    n_layers: int = 2
    d_model: int = 40
    n_heads: int = 10                   # query heads
    n_kv_heads: int = 2
    head_dim: int = 8
    d_ff: int = 64
    max_len: int = 512
    eos_id: int = 1
    seed: int = 0
    rope_theta: float = 1e11
    rms_norm_eps: float = 1e-5
    # the state-space mixer
    mamba_d_ssm: int = 32
    mamba_n_heads: int = 4
    mamba_d_head: int = 8
    mamba_d_state: int = 16
    mamba_n_groups: int = 2
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 128
    # muP multipliers
    embedding_multiplier: float = 1.0
    lm_head_multiplier: float = 1.0
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 1.0
    key_multiplier: float = 1.0
    ssm_in_multiplier: float = 1.0
    ssm_out_multiplier: float = 1.0
    ssm_multipliers: Tuple[float, ...] = field(
        default_factory=lambda: (1.0,) * 5)
    mlp_multipliers: Tuple[float, ...] = field(
        default_factory=lambda: (1.0, 1.0))

    @staticmethod
    def from_published(cfg: dict, **kw) -> "FalconH1Config":
        """From a ``config.json``-shaped dict (``num_hidden_layers``
        as the caller cut it)."""
        same = ("vocab_size", "head_dim", "rope_theta", "rms_norm_eps",
                "mamba_d_ssm", "mamba_n_heads", "mamba_d_head",
                "mamba_d_state", "mamba_n_groups", "mamba_d_conv",
                "mamba_chunk_size", "embedding_multiplier",
                "lm_head_multiplier", "attention_in_multiplier",
                "attention_out_multiplier", "key_multiplier",
                "ssm_in_multiplier", "ssm_out_multiplier")
        return FalconH1Config(
            n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
            n_heads=cfg["num_attention_heads"],
            n_kv_heads=cfg["num_key_value_heads"],
            d_ff=cfg["intermediate_size"],
            ssm_multipliers=tuple(cfg["ssm_multipliers"]),
            mlp_multipliers=tuple(cfg["mlp_multipliers"]),
            **{k: cfg[k] for k in same}, **kw)

    @property
    def conv_dim(self) -> int:
        return self.mamba_d_ssm + 2 * self.mamba_n_groups * self.mamba_d_state

    @property
    def in_proj_dim(self) -> int:
        return self.mamba_d_ssm + self.conv_dim + self.mamba_n_heads


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * w


def _rope(x, positions, theta):
    """Rotary positions on ``x [..., t, heads, dh]`` at ``positions
    [..., t]``: half-rotation pairs over all of ``dh``."""
    dh = x.shape[-1]
    inv = 1.0 / (float(theta) ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = positions.astype(jnp.float32)[..., None] * inv
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[..., None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[..., None, :]
    half = jnp.concatenate([-x[..., dh // 2:], x[..., :dh // 2]], -1)
    return x * cos + half * sin


class FalconH1LM:
    """Parallel Mamba-2 + grouped-query attention decoder over token
    ids."""

    def __init__(self, conf: Optional[FalconH1Config] = None, **kw):
        self.conf = conf if conf is not None else FalconH1Config(**kw)
        self.params = None

    # -- what the cache manager holds for a sequence --------------------
    def state_shapes(self) -> dict:
        """``{kind: (shape a slot, dtype)}`` of the recurrent state a
        live sequence keeps beside its K/V blocks, in the order
        ``prefill`` / ``decode_step`` pass the arrays."""
        c = self.conf
        return {"ssm": ((c.mamba_n_heads, c.mamba_d_head,
                         c.mamba_d_state), jnp.float32),
                "conv": ((c.mamba_d_conv - 1, c.conv_dim), jnp.float32)}

    # -- init -----------------------------------------------------------
    def init(self, key=None) -> dict:
        """Seeded float32 weights in the serving layout ``{entry:
        {leaf: array}}`` (projections ``[in, out]``)."""
        c = self.conf
        if key is None:
            key = jax.random.PRNGKey(c.seed)
        d, f, h = c.d_model, c.d_ff, c.mamba_n_heads

        def dense(k, shape):
            return jax.random.normal(k, shape, jnp.float32) * 0.02

        keys = iter(jax.random.split(key, 2 + 12 * c.n_layers))
        params = {"embed": {"tok": dense(next(keys), (c.vocab_size, d))}}
        for i in range(c.n_layers):
            dt = jnp.exp(jax.random.uniform(
                next(keys), (h,), jnp.float32, jnp.log(0.001),
                jnp.log(0.1)))
            params[f"layer_{i}"] = {
                "norm1": jnp.ones((d,)), "norm2": jnp.ones((d,)),
                "wq": dense(next(keys), (d, c.n_heads * c.head_dim)),
                "wk": dense(next(keys), (d, c.n_kv_heads * c.head_dim)),
                "wv": dense(next(keys), (d, c.n_kv_heads * c.head_dim)),
                "wo": dense(next(keys), (c.n_heads * c.head_dim, d)),
                "in_proj": dense(next(keys), (d, c.in_proj_dim)),
                "conv_w": jax.random.uniform(
                    next(keys), (c.mamba_d_conv, c.conv_dim),
                    jnp.float32, -0.5, 0.5),
                "conv_b": jnp.zeros((c.conv_dim,)),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "A_log": jnp.log(jax.random.uniform(
                    next(keys), (h,), jnp.float32, 1.0, 16.0)),
                "D": jnp.ones((h,)),
                "ssm_norm": jnp.ones((c.mamba_d_ssm,)),
                "out_proj": dense(next(keys), (c.mamba_d_ssm, d)),
                "gate": dense(next(keys), (d, f)),
                "up": dense(next(keys), (d, f)),
                "down": dense(next(keys), (f, d)),
            }
        params["head"] = {"norm": jnp.ones((d,)),
                          "w": dense(next(keys), (d, c.vocab_size))}
        self.params = params
        return params

    # -- shared pieces --------------------------------------------------
    def _qkv(self, p, h, positions):
        """q ``[..., t, n_heads, dh]``, k/v ``[..., t, n_kv_heads, dh]``
        of the normed input ``h [..., t, d]``, rotated."""
        c = self.conf
        h = h * c.attention_in_multiplier

        def heads(w, n):
            return jnp.reshape(_mm(h, w), h.shape[:-1] + (n, c.head_dim))
        q = heads(p["wq"], c.n_heads)
        k = heads(p["wk"], c.n_kv_heads) * c.key_multiplier
        v = heads(p["wv"], c.n_kv_heads)
        return (_rope(q, positions, c.rope_theta),
                _rope(k, positions, c.rope_theta), v)

    def _ssm_inputs(self, p, h):
        """The mixer's input projection of ``h [..., d]``, scaled
        segment by segment: gate ``z``, the convolution's input
        ``xBC`` and the raw ``dt``."""
        c = self.conf
        gn = c.mamba_n_groups * c.mamba_d_state
        m = c.ssm_multipliers
        mup = np.concatenate([np.full((w,), m[i], np.float32)
                              for i, w in enumerate(
                                  (c.mamba_d_ssm, c.mamba_d_ssm, gn, gn,
                                   c.mamba_n_heads))])
        u = _mm(h * c.ssm_in_multiplier, p["in_proj"]) * mup
        return (u[..., :c.mamba_d_ssm],
                u[..., c.mamba_d_ssm:c.mamba_d_ssm + c.conv_dim],
                u[..., c.mamba_d_ssm + c.conv_dim:])

    def _ssm_split(self, xbc):
        """After the convolution and its silu: ``x [..., heads, p]``,
        ``B``/``C`` ``[..., groups, n]``."""
        c = self.conf
        gn = c.mamba_n_groups * c.mamba_d_state
        lead = xbc.shape[:-1]
        x = xbc[..., :c.mamba_d_ssm].reshape(
            lead + (c.mamba_n_heads, c.mamba_d_head))
        b = xbc[..., c.mamba_d_ssm:c.mamba_d_ssm + gn].reshape(
            lead + (c.mamba_n_groups, c.mamba_d_state))
        cc = xbc[..., c.mamba_d_ssm + gn:].reshape(
            lead + (c.mamba_n_groups, c.mamba_d_state))
        return x, b, cc

    def _ssm_out(self, p, y, x, z):
        """``D`` skip, gate (``mamba_norm_before_gate`` false: gate
        first), RMSNorm over each group, output projection."""
        c = self.conf
        lead = y.shape[:-2]
        y = y + p["D"][:, None] * x
        y = y.reshape(lead + (c.mamba_d_ssm,)) * jax.nn.silu(z)
        g = c.mamba_n_groups
        y = _rms(y.reshape(lead + (g, c.mamba_d_ssm // g)),
                 p["ssm_norm"].reshape(g, -1), c.rms_norm_eps)
        return _mm(y.reshape(lead + (c.mamba_d_ssm,)),
                   p["out_proj"]) * c.ssm_out_multiplier

    def _mlp(self, p, x):
        c = self.conf
        h = _rms(x, p["norm2"], c.rms_norm_eps)
        return x + swiglu(h, p["gate"], p["up"], p["down"],
                          c.mlp_multipliers[0]) * c.mlp_multipliers[1]

    def _logits(self, params, x):
        c = self.conf
        hp = params["head"]
        return _mm(_rms(x, hp["norm"], c.rms_norm_eps),
                   hp["w"]) * c.lm_head_multiplier

    # -- full causal forward (prefill / reference decode) ---------------
    def _trunk(self, params, tokens, length=None):
        """The residual stream ``[b, t, d]`` after every block, with
        each layer's K/V ``[n_layers, b, t, n_kv_heads, dh]``, final
        recurrent state ``[n_layers, b, heads, p, n]`` and convolution
        tail ``[n_layers, b, d_conv - 1, conv_dim]`` at position
        ``length - 1`` (the last position without ``length``)."""
        from deeplearning4j_tpu.ops.attention import \
            dot_product_attention
        from deeplearning4j_tpu.ops.ssm_pallas import ssd_chunked_scan
        c = self.conf
        b, t = tokens.shape
        kw = c.mamba_d_conv
        pos = jnp.arange(t, dtype=jnp.int32)
        length = (jnp.full((b,), t, jnp.int32) if length is None
                  else jnp.asarray(length, jnp.int32))
        valid = pos[None, :] < length[:, None]               # [b, t]
        mask = (jnp.tril(jnp.ones((t, t), jnp.float32))[None, None]
                * valid.astype(jnp.float32)[:, None, None, :])
        rep = c.n_heads // c.n_kv_heads
        x = (params["embed"]["tok"][tokens].astype(jnp.float32)
             * c.embedding_multiplier)
        ks, vs, states, tails = [], [], [], []
        for i in range(c.n_layers):
            p = params[f"layer_{i}"]
            h = _rms(x, p["norm1"], c.rms_norm_eps)
            # attention: query head i reads KV head i // rep
            q, k, v = self._qkv(p, h, pos[None, :])
            a = dot_product_attention(
                jnp.swapaxes(q, 1, 2),
                jnp.repeat(jnp.swapaxes(k, 1, 2), rep, axis=1),
                jnp.repeat(jnp.swapaxes(v, 1, 2), rep, axis=1),
                mask=mask)
            a = _mm(jnp.reshape(jnp.swapaxes(a, 1, 2), (b, t, -1)),
                    p["wo"]) * c.attention_out_multiplier
            # the state-space mixer on the same normed input
            z, xbc, dt = self._ssm_inputs(p, h)
            padded = jnp.pad(xbc, ((0, 0), (kw - 1, 0), (0, 0)))
            conv = sum(padded[:, j:j + t] * p["conv_w"][j]
                       for j in range(kw)) + p["conv_b"]
            xs, bs, cs = self._ssm_split(jax.nn.silu(conv))
            dt = jnp.where(valid[..., None],
                           jax.nn.softplus(dt + p["dt_bias"]), 0.0)
            y, state = ssd_chunked_scan(
                xs, dt, -jnp.exp(p["A_log"]), bs, cs,
                chunk=c.mamba_chunk_size)
            x = x + self._ssm_out(p, y, xs, z) + a
            x = self._mlp(p, x)
            ks.append(k)
            vs.append(v)
            states.append(state)
            # the convolution's last kw - 1 valid inputs: rows
            # [length, length + kw - 1) of the left-padded input
            tails.append(jax.vmap(
                lambda a_, n: jax.lax.dynamic_slice_in_dim(a_, n, kw - 1)
            )(padded, length))
        return (x, jnp.stack(ks), jnp.stack(vs), jnp.stack(states),
                jnp.stack(tails))

    def forward(self, params, tokens, length=None):
        """Logits ``[b, t, vocab]`` at every position: the tests' and
        the conformance gate's full forward (small sizes only: at a
        published vocabulary that is gigabytes of logits)."""
        x = self._trunk(params, jnp.asarray(tokens), length)[0]
        return self._logits(params, x)

    def output(self, tokens):
        """Full-sequence logits (the generic serving surface)."""
        if self.params is None:
            self.init()
        return self.forward(self.params, tokens)

    def prefill(self, params, tokens, length):
        """Prompt pass: ``(last_logits [b, vocab], k, v, ssm, conv)``.
        The head runs on position ``length - 1`` only."""
        x, k, v, ssm, conv = self._trunk(params, tokens, length)
        return (self._logits(params, last_position(x, length)),
                k, v, ssm, conv)

    # -- one fused decode step over the cache ---------------------------
    def decode_step(self, params, tokens, positions, k_pool, v_pool,
                    ssm, conv, block_tables, state_slots, *,
                    paged: bool = False):
        """One token for every row of the decode batch.

        As :meth:`DecoderLM.decode_step`, with ``k_pool``/``v_pool``
        ``[n_layers, num_blocks, block, n_kv_heads * head_dim]`` and, for
        the recurrent state, ``ssm [n_layers, slots, heads, p, n]``,
        ``conv [n_layers, slots, d_conv - 1, conv_dim]`` and
        ``state_slots [b]`` int32 (0, the scratch slot, for a dead
        row). Returns ``(logits [b, vocab], k_pool, v_pool, ssm,
        conv)``."""
        from deeplearning4j_tpu.ops.attention_pallas import (
            paged_attention_reference, paged_decode_attention)
        from deeplearning4j_tpu.ops.ssm_pallas import ssm_state_update
        c = self.conf
        b = tokens.shape[0]
        bs = k_pool.shape[2]
        attend = (paged_decode_attention if paged
                  else paged_attention_reference)
        x = (params["embed"]["tok"][tokens].astype(jnp.float32)
             * c.embedding_multiplier)                       # [b, d]
        blk, off = pool_rows(block_tables, positions, bs)    # [b] each
        lengths = positions + 1
        for i in range(c.n_layers):
            p = params[f"layer_{i}"]
            h = _rms(x, p["norm1"], c.rms_norm_eps)
            q, k_new, v_new = self._qkv(p, h, positions)
            k_pool = write_rows(k_pool, i, blk, off, k_new)
            v_pool = write_rows(v_pool, i, blk, off, v_new)
            a = attend(q, k_pool, v_pool, block_tables, lengths, i)
            a = _mm(jnp.reshape(a, (b, -1)),
                    p["wo"]) * c.attention_out_multiplier
            # the mixer: advance the row's convolution tail, then its
            # recurrent state, both in the row's slot
            z, xbc, dt = self._ssm_inputs(p, h)
            window = jnp.concatenate(
                [conv[i, state_slots], xbc[:, None, :]], axis=1)
            conv = conv.at[i, state_slots].set(window[:, 1:])
            xs, bs_, cs = self._ssm_split(jax.nn.silu(
                jnp.sum(window * p["conv_w"], axis=1) + p["conv_b"]))
            dt = jax.nn.softplus(dt + p["dt_bias"])
            ssm, y = ssm_state_update(
                ssm, i, state_slots, xs, dt,
                jnp.exp(dt * -jnp.exp(p["A_log"])), bs_, cs)
            x = x + self._ssm_out(p, y, xs, z) + a
            x = self._mlp(p, x)
        return self._logits(params, x), k_pool, v_pool, ssm, conv

    # -- reference decode (conformance gate) ----------------------------
    def reference_decode(self, params, prompt, max_tokens: int,
                         eos_id: Optional[int] = None):
        """Greedy decode by full re-forward each step (no cache, no
        state carried): what cached decode must match token for
        token."""
        eos = self.conf.eos_id if eos_id is None else eos_id
        ids = list(np.asarray(prompt, np.int32))
        out = []
        for _ in range(max_tokens):
            logits = self.forward(params, jnp.asarray([ids], jnp.int32))
            nxt = int(jnp.argmax(logits[0, -1]))
            out.append(nxt)
            ids.append(nxt)
            if nxt == eos:
                break
        return out
