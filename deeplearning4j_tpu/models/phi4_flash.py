"""Phi-4-mini-flash decoder (SambaY, arXiv:2507.06607, with differential
attention, arXiv:2410.05258) behind the paged-decode serving contract.

A decoder-decoder: the lower half (the *self-decoder*) alternates
Mamba-1 mixers and differential attention over a sliding window; one
full-attention layer follows, and its K/V is the **only** cache of the
upper half (the *cross-decoder*), which alternates Gated Memory Units —
``W_out (m * silu(W_in h))`` with ``m`` the read-out of the last Mamba
layer before its gate — and differential cross-attention that projects
queries only. No positional encoding anywhere. Every layer is
``x += Mixer(LN1(x)); x += SwiGLU(LN2(x))``; the head is tied to the
embedding.

With ``n`` layers and ``h = n // 2`` (``n`` a multiple of 4)::

    l even, l <= h      mamba   (layer h also hands on its read-out m)
    l odd,  l <  h      window  (the last ``window`` positions)
    l = h + 1           full    (writes the one growing K/V layer)
    l even, l >  h      gmu
    l odd,  l >  h + 1  cross   (reads layer h + 1's K/V)

A live sequence therefore keeps **three kinds of state**
(:meth:`Phi4FlashLM.state_shapes`, ``kv_layers``):

- blocks of **one** K/V layer that grow with the context (the pool's
  K/V arrays, ``kv_layers = 1``, read by the full layer and every cross
  layer);
- a **ring of ``window`` positions** in each window layer, a
  fixed-shape slot kind: position ``p`` lives at ``p mod window`` (with
  no positional encoding the order inside the ring does not matter),
  so a window layer's bytes never depend on the context;
- the Mamba-1 state ``[d_state, d_inner]`` float32 and the
  convolution's tail of ``d_conv - 1`` inputs in each Mamba layer.

The paged kernel reads all attention: the ring of slot ``s`` is blocks
``[s * window / block, ...)`` of the ring array seen as a pool
(``[layers, slots * window / block, block, lanes]``, a merge of leading
axes), its table is fixed and its length ``min(context, window)``.
Differential heads reach the kernel as grouped queries with
``v_group = 2``: K heads of ``head_dim``, the pair's V of ``2 *
head_dim`` (``ops.attention_pallas``); the subtraction, ``lambda`` and
the pair's RMS norm are an epilogue on ``[rows, heads, 2 * head_dim]``.

:meth:`Phi4FlashLM.prefill` runs as the model is built (YOCO): layers
``0 .. h`` and layer ``h + 1``'s K/V projection over the prompt, layers
``h + 1 .. n - 1`` on position ``length - 1`` only. :meth:`forward` runs
every layer at every position and is the tests' form.

Weights are whatever type ``params`` holds (bfloat16 in the
benchmark); the residual stream, activations and recurrent state are
float32, products at the backend's default precision.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.models.served import (
    greedy_by_reforward, last_position, mm as _mm, pool_rows, swiglu,
    write_rows)

_NEG = -1e30


@dataclass
class Phi4FlashConfig:
    """Hyperparameters. The defaults are a test size that keeps the
    published ratios: query width = ``d_model``, 2 query heads a KV
    head, ``d_ff = 4 d_model``, ``d_inner = 2 d_model``, ``dt_rank =
    d_model / 16``, a state of 16 and a convolution of 4."""

    vocab_size: int = 96
    n_layers: int = 8
    d_model: int = 64
    n_heads: int = 8                    # query heads (pairs: half)
    n_kv_heads: int = 4
    head_dim: int = 8
    d_ff: int = 256
    window: int = 8
    layer_norm_eps: float = 1e-5
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: Optional[int] = None     # None: ceil(d_model / 16)
    max_len: int = 512
    eos_id: int = 1
    seed: int = 0

    def __post_init__(self):
        if self.n_layers % 4 or self.n_layers < 8:
            raise ValueError("n_layers must be a multiple of 4, >= 8")
        if self.n_heads % 2 or self.n_kv_heads % 2 \
                or self.n_heads % self.n_kv_heads:
            raise ValueError("query heads pair up over pairs of KV heads")
        if self.mamba_dt_rank is None:
            self.mamba_dt_rank = -(-self.d_model // 16)

    @staticmethod
    def from_published(cfg: dict, **kw) -> "Phi4FlashConfig":
        """From a ``config.json``-shaped dict; the Mamba sizes, which
        the published file leaves to the class's defaults, are read
        where ``cfg`` names them."""
        d = cfg["hidden_size"]
        mamba = {k: cfg[k] for k in ("mamba_d_state", "mamba_d_conv",
                                     "mamba_expand", "mamba_dt_rank")
                 if k in cfg}
        return Phi4FlashConfig(
            vocab_size=cfg["vocab_size"], n_layers=cfg["num_hidden_layers"],
            d_model=d, n_heads=cfg["num_attention_heads"],
            n_kv_heads=cfg["num_key_value_heads"],
            head_dim=d // cfg["num_attention_heads"],
            d_ff=cfg["intermediate_size"], window=cfg["sliding_window"],
            layer_norm_eps=cfg["layer_norm_eps"], **mamba, **kw)

    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.d_model

    @property
    def kinds(self) -> tuple:
        """The mixer of every layer, by the table in the module's
        head."""
        h = self.n_layers // 2
        return tuple(
            ("mamba" if l <= h else "gmu") if l % 2 == 0 else
            ("window" if l < h else "full" if l == h + 1 else "cross")
            for l in range(self.n_layers))

    def lambda_init(self, layer: int) -> float:
        return 0.8 - 0.6 * math.exp(-0.3 * layer)


def _ln(x, g, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


class Phi4FlashLM:
    """Mamba-1 + sliding-window self-decoder, one full-attention cache,
    a cross-decoder of Gated Memory Units, over token ids."""

    def __init__(self, conf: Optional[Phi4FlashConfig] = None, **kw):
        self.conf = conf if conf is not None else Phi4FlashConfig(**kw)
        self.params = None
        self._forward_jit = None        # reference_decode's, made once
        kinds = self.conf.kinds
        #: a layer's row in its kind's state arrays
        self._ordinal = [kinds[:l].count(k) for l, k in enumerate(kinds)]

    # -- what the cache manager holds for a sequence --------------------
    #: K/V layers whose blocks grow with the context: the full layer's
    kv_layers = 1

    def state_shapes(self) -> dict:
        """The slot kinds beside the one growing K/V layer, in the
        order ``prefill`` / ``decode_step`` pass the arrays: each
        window layer's K and V ring (``window`` positions, in the
        pool's K/V type), each Mamba layer's state and convolution
        tail."""
        c = self.conf
        kinds = c.kinds
        ring = {"shape": (c.window, c.n_kv_heads * c.head_dim),
                "dtype": None, "layers": kinds.count("window"),
                "window": c.window}
        mamba = kinds.count("mamba")
        return {"ring_k": ring, "ring_v": dict(ring),
                "ssm": {"shape": (c.mamba_d_state, c.d_inner),
                        "dtype": jnp.float32, "layers": mamba},
                "conv": {"shape": (c.mamba_d_conv - 1, c.d_inner),
                         "dtype": jnp.float32, "layers": mamba}}

    def cache_reads(self) -> dict:
        """Who reads what in one decode step: ``kv_readers`` layers read
        the growing K/V layer whole, ``window_layers`` read a ring of
        ``window`` positions."""
        kinds = self.conf.kinds
        return {"kv_readers": 1 + kinds.count("cross"),
                "window_layers": kinds.count("window"),
                "window": self.conf.window}

    def prefill_layer_positions(self, bucket: int) -> tuple:
        """``(computed, dense)`` layer-positions of a prefill over a
        bucket of ``bucket`` positions: the layers from the full one up
        run on one position."""
        n = self.conf.n_layers
        lower = n // 2 + 1
        return lower * bucket + (n - lower), n * bucket

    # -- init -----------------------------------------------------------
    def init(self, key=None) -> dict:
        """Seeded float32 weights in the serving layout ``{entry:
        {leaf: array}}`` (projections ``[in, out]``, ``A_log [d_state,
        d_inner]``)."""
        c = self.conf
        if key is None:
            key = jax.random.PRNGKey(c.seed)
        d, f, e, n, r = (c.d_model, c.d_ff, c.d_inner, c.mamba_d_state,
                         c.mamba_dt_rank)
        q, kv, dh = c.n_heads * c.head_dim, c.n_kv_heads * c.head_dim, \
            c.head_dim
        keys = iter(jax.random.split(key, 1 + 16 * c.n_layers))

        def dense(shape, std=0.02):
            return jax.random.normal(next(keys), shape, jnp.float32) * std

        def attention(cross):
            p = {"wq": dense((d, q)), "bq": jnp.zeros((q,)),
                 "wo": dense((q, d)), "bo": jnp.zeros((d,)),
                 "subln": jnp.ones((2 * dh,)),
                 **{k: dense((dh,), 0.1)
                    for k in ("lq1", "lk1", "lq2", "lk2")}}
            if not cross:
                p.update(wk=dense((d, kv)), bk=jnp.zeros((kv,)),
                         wv=dense((d, kv)), bv=jnp.zeros((kv,)))
            return p

        def mamba():
            dt = jnp.exp(jax.random.uniform(
                next(keys), (e,), jnp.float32, jnp.log(0.001),
                jnp.log(0.1)))
            return {"in_proj": dense((d, 2 * e)),
                    "conv_w": jax.random.uniform(
                        next(keys), (c.mamba_d_conv, e), jnp.float32,
                        -0.5, 0.5),
                    "conv_b": jnp.zeros((e,)),
                    "x_proj": dense((e, r + 2 * n)),
                    "dt_proj": dense((r, e), r ** -0.5),
                    "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                    "A_log": jnp.log(jnp.broadcast_to(
                        jnp.arange(1, n + 1, dtype=jnp.float32)[:, None],
                        (n, e))),
                    "D": jnp.ones((e,)), "out_proj": dense((e, d))}

        params = {"embed": {"tok": dense((c.vocab_size, d))}}
        for l, kind in enumerate(c.kinds):
            if kind == "mamba":
                p = mamba()
            elif kind == "gmu":
                p = {"in_proj": dense((d, e)), "out_proj": dense((e, d))}
            else:
                p = attention(kind == "cross")
            p.update({"ln1_g": jnp.ones((d,)), "ln1_b": jnp.zeros((d,)),
                      "ln2_g": jnp.ones((d,)), "ln2_b": jnp.zeros((d,)),
                      "gate": dense((d, f)), "up": dense((d, f)),
                      "down": dense((f, d))})
            params[f"layer_{l}"] = p
        params["head"] = {"ln_g": jnp.ones((d,)), "ln_b": jnp.zeros((d,))}
        self.params = params
        return params

    # -- shared pieces --------------------------------------------------
    def _heads(self, h, w, b, n):
        return jnp.reshape(_mm(h, w) + b,
                           h.shape[:-1] + (n, self.conf.head_dim))

    def _mlp(self, p, x):
        h = _ln(x, p["ln2_g"], p["ln2_b"], self.conf.layer_norm_eps)
        return x + swiglu(h, p["gate"], p["up"], p["down"])

    def _logits(self, params, x):
        """The last LayerNorm and the tied head."""
        hp, tok = params["head"], params["embed"]["tok"]
        h = _ln(x, hp["ln_g"], hp["ln_b"], self.conf.layer_norm_eps)
        return jax.lax.dot_general(
            h.astype(tok.dtype), tok, (((h.ndim - 1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)

    def _diff_out(self, p, layer, o):
        """The differential epilogue on ``o [..., kv_pairs, m, 2, 2 *
        dh]`` (axis -2: the pair's two softmax maps times the pair's
        V): subtract, RMS norm over the pair's output with a learned
        weight, ``1 - lambda_init``, the output projection."""
        c = self.conf
        lam0 = c.lambda_init(layer)
        f32 = jnp.float32
        lam = (jnp.exp(jnp.sum(p["lq1"].astype(f32) * p["lk1"].astype(f32)))
               - jnp.exp(jnp.sum(p["lq2"].astype(f32)
                                 * p["lk2"].astype(f32))) + lam0)
        o = o[..., 0, :] - lam * o[..., 1, :]
        o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), axis=-1,
                                       keepdims=True) + c.layer_norm_eps)
        o = o * p["subln"] * (1.0 - lam0)
        lead = o.shape[:-3]
        return _mm(o.reshape(lead + (-1,)), p["wo"]) + p["bo"]

    def _diff_attend(self, q, k, v, mask):
        """Both softmax maps of every pair over dense K/V: ``q [b, tq,
        H, dh]`` (heads ``(kv pair j, m, s)``: pair ``i = rep * j + m``
        is ``(q[2i], q[2i + 1])``), ``k``/``v`` ``[b, t, Hkv, dh]``
        (pair ``j``: ``k[2j]``, ``k[2j + 1]``, V the two side by side),
        ``mask [b, tq, t]``. Returns ``[b, tq, j, m, s, 2 * dh]``."""
        c = self.conf
        b, tq = q.shape[:2]
        t, dh, pairs = k.shape[1], c.head_dim, c.n_kv_heads // 2
        qg = q.reshape(b, tq, pairs, -1, 2, dh)
        kg = k.reshape(b, t, pairs, 2, dh)
        vg = v.reshape(b, t, pairs, 2 * dh)
        sc = jnp.einsum("bqjmsd,bkjsd->bjmsqk", qg, kg) / math.sqrt(dh)
        sc = jnp.where(mask[:, None, None, None], sc, _NEG)
        w = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("bjmsqk,bkje->bqjmse", w, vg)

    def _mamba_inputs(self, p, xc):
        """``dt [.., d_inner]`` (softplus'd), ``B``/``C`` ``[.., n]`` of
        the convolved input."""
        c = self.conf
        r, n = c.mamba_dt_rank, c.mamba_d_state
        dbc = _mm(xc, p["x_proj"])
        dt = jax.nn.softplus(_mm(dbc[..., :r], p["dt_proj"]) + p["dt_bias"])
        return dt, dbc[..., r:r + n], dbc[..., r + n:]

    # -- the self-decoder over a whole sequence -------------------------
    def _lower(self, params, tokens, length):
        """Layers ``0 .. n/2`` over ``tokens [b, t]``: the stream ``x
        [b, t, d]``, the memory ``m [b, t, d_inner]``, every window
        layer's K/V ``[b, t, Hkv, dh]``, every Mamba layer's state and
        tail at ``length - 1``, and the mask of valid positions."""
        from deeplearning4j_tpu.ops.ssm_pallas import selective_scan
        c = self.conf
        t = tokens.shape[1]
        kw, e = c.mamba_d_conv, c.d_inner
        pos = jnp.arange(t, dtype=jnp.int32)
        valid = pos[None, :] < length[:, None]                    # [b, t]
        near = (pos[:, None] >= pos[None, :]) \
            & (pos[:, None] - pos[None, :] < c.window)
        mask = near[None] & valid[:, None, :]
        x = params["embed"]["tok"][tokens].astype(jnp.float32)
        ks, vs, states, tails, m = [], [], [], [], None
        for l in range(c.n_layers // 2 + 1):
            p = params[f"layer_{l}"]
            h = _ln(x, p["ln1_g"], p["ln1_b"], c.layer_norm_eps)
            if c.kinds[l] == "mamba":
                with jax.named_scope("mixer.mamba"):
                    xz = _mm(h, p["in_proj"])
                    xt, z = xz[..., :e], xz[..., e:]
                    padded = jnp.pad(xt, ((0, 0), (kw - 1, 0), (0, 0)))
                    xc = jax.nn.silu(sum(
                        padded[:, j:j + t] * p["conv_w"][j]
                        for j in range(kw)) + p["conv_b"])
                    dt, bb, cc = self._mamba_inputs(p, xc)
                    dt = jnp.where(valid[..., None], dt, 0.0)
                    y, state = selective_scan(
                        xc, dt, -jnp.exp(p["A_log"].astype(jnp.float32)),
                        bb, cc)
                    m = y + p["D"] * xc
                    x = x + _mm(m * jax.nn.silu(z), p["out_proj"])
                states.append(state)
                # the convolution's last kw - 1 valid inputs: rows
                # [length, length + kw - 1) of the left-padded input
                tails.append(jax.vmap(
                    lambda a_, n: jax.lax.dynamic_slice_in_dim(
                        a_, n, kw - 1))(padded, length))
            else:
                with jax.named_scope("mixer.window"):
                    q = self._heads(h, p["wq"], p["bq"], c.n_heads)
                    k = self._heads(h, p["wk"], p["bk"], c.n_kv_heads)
                    v = self._heads(h, p["wv"], p["bv"], c.n_kv_heads)
                    x = x + self._diff_out(
                        p, l, self._diff_attend(q, k, v, mask))
                ks.append(k)
                vs.append(v)
            x = self._mlp(p, x)
        return x, m, ks, vs, states, tails, valid

    def _upper(self, params, xq, mq, x_all, mask):
        """Layers ``n/2 + 1 .. n - 1`` at the query positions ``xq [b,
        tq, d]`` (their memory ``mq``), over the full layer's K/V of
        ``x_all [b, t, d]`` under ``mask [b, tq, t]``. Returns the
        stream at the query positions and the full layer's K/V."""
        c = self.conf
        full = c.n_layers // 2 + 1
        pf = params[f"layer_{full}"]
        h_all = _ln(x_all, pf["ln1_g"], pf["ln1_b"], c.layer_norm_eps)
        k = self._heads(h_all, pf["wk"], pf["bk"], c.n_kv_heads)
        v = self._heads(h_all, pf["wv"], pf["bv"], c.n_kv_heads)
        for l in range(full, c.n_layers):
            p = params[f"layer_{l}"]
            h = _ln(xq, p["ln1_g"], p["ln1_b"], c.layer_norm_eps)
            with jax.named_scope(f"mixer.{c.kinds[l]}"):
                if c.kinds[l] == "gmu":
                    xq = xq + _mm(mq * jax.nn.silu(_mm(h, p["in_proj"])),
                                  p["out_proj"])
                else:
                    q = self._heads(h, p["wq"], p["bq"], c.n_heads)
                    xq = xq + self._diff_out(
                        p, l, self._diff_attend(q, k, v, mask))
            xq = self._mlp(p, xq)
        return xq, k, v

    def _length(self, tokens, length):
        b, t = tokens.shape
        return (jnp.full((b,), t, jnp.int32) if length is None
                else jnp.asarray(length, jnp.int32))

    def forward(self, params, tokens, length=None):
        """Logits ``[b, t, vocab]``: every layer at every position (the
        tests' full forward; small sizes only)."""
        tokens = jnp.asarray(tokens)
        length = self._length(tokens, length)
        x, m, *_, valid = self._lower(params, tokens, length)
        t = tokens.shape[1]
        mask = jnp.tril(jnp.ones((t, t), bool))[None] & valid[:, None, :]
        return self._logits(params, self._upper(params, x, m, x, mask)[0])

    def output(self, tokens):
        """Full-sequence logits (the generic serving surface)."""
        if self.params is None:
            self.init()
        return self.forward(self.params, tokens)

    def prefill(self, params, tokens, length):
        """Prompt pass as the model is built: ``(last_logits [b, vocab],
        k, v [1, b, t, Hkv, dh], ring_k, ring_v [window layers, b,
        window, lanes], ssm [mamba layers, b, n, d_inner], conv [mamba
        layers, b, d_conv - 1, d_inner])``. Layers from the full one up
        run on position ``length - 1`` only; a ring holds position ``p``
        of the last ``min(length, window)`` at ``p mod window``."""
        c = self.conf
        tokens = jnp.asarray(tokens)
        length = self._length(tokens, length)
        x, m, ks, vs, states, tails, valid = self._lower(
            params, tokens, length)
        xq, k, v = self._upper(
            params, last_position(x, length)[:, None],
            last_position(m, length)[:, None], x, valid[:, None, :])
        # ring index r holds the newest position p < length, p = r mod W
        r = jnp.arange(c.window, dtype=jnp.int32)[None, :]
        last = length[:, None] - 1
        at = jnp.where(r <= last, r + c.window * ((last - r) // c.window), 0)

        def ring(a):                    # [b, t, Hkv, dh] -> [b, W, lanes]
            a = a.reshape(a.shape[0], a.shape[1], -1)
            return jnp.take_along_axis(a, at[:, :, None], axis=1)
        return (self._logits(params, xq[:, 0]), k[None], v[None],
                jnp.stack([ring(a) for a in ks]),
                jnp.stack([ring(a) for a in vs]),
                jnp.stack(states), jnp.stack(tails))

    # -- one fused decode step over the cache ---------------------------
    def decode_step(self, params, tokens, positions, k_pool, v_pool,
                    ring_k, ring_v, ssm, conv, block_tables, state_slots,
                    *, paged: bool = False):
        """One token for every row of the decode batch.

        ``k_pool``/``v_pool`` ``[1, num_blocks, block, Hkv * dh]`` (the
        full layer's), ``ring_k``/``ring_v`` ``[window layers, slots,
        window / block, block, Hkv * dh]``, ``ssm [mamba layers, slots,
        n, d_inner]``, ``conv [mamba layers, slots, d_conv - 1,
        d_inner]``, ``block_tables [b, max_blocks]``, ``state_slots
        [b]`` (0, the scratch slot, for a dead row, whose table names
        block 0). Returns ``(logits [b, vocab], k_pool, v_pool, ring_k,
        ring_v, ssm, conv)``."""
        from deeplearning4j_tpu.ops.attention_pallas import (
            paged_attention_reference, paged_decode_attention)
        from deeplearning4j_tpu.ops.ssm_pallas import selective_state_update
        c = self.conf
        b = tokens.shape[0]
        bs, e = k_pool.shape[2], c.d_inner
        pairs, dh = c.n_kv_heads // 2, c.head_dim
        kernel = paged_decode_attention if paged \
            else paged_attention_reference

        def attend(q, kp, vp, tables, lengths, layer):
            # model order (j, m, s) -> the kernel's grouped order (j, s,
            # m): query head r scores against KV head r // rep
            q = jnp.swapaxes(q.reshape(b, pairs, -1, 2, dh), 2, 3)
            o = kernel(q.reshape(b, -1, dh), kp, vp, tables, lengths,
                       layer, v_group=2)
            return jnp.swapaxes(o.reshape(b, pairs, 2, -1, 2 * dh), 2, 3)

        blk, off = pool_rows(block_tables, positions, bs)
        lengths = positions + 1
        # the rings as a pool: slot s is blocks [s * per, (s + 1) * per)
        ring_shape = ring_k.shape
        per = ring_shape[2]
        ring_k = ring_k.reshape(ring_shape[0], -1, bs, ring_shape[-1])
        ring_v = ring_v.reshape(ring_k.shape)
        at = positions % c.window
        ring_blk, ring_off = state_slots * per + at // bs, at % bs
        ring_tables = (state_slots[:, None] * per
                       + jnp.arange(per, dtype=state_slots.dtype)[None, :])
        ring_len = jnp.minimum(lengths, c.window)

        x = params["embed"]["tok"][tokens].astype(jnp.float32)     # [b, d]
        m = None
        for l, kind in enumerate(c.kinds):
            p = params[f"layer_{l}"]
            i = self._ordinal[l]
            h = _ln(x, p["ln1_g"], p["ln1_b"], c.layer_norm_eps)
            with jax.named_scope(f"mixer.{kind}"):
                if kind == "mamba":
                    xz = _mm(h, p["in_proj"])
                    xt, z = xz[:, :e], xz[:, e:]
                    taps = jnp.concatenate(
                        [conv[i, state_slots], xt[:, None, :]], axis=1)
                    conv = conv.at[i, state_slots].set(taps[:, 1:])
                    xc = jax.nn.silu(jnp.sum(taps * p["conv_w"], axis=1)
                                     + p["conv_b"])
                    dt, bb, cc = self._mamba_inputs(p, xc)
                    ssm, y = selective_state_update(
                        ssm, i, state_slots, xc, dt,
                        -jnp.exp(p["A_log"].astype(jnp.float32)), bb, cc)
                    m = y + p["D"] * xc
                    x = x + _mm(m * jax.nn.silu(z), p["out_proj"])
                elif kind == "gmu":
                    x = x + _mm(m * jax.nn.silu(_mm(h, p["in_proj"])),
                                p["out_proj"])
                else:
                    q = self._heads(h, p["wq"], p["bq"], c.n_heads)
                    if kind != "cross":
                        k = self._heads(h, p["wk"], p["bk"], c.n_kv_heads)
                        v = self._heads(h, p["wv"], p["bv"], c.n_kv_heads)
                    if kind == "window":
                        ring_k = write_rows(ring_k, i, ring_blk, ring_off, k)
                        ring_v = write_rows(ring_v, i, ring_blk, ring_off, v)
                        o = attend(q, ring_k, ring_v, ring_tables,
                                   ring_len, i)
                    else:
                        if kind == "full":
                            k_pool = write_rows(k_pool, 0, blk, off, k)
                            v_pool = write_rows(v_pool, 0, blk, off, v)
                        o = attend(q, k_pool, v_pool, block_tables,
                                   lengths, 0)
                    x = x + self._diff_out(p, l, o)
            x = self._mlp(p, x)
        return (self._logits(params, x), k_pool, v_pool,
                ring_k.reshape(ring_shape), ring_v.reshape(ring_shape),
                ssm, conv)

    # -- reference decode (conformance gate) ----------------------------
    def reference_decode(self, params, prompt, max_tokens: int,
                         eos_id: Optional[int] = None):
        """Greedy decode by full re-forward each step: what cached
        decode must match token for token."""
        return greedy_by_reforward(self, params, prompt, max_tokens, eos_id)
