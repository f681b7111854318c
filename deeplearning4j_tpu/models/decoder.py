"""Decoder-only transformer LM with a paged-decode serving contract.

The generative serving engine needs a model that exposes the
prefill/decode split explicitly:

- :meth:`DecoderLM.prefill` — full causal attention over the (padded)
  prompt through ``sdpa_core`` (:func:`ops.attention.
  dot_product_attention`, so the flash kernel engages exactly where
  the classifier path's heuristics say), returning the last valid
  position's logits plus every layer's K/V for the KV pool.
- :meth:`DecoderLM.decode_step` — one token per live sequence: project
  q/k/v for the new token, write its K/V rows into the paged pool at
  the block-table slot (in place: the engine donates the pool), then
  paged attention over the stacked pool as it is stored (Pallas kernel
  or dense-gather fallback via the ``paged_attention`` kernel-select
  family). Everything is shape-stable in (batch, table-width), so one
  compiled step serves the whole continuous batch forever.

Parameters are a **two-level dict** ``{entry: {leaf: array}}`` with
per-layer entries (``layer_0`` … ``layer_{n-1}``), the exact layout
``parallel.zero.params_to_fsdp`` / ``serving.residency`` shard — so a
generative model composes with ``mode="sharded"``/``"fsdp"`` residency
out of the box (the forward walks ``params[entry]``, which an
``FsdpParamView`` serves with a point-of-use all-gather).

Causality makes the two paths agree: token *t*'s activations depend
only on tokens ``<= t``, so a decode step over cached K/V computes the
same logits (up to float associativity) as a full forward's last
position — the property the conformance gate
(``scripts/check_generative.py``) asserts as greedy token equality.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


@dataclass
class DecoderConfig:
    """Hyperparameters; ``tiny()`` is the test/bench size."""

    vocab_size: int = 64
    n_layers: int = 2
    n_heads: int = 2
    d_model: int = 32
    d_ff: int = 64
    max_len: int = 256
    eos_id: int = 1
    seed: int = 0

    @staticmethod
    def tiny(**kw) -> "DecoderConfig":
        return DecoderConfig(**kw)

    @property
    def head_dim(self) -> int:
        assert self.d_model % self.n_heads == 0
        return self.d_model // self.n_heads


def _ln(x, g, b, eps=1e-5):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


class DecoderLM:
    """Pre-LN decoder-only transformer over token ids."""

    def __init__(self, conf: Optional[DecoderConfig] = None, **kw):
        self.conf = conf if conf is not None else DecoderConfig(**kw)
        self.params = None

    # -- init -----------------------------------------------------------
    def init(self, key=None) -> dict:
        c = self.conf
        if key is None:
            key = jax.random.PRNGKey(c.seed)
        d, f, v = c.d_model, c.d_ff, c.vocab_size

        def dense(k, shape, scale=0.02):
            return (jax.random.normal(k, shape, jnp.float32)
                    * jnp.float32(scale))

        keys = iter(jax.random.split(key, 4 + 6 * c.n_layers))
        params = {"embed": {"tok": dense(next(keys), (v, d)),
                            "pos": dense(next(keys), (c.max_len, d))}}
        for i in range(c.n_layers):
            params[f"layer_{i}"] = {
                "ln1_g": jnp.ones((d,)), "ln1_b": jnp.zeros((d,)),
                "wq": dense(next(keys), (d, d)),
                "wk": dense(next(keys), (d, d)),
                "wv": dense(next(keys), (d, d)),
                "wo": dense(next(keys), (d, d)),
                "ln2_g": jnp.ones((d,)), "ln2_b": jnp.zeros((d,)),
                "w1": dense(next(keys), (d, f)),
                "b1": jnp.zeros((f,)),
                "w2": dense(next(keys), (f, d)),
                "b2": jnp.zeros((d,)),
            }
        params["head"] = {"ln_g": jnp.ones((d,)),
                          "ln_b": jnp.zeros((d,)),
                          "w": dense(next(keys), (d, v))}
        self.params = params
        return params

    # -- shared blocks --------------------------------------------------
    def _attn_qkv(self, p, h, heads_first: bool):
        c = self.conf
        shp = h.shape[:-1] + (c.n_heads, c.head_dim)
        q = jnp.reshape(h @ p["wq"], shp)
        k = jnp.reshape(h @ p["wk"], shp)
        v = jnp.reshape(h @ p["wv"], shp)
        if heads_first:                  # [b, t, h, dh] -> [b, h, t, dh]
            q, k, v = (jnp.swapaxes(a, -3, -2) for a in (q, k, v))
        return q, k, v

    def _mlp(self, p, x):
        h = _ln(x, p["ln2_g"], p["ln2_b"])
        return x + jax.nn.gelu(h @ p["w1"] + p["b1"]) @ p["w2"] + p["b2"]

    # -- full causal forward (prefill / reference decode) ---------------
    def forward_with_kv(self, params, tokens, length=None):
        """Logits ``[b, t, vocab]`` plus stacked per-layer K/V
        ``[n_layers, b, t, heads, head_dim]`` (token-major — the KV
        pool's block layout). ``length`` ``[b]`` masks right-padding;
        padded positions still produce K/V (callers route them to the
        scratch block)."""
        from deeplearning4j_tpu.ops.attention import \
            dot_product_attention
        c = self.conf
        b, t = tokens.shape
        pos = jnp.arange(t, dtype=jnp.int32)
        x = params["embed"]["tok"][tokens] + params["embed"]["pos"][pos]
        causal = jnp.tril(jnp.ones((t, t), jnp.float32))
        mask = causal[None, None]                   # [1, 1, t, t]
        if length is not None:
            valid = (pos[None, :]
                     < jnp.asarray(length)[:, None]).astype(jnp.float32)
            mask = mask * valid[:, None, None, :]
        ks, vs = [], []
        for i in range(c.n_layers):
            p = params[f"layer_{i}"]
            h = _ln(x, p["ln1_g"], p["ln1_b"])
            q, k, v = self._attn_qkv(p, h, heads_first=True)
            a = dot_product_attention(q, k, v, mask=mask)
            x = x + jnp.reshape(jnp.swapaxes(a, 1, 2),
                                (b, t, c.d_model)) @ p["wo"]
            x = self._mlp(p, x)
            ks.append(jnp.swapaxes(k, 1, 2))        # [b, t, h, dh]
            vs.append(jnp.swapaxes(v, 1, 2))
        hp = params["head"]
        logits = _ln(x, hp["ln_g"], hp["ln_b"]) @ hp["w"]
        return logits, jnp.stack(ks), jnp.stack(vs)

    def output(self, tokens):
        """Full-sequence logits (the generic serving surface; also the
        dense-attention reference the conformance gate decodes with).
        """
        if self.params is None:
            self.init()
        logits, _, _ = self.forward_with_kv(self.params,
                                            jnp.asarray(tokens))
        return logits

    def prefill(self, params, tokens, length):
        """Prompt pass: ``(last_logits [b, vocab], k, v)`` where
        ``last_logits`` is position ``length-1``'s row and k/v are the
        stacked caches from :meth:`forward_with_kv`."""
        logits, k, v = self.forward_with_kv(params, tokens, length)
        b = tokens.shape[0]
        last = logits[jnp.arange(b), jnp.asarray(length) - 1]
        return last, k, v

    # -- one fused decode step over the paged pool ----------------------
    def decode_step(self, params, tokens, positions, k_pool, v_pool,
                    block_tables, *, paged: bool = False):
        """One token for every sequence in the decode batch.

        ``tokens``/``positions`` ``[b]`` int32 (position = index of
        this token; the KV valid length becomes ``positions + 1``);
        ``k_pool``/``v_pool`` ``[n_layers, num_blocks, block, heads *
        head_dim]``, as ``KVBlockPool`` stores them; ``block_tables``
        ``[b, max_blocks]`` int32 padded with the scratch block 0 (dead
        batch slots pass position 0 and an all-zero table — their
        writes land in scratch). ``paged`` picks the Pallas kernel over
        the dense-gather fallback. Returns ``(logits [b, vocab],
        k_pool, v_pool)``: the pools with ``b`` rows a layer written
        and nothing else moved — no reshape, slice or copy of a pool or
        of a layer of it, so under donation the update is in place."""
        from deeplearning4j_tpu.ops.attention_pallas import (
            paged_attention_reference, paged_decode_attention)
        c = self.conf
        b = tokens.shape[0]
        bs = k_pool.shape[2]
        attend = (paged_decode_attention if paged
                  else paged_attention_reference)
        x = (params["embed"]["tok"][tokens]
             + params["embed"]["pos"][positions])        # [b, d]
        blk = block_tables[jnp.arange(b), positions // bs]   # [b]
        off = positions % bs
        lengths = positions + 1
        for i in range(c.n_layers):
            p = params[f"layer_{i}"]
            h = _ln(x, p["ln1_g"], p["ln1_b"])
            q, k_new, v_new = self._attn_qkv(p, h, heads_first=False)
            # low-precision pools (kv_dtype=bf16) take writes in the
            # pool's own dtype; attention math re-promotes via q
            k_pool = k_pool.at[i, blk, off].set(
                jnp.reshape(k_new, (b, -1)).astype(k_pool.dtype))
            v_pool = v_pool.at[i, blk, off].set(
                jnp.reshape(v_new, (b, -1)).astype(v_pool.dtype))
            a = attend(q, k_pool, v_pool, block_tables, lengths, i)
            x = x + jnp.reshape(a, (b, c.d_model)) @ p["wo"]
            x = self._mlp(p, x)
        hp = params["head"]
        logits = _ln(x, hp["ln_g"], hp["ln_b"]) @ hp["w"]
        return logits, k_pool, v_pool

    # -- reference decode (conformance gate) ----------------------------
    def reference_decode(self, params, prompt, max_tokens: int,
                         eos_id: Optional[int] = None):
        """Greedy decode by full re-forward each step — the
        dense-attention reference paged decode must match token for
        token. ``prompt`` is a 1-D id list; returns generated ids."""
        eos = self.conf.eos_id if eos_id is None else eos_id
        ids = list(np.asarray(prompt, np.int32))
        out = []
        for _ in range(max_tokens):
            tok = jnp.asarray([ids], jnp.int32)
            logits, _, _ = self.forward_with_kv(params, tok)
            nxt = int(jnp.argmax(logits[0, -1]))
            out.append(nxt)
            ids.append(nxt)
            if nxt == eos:
                break
        return out
