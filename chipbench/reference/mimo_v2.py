"""Plain reference for a MiMo-V2 decoder cut to one chip's share of an expert-parallel
deployment: weights from the seed and the full causal forward, every layer over every
position, in jax.numpy. Imports nothing of the program.

With ``l`` 0-based, every layer is ``x += W_o Attn_l(RMSNorm(x)); x += FFN_l(RMSNorm(x))``,
then the last RMSNorm and an untied head over the held slice of the vocabulary.

- ``[q | k | v] = h W_qkv`` (no bias): 64 query heads of ``head_dim``; ``hybrid_layer_pattern[l]``
  0 is a **full** layer (``num_key_value_heads`` KV heads, rotary base ``rope_theta``), 1 a
  **window** layer (``swa_num_key_value_heads`` KV heads, ``swa_rope_theta``, the last
  ``sliding_window`` positions). K heads are ``head_dim`` wide, V heads ``v_head_dim``.
  Rotary positions, rotate-half, on the first ``int(head_dim * partial_rotary_factor)``
  dimensions of every q and k head. ``v <- attention_value_scale * v``.
- Scores ``q_i . k_j / sqrt(head_dim)`` under the mask, softmax; in window layers a learned
  **sink**, one logit a query head, is a column appended to the scores and dropped after the
  softmax (it takes probability and adds no value).
- ``moe_layer_freq[l]`` 0: ``W_down(silu(W_gate h) * W_up h)``; 1: ``s = sigmoid(h W_r)`` over
  all ``router_experts`` experts in float32, the ``num_experts_per_tok`` largest of ``s + b``
  chosen, gates ``s_e / (sum of the chosen s + 1e-20)``, and ``x += sum over the chosen experts
  held here of g_e E_e(h)``: experts ``[experts_first, experts_first + n_routed_experts)``. What
  the other experts would add is another chip's and is left out, here as in the program.

Departures from the published description, each for the chip's 16 GB or for the minute a
run may take after its window:
- the weights are the bfloat16 values the checkpoint's type holds (the program gets the
  same arrays); they are widened to float32 a layer at a time, where they are used;
- attention runs a block of queries at a time (every key, the whole mask: no band, no ring);
- the experts are a loop over the held ones, each over the rows routed to it, gathered
  ``EXPERT_ROWS`` at a time for as many trips as they take: never a dropped row (a dense
  product of every held expert over every position would be 59 TFLOP a request);
- the head is taken in blocks of the vocabulary and at every position.
"""
import functools
import json
import math

import jax
import jax.numpy as jnp

#: blocks the vocabulary is cut into wherever all of it is touched at once
VOCAB_BLOCKS = 8
#: query positions one block of the attention takes
Q_BLOCK = 704
#: the mean of the sink's draw. A sink drawn normal(0, 1) beside 128 keys whose scores spread by
#: 1.6 takes 1 / 500 of the softmax's mass: with it left out a window layer's output moves by
#: 0.6 % past position 128 (rms 0.0028 of 0.50, counted on the CPU at the published widths;
#: PERF.md section 4), less than rounding. The sum of ``exp(score)`` over a full window is near
#: ``exp(6.2)``, so a sink near 5 takes a quarter of the mass, as a trained sink takes a share
#: worth learning
SINK_MEAN = 5.0
#: rows of one held expert that one trip of its loop takes (2816 positions send it 88 on average)
EXPERT_ROWS = 256


def key_of(seed):
    """A PRNG key from any whole number up to 2**63 (a seed may pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


def sizes(cfg):
    return {"d": cfg["hidden_size"], "hq": cfg["num_attention_heads"], "dk": cfg["head_dim"],
            "dv": cfg["v_head_dim"], "f": cfg["intermediate_size"], "fe": cfg["moe_intermediate_size"],
            "v": cfg["vocab_size"], "w": cfg["sliding_window"], "eps": cfg["layernorm_epsilon"],
            "experts": cfg.get("router_experts", cfg["n_routed_experts"]), "held": cfg["n_routed_experts"],
            "first": cfg.get("experts_first", 0), "top": cfg["num_experts_per_tok"],
            "rot": int(cfg["head_dim"] * cfg["partial_rotary_factor"])}


def kind_of(cfg, l):
    return "window" if cfg["hybrid_layer_pattern"][l] else "full"


def kv_heads(cfg, l):
    return cfg["swa_num_key_value_heads" if kind_of(cfg, l) == "window" else "num_key_value_heads"]


def _normal(key, shape, std):
    """normal(0, std) rounded to bfloat16, the checkpoint's type."""
    return (jax.random.normal(key, shape, jnp.float32) * jnp.float32(std)).astype(jnp.bfloat16)


def generate_layer(cfg, l, key):
    """Layer ``l``'s weights (traceable): projections bfloat16 ``[in, out]`` normal(0, 0.02)
    (``init_std`` in a test's configuration widens them), the held experts stacked ``[held, in,
    out]``; the router normal(0, 0.02) and its selection bias normal(0, 0.02) in float32; the
    sink normal(``SINK_MEAN``, 1) in float32 (a sink or a bias of 0 would hide one left out);
    norms 1."""
    s = sizes(cfg)
    d, hkv = s["d"], kv_heads(cfg, l)
    std = cfg.get("init_std", 0.02)
    ks = iter(jax.random.split(key, 10))
    p = {"ln1": jnp.ones((d,), jnp.float32), "ln2": jnp.ones((d,), jnp.float32),
         "wqkv": _normal(next(ks), (d, s["hq"] * s["dk"] + hkv * (s["dk"] + s["dv"])), std),
         "wo": _normal(next(ks), (s["hq"] * s["dv"], d), std)}
    if kind_of(cfg, l) == "window":
        p["sink"] = cfg.get("sink_mean", SINK_MEAN) + jax.random.normal(next(ks), (s["hq"],), jnp.float32)
    if cfg["moe_layer_freq"][l]:
        e, f = s["held"], s["fe"]
        p.update(router=jax.random.normal(next(ks), (d, s["experts"]), jnp.float32) * std,
                 bias=jax.random.normal(next(ks), (s["experts"],), jnp.float32) * cfg.get("bias_std", 0.02),
                 e_gate=_normal(next(ks), (e, d, f), std), e_up=_normal(next(ks), (e, d, f), std),
                 e_down=_normal(next(ks), (e, f, d), std))
    else:
        f = s["f"]
        p.update(gate=_normal(next(ks), (d, f), std), up=_normal(next(ks), (d, f), std),
                 down=_normal(next(ks), (f, d), std))
    return p


def generate_ends(cfg, key):
    """The embedding ``[vocab, d]``, the untied head ``[d, vocab]`` and the last norm."""
    s = sizes(cfg)
    std = cfg.get("init_std", 0.02)
    ke, kh = jax.random.split(key)
    return {"embed": _normal(ke, (s["v"], s["d"]), std), "head": _normal(kh, (s["d"], s["v"]), std),
            "final": jnp.ones((s["d"],), jnp.float32)}


def generate(cfg, key):
    """All weights: ``{"embed", "head", "final", "layers": [one dict a layer]}``, each layer by a
    call of its own so that no draw's float32 temporaries outlive it; layers of one shape
    share one compiled program."""
    n = cfg["num_hidden_layers"]
    out = jax.jit(functools.partial(generate_ends, cfg))(jax.random.fold_in(key, n))
    made, layers = {}, []
    for l in range(n):
        shape = (kind_of(cfg, l), cfg["moe_layer_freq"][l])
        if shape not in made:           # the draws do not depend on l beyond the key
            made[shape] = jax.jit(functools.partial(generate_layer, cfg, l))
        layers.append(made[shape](jax.random.fold_in(key, l)))
    out["layers"] = layers
    return out


def make_params(cfg, seed):
    return generate(cfg, key_of(seed))


# ------------------------------------------------------------------- the equations
def _rms(x, g, eps):
    x32 = x.astype(jnp.float32)
    return (x32 * jax.lax.rsqrt(jnp.mean(jnp.square(x32), -1, keepdims=True) + eps) * g).astype(x.dtype)


def _silu(x):
    return x * jax.nn.sigmoid(x)


def _rotate(x, rot, theta):
    """Rotary positions 0..t-1, rotate-half, on the first ``rot`` dimensions of ``x [t, heads, dk]``."""
    t = x.shape[0]
    inv = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(ang).astype(x.dtype), jnp.sin(ang).astype(x.dtype)
    x1, x2 = x[..., :rot // 2], x[..., rot // 2:rot]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., rot:]], -1)


def attention(cfg, l, p, h):
    """Layer ``l``'s attention over ``h [t, d]``, before ``W_o``: ``[t, hq * dv]``."""
    s = sizes(cfg)
    t, hq, dk, dv, hkv = h.shape[0], s["hq"], s["dk"], s["dv"], kv_heads(cfg, l)
    window = kind_of(cfg, l) == "window"
    theta = cfg["swa_rope_theta"] if window else cfg["rope_theta"]
    qkv = h @ p["wqkv"]
    q = _rotate(qkv[:, :hq * dk].reshape(t, hq, dk), s["rot"], theta)
    k = _rotate(qkv[:, hq * dk:(hq + hkv) * dk].reshape(t, hkv, dk), s["rot"], theta)
    v = qkv[:, (hq + hkv) * dk:].reshape(t, hkv, dv) * jnp.asarray(cfg["attention_value_scale"], h.dtype)
    k, v = jnp.repeat(k, hq // hkv, 1), jnp.repeat(v, hq // hkv, 1)     # [t, hq, .]
    j = jnp.arange(t)[None, :]
    out = []
    for lo in range(0, t, Q_BLOCK):
        i = jnp.arange(lo, min(t, lo + Q_BLOCK))[:, None]
        mask = (j <= i) & (i - j < s["w"]) if window else (j <= i)
        sc = jnp.einsum("qhd,khd->hqk", q[lo:lo + Q_BLOCK], k) / jnp.asarray(math.sqrt(dk), h.dtype)
        sc = jnp.where(mask[None], sc, jnp.asarray(-1e30, sc.dtype)).astype(jnp.float32)
        if window:      # the sink: one more column, dropped after the softmax
            col = jnp.broadcast_to(p["sink"][:, None, None], sc.shape[:2] + (1,))
            a = jax.nn.softmax(jnp.concatenate([sc, col], -1), -1)[..., :-1]
        else:
            a = jax.nn.softmax(sc, -1)
        out.append(jnp.einsum("hqk,khe->qhe", a.astype(h.dtype), v))
    return jnp.concatenate(out).reshape(t, hq * dv)


def route(cfg, p, h):
    """``(chosen experts [t, top], gates [t, top])``: sigmoid scores over every expert in float32,
    the ``top`` largest of ``score + bias``, the chosen scores over their sum."""
    top = sizes(cfg)["top"]
    with jax.default_matmul_precision("highest"):
        s = jax.nn.sigmoid(h.astype(jnp.float32) @ p["router"])
    _, idx = jax.lax.top_k(s + p["bias"], top)
    chosen = jnp.take_along_axis(s, idx, -1)
    return idx, chosen / (jnp.sum(chosen, -1, keepdims=True) + 1e-20)


def experts(cfg, p, h):
    """The held experts' part of the layer over ``h [t, d]``: a loop over the held experts, each
    over the rows routed to it, ``EXPERT_ROWS`` at a time for as many trips as its rows take
    (a request's padding is one token many times over and may all fall on one expert)."""
    s = sizes(cfg)
    t = h.shape[0]
    chunk = min(t, cfg.get("expert_rows", EXPERT_ROWS))
    idx, gates = route(cfg, p, h)
    padded = jnp.concatenate([h, jnp.zeros((1, h.shape[1]), h.dtype)])
    out = jnp.zeros((t + 1, h.shape[1]), jnp.float32)
    for e in range(s["held"]):
        mine = idx == s["first"] + e                             # [t, top]
        g = jnp.concatenate([jnp.sum(jnp.where(mine, gates, 0.0), -1), jnp.zeros((1,))])
        routed = jnp.any(mine, -1)
        n = jnp.sum(routed)
        # the routed rows first, in order, then row t (zeros in, nothing out)
        order = jnp.concatenate([jnp.argsort(~routed, stable=True), jnp.full((chunk,), t)])

        def trip(c, out, e=e, g=g, n=n, order=order):
            rows = jax.lax.dynamic_slice(order, (c * chunk,), (chunk,))
            rows = jnp.where(c * chunk + jnp.arange(chunk) < n, rows, t)
            x = padded[rows]
            y = (_silu(x @ p["e_gate"][e]) * (x @ p["e_up"][e])) @ p["e_down"][e]
            return out.at[rows].add(y.astype(jnp.float32) * g[rows][:, None])
        out = jax.lax.fori_loop(0, -(-n // chunk), trip, out)
    return out[:t].astype(h.dtype)


def hidden(cfg, params, tokens, act=jnp.float32):
    """One sequence through every layer, activations in ``act``: the last norm's output ``[t, d]``."""
    s = sizes(cfg)
    x = params["embed"][tokens].astype(act)
    for l, p in enumerate(params["layers"]):
        # this layer's weights in the activations' type (float32: widened here); the router,
        # its bias and the sink stay float32
        p = {k: (w.astype(act) if w.dtype == jnp.bfloat16 else w) for k, w in p.items()}
        x = x + attention(cfg, l, p, _rms(x, p["ln1"], s["eps"])) @ p["wo"]
        h = _rms(x, p["ln2"], s["eps"])
        if cfg["moe_layer_freq"][l]:
            x = x + experts(cfg, p, h)
        else:
            x = x + (_silu(h @ p["gate"]) * (h @ p["up"])) @ p["down"]
    return _rms(x, params["final"], s["eps"])


def forward(cfg, params, tokens, act=jnp.float32):
    """Logits ``[t, vocab]`` at every position, float32: for the tests' small sizes."""
    h = hidden(cfg, params, tokens, act)
    return (h @ params["head"].astype(act)).astype(jnp.float32)


def _best_and_at(params, h, judged, act):
    """Over the vocabulary in blocks: each position's largest logit, where it is, and the
    logit of the token ``judged`` there."""
    head = params["head"]
    d, v = head.shape
    nb = VOCAB_BLOCKS if v % VOCAB_BLOCKS == 0 else 1
    width = v // nb
    blocks = jnp.transpose(head.reshape(d, nb, width), (1, 0, 2))

    def block(carry, xs):
        best, where, at = carry
        w, lo = xs
        logits = (h @ w.astype(act)).astype(jnp.float32)
        top = jnp.max(logits, -1)
        where = jnp.where(top > best, lo + jnp.argmax(logits, -1), where)
        inside = (judged >= lo) & (judged < lo + width)
        mine = jnp.take_along_axis(logits, jnp.clip(judged - lo, 0, width - 1)[:, None], 1)[:, 0]
        return (jnp.maximum(best, top), where, jnp.where(inside, mine, at)), None

    t = h.shape[0]
    init = (jnp.full((t,), -jnp.inf, jnp.float32), jnp.zeros((t,), jnp.int32), jnp.zeros((t,), jnp.float32))
    los = jnp.arange(nb, dtype=jnp.int32) * width
    (best, where, at), _ = jax.lax.scan(block, init, (blocks, los))
    return best, where, at


def served_gaps(cfg, params, tokens, control=False):
    """For one request, ``tokens`` being its prompt and served ids (padded): ``[t - 1]`` gaps in
    the float32 reference's logits between its best token at position ``i`` and the one judged
    there. That is ``tokens[i + 1]``, the served one; with ``control`` it is the first choice of
    the same forward with activations in bfloat16, the precision below the float32 the
    configuration states (a program of its own)."""
    frozen, tokens = _Frozen(cfg), jnp.asarray(tokens)
    judged = _first_choice_bf16(frozen, params, tokens) if control else tokens[1:]
    return _gaps(frozen, params, tokens, judged)


class _Frozen:
    """A configuration as a static argument of ``jax.jit``: hashed by its contents."""

    def __init__(self, cfg):
        self.cfg, self._key = cfg, json.dumps(cfg, sort_keys=True)

    def __hash__(self):
        return hash(self._key)

    def __eq__(self, other):
        return self._key == other._key


@functools.partial(jax.jit, static_argnums=0)
def _first_choice_bf16(frozen, params, tokens):
    low = hidden(frozen.cfg, params, tokens, jnp.bfloat16)[:-1]
    return _best_and_at(params, low, tokens[1:], jnp.bfloat16)[1]


@functools.partial(jax.jit, static_argnums=0)
def _gaps(frozen, params, tokens, judged):
    with jax.default_matmul_precision("highest"):
        h = hidden(frozen.cfg, params, tokens, jnp.float32)[:-1]
        best, _, at = _best_and_at(params, h, judged, jnp.float32)
    return best - at
