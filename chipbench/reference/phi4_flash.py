"""Plain reference for a Phi-4-mini-flash decoder (SambaY, arXiv:2507.06607, with
differential attention, arXiv:2410.05258): weights from the seed and the full causal
forward, every layer over every position, in jax.numpy. Imports nothing of the program.

With ``n`` layers (32) and ``l`` 0-based, every layer is ``x += Mixer_l(LN1(x)); x +=
W_down(silu(g) * u)``, ``[g | u] = W_gate_up LN2(x)``; then the last LayerNorm and the
head tied to the embedding. No positional encoding. The mixers:

- ``l`` even, ``l <= n/2``: Mamba-1. ``[xt | z] = W_in h``; ``x = silu(conv_4(xt) + b_c)``
  (depthwise, causal); ``[dr | B | C] = W_x x``; ``dt = softplus(W_dt dr + b_dt)``;
  ``A = -exp(A_log)`` ``[d_in, N]``; ``S_t[c, n] = exp(dt_t[c] A[c, n]) S_{t-1}[c, n] +
  dt_t[c] B_t[n] x_t[c]``; ``y_t[c] = sum_n C_t[n] S_t[c, n] + D[c] x_t[c]``; out ``W_out
  (y_t * silu(z_t))``. Layer ``n/2`` also hands on ``m_t = y_t`` as the memory of the
  layers above.
- ``l`` odd, ``l < n/2``: differential attention over the last ``sliding_window``
  positions; ``l = n/2 + 1``: the same, full causal.
- ``l`` even, ``l > n/2``: Gated Memory Unit, ``W_out (m_t * silu(W_in h_t))``.
- ``l`` odd, ``l > n/2 + 1``: differential cross-attention: ``q = W_q h`` only; K and V
  are those of layer ``n/2 + 1``.

Differential attention: query pair ``i`` of 20 is ``(q[2i], q[2i+1])``, KV pair ``j = i //
2`` of 10 is ``k1 = k[2j]``, ``k2 = k[2j+1]``, ``v = [v[2j] | v[2j+1]]``; ``A_s =
softmax(q_s k_s^T / sqrt(dh))`` under the mask; ``o_i = (A_1 - lam A_2) v``; ``lam =
exp(lq1 . lk1) - exp(lq2 . lk2) + lam0(l)``, ``lam0(l) = 0.8 - 0.6 exp(-0.3 l)``; ``o_i <-
(1 - lam0(l)) RMSNorm(o_i)`` with a learned weight; the 20 outputs side by side into
``W_o``.

The forms were written from the two papers and from memory of the public
``modeling_phi4flash.py`` (no network); where it may differ, the configuration file's
``assumed.equations`` says which form was taken. ``W_gate_up`` and ``Wqkv`` are held as
their column blocks (``gate``/``up``, ``wq``/``wk``/``wv``): the same product.

Departures from the published description, each for the chip's 16 GB:
- the weights are the bfloat16 values the checkpoint's type holds (the program gets the
  same arrays); they are widened to float32 a layer at a time, where they are used;
- the layers are a Python loop over per-layer weights, not one stacked scan;
- the recurrence is a ``lax.scan`` over tokens, the window is a mask, nothing is cached
  or skipped (no ring, no last-position shortcut), the head is taken in blocks of the
  vocabulary and at every position: the driver's call does not name the positions that
  served a token.
"""
import functools
import json
import math

import jax
import jax.numpy as jnp

#: blocks the vocabulary is cut into wherever all of it is touched at once
VOCAB_BLOCKS = 8
#: the draw of ``W_x``, the projection to ``[dr | B | C]``. At 0.02, like the other
#: projections, B and C come out near 0.5 and the state's read-out ``sum_n C S`` at a
#: tenth of the skip ``D x`` beside it (counted on the CPU at the published widths, rms
#: over 700 positions: 0.037 against 0.37; PERF.md section 4), so a broken state would
#: move the logits little more than rounding does. At 0.05 the read-out is of the order of
#: the skip, and the memory that the Gated Memory Units read (``y`` with its skip) is
#: carried by the state as much as by the current token
X_PROJ_STD = 0.05


def key_of(seed):
    """A PRNG key from any whole number up to 2**63 (a seed may pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


def sizes(cfg):
    """The derived sizes of a configuration, under the names used below. The Mamba sizes
    are the class's defaults in the public modeling file unless the file names them."""
    d, hq = cfg["hidden_size"], cfg["num_attention_heads"]
    return {"d": d, "f": cfg["intermediate_size"], "v": cfg["vocab_size"], "hq": hq,
            "hkv": cfg["num_key_value_heads"], "dh": d // hq, "w": cfg["sliding_window"], "eps": cfg["layer_norm_eps"],
            "n": cfg.get("mamba_d_state", 16), "k": cfg.get("mamba_d_conv", 4),
            "e": cfg.get("mamba_expand", 2) * d,
            "r": cfg.get("mamba_dt_rank") or -(-d // 16)}


def kind_of(cfg, l):
    """The mixer of layer ``l``: mamba | window | full | gmu | cross."""
    half = cfg["num_hidden_layers"] // 2
    if l % 2 == 0:
        return "mamba" if l <= half else "gmu"
    return "window" if l < half else "full" if l == half + 1 else "cross"


def lambda_init(l):
    return 0.8 - 0.6 * math.exp(-0.3 * l)


def _normal(key, shape, std):
    """normal(0, std) rounded to bfloat16, the checkpoint's type."""
    return (jax.random.normal(key, shape, jnp.float32) * jnp.float32(std)).astype(jnp.bfloat16)


def _rounded(a):
    """A small float32 vector holding bfloat16 values, as the checkpoint would."""
    return a.astype(jnp.bfloat16).astype(jnp.float32)


def generate_layer(cfg, l, key):
    """Layer ``l``'s weights (traceable): projections bfloat16 ``[in, out]``, the small
    vectors float32 holding bfloat16 values. Projections normal(0, 0.02) (``init_std`` in a
    test's configuration widens them), ``W_x`` normal(0, ``X_PROJ_STD``); the linear biases
    normal(0, 0.02); LayerNorms 1 and 0;
    the four lambda vectors normal(0, 0.1) and the pair norm 1 (the differential
    transformer's own); ``D`` 1, ``A_log = log(1..N)`` in every channel, ``dt_bias`` the
    inverse softplus of a log-uniform draw in [0.001, 0.1], ``W_dt`` uniform(+-R^-0.5), the
    convolution and its bias uniform(-0.5, 0.5) (Mamba-1's own initialisation)."""
    s = sizes(cfg)
    d, f, e, n, r, dh = s["d"], s["f"], s["e"], s["n"], s["r"], s["dh"]
    q, kv = s["hq"] * dh, s["hkv"] * dh
    std = cfg.get("init_std", 0.02)
    ks = iter(jax.random.split(key, 20))
    w = lambda shape, sd=std: _normal(next(ks), shape, sd)                  # noqa: E731
    vec = lambda shape, sd: _rounded(jax.random.normal(next(ks), shape) * sd)  # noqa: E731
    uni = lambda shape, a: _rounded(jax.random.uniform(next(ks), shape, jnp.float32, -a, a))  # noqa: E731
    kind = kind_of(cfg, l)
    p = {"ln1_g": jnp.ones((d,), jnp.float32), "ln1_b": jnp.zeros((d,), jnp.float32),
         "ln2_g": jnp.ones((d,), jnp.float32), "ln2_b": jnp.zeros((d,), jnp.float32),
         "gate": w((d, f)), "up": w((d, f)), "down": w((f, d))}
    if kind == "mamba":
        dt = jnp.exp(jax.random.uniform(next(ks), (e,), jnp.float32, math.log(0.001), math.log(0.1)))
        p.update(in_proj=w((d, 2 * e)), conv_w=uni((s["k"], e), 0.5), conv_b=uni((e,), 0.5),
                 x_proj=w((e, r + 2 * n), cfg.get("x_proj_std", X_PROJ_STD)),
                 dt_proj=jax.random.uniform(next(ks), (r, e), jnp.float32, -r ** -0.5,
                                            r ** -0.5).astype(jnp.bfloat16),
                 dt_bias=_rounded(dt + jnp.log(-jnp.expm1(-dt))),
                 A_log=_rounded(jnp.log(jnp.broadcast_to(jnp.arange(1, n + 1, dtype=jnp.float32), (e, n)))),
                 D=jnp.ones((e,), jnp.float32), out_proj=w((e, d)))
    elif kind == "gmu":
        p.update(in_proj=w((d, e)), out_proj=w((e, d)))
    else:
        p.update(wq=w((d, q)), bq=vec((q,), 0.02), wo=w((q, d)), bo=vec((d,), 0.02),
                 lq1=vec((dh,), 0.1), lk1=vec((dh,), 0.1), lq2=vec((dh,), 0.1), lk2=vec((dh,), 0.1),
                 subln=jnp.ones((2 * dh,), jnp.float32))
        if kind != "cross":
            p.update(wk=w((d, kv)), bk=vec((kv,), 0.02), wv=w((d, kv)), bv=vec((kv,), 0.02))
    return p


def generate_ends(cfg, key):
    """The embedding ``[vocab, d]``, which is the head too, and the last LayerNorm
    (traceable). The table is drawn a block of the vocabulary at a time."""
    s = sizes(cfg)
    d, v = s["d"], s["v"]
    nb = VOCAB_BLOCKS if v % VOCAB_BLOCKS == 0 else 1
    std = cfg.get("init_std", 0.02)
    embed = jax.lax.map(lambda k: _normal(k, (v // nb, d), std), jax.random.split(key, nb))
    return {"embed": embed.reshape(v, d), "final_g": jnp.ones((d,), jnp.float32),
            "final_b": jnp.zeros((d,), jnp.float32)}


def generate(cfg, key):
    """All weights: ``{"embed", "final_g", "final_b", "layers": [one dict a layer]}``. Each
    layer and the ends are made by a call of their own, so that no draw's float32
    temporaries outlive it; layers of one kind share one compiled program."""
    n = cfg["num_hidden_layers"]
    out = jax.jit(functools.partial(generate_ends, cfg))(jax.random.fold_in(key, n))
    made = {}
    layers = []
    for l in range(n):
        kind = kind_of(cfg, l)
        if kind not in made:            # the draws do not depend on l beyond the key
            made[kind] = jax.jit(functools.partial(generate_layer, cfg, l))
        layers.append(made[kind](jax.random.fold_in(key, l)))
    out["layers"] = layers
    return out


def make_params(cfg, seed):
    return generate(cfg, key_of(seed))


# ------------------------------------------------------------------- the equations
def _ln(x, g, b, eps):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, -1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(jnp.mean(jnp.square(x32 - mu), -1, keepdims=True) + eps)
    return (y * g + b).astype(x.dtype)


def _silu(x):
    return x * jax.nn.sigmoid(x)


def _mamba(s, p, h, state):
    """The Mamba-1 mixer over ``h [t, d]``, token by token: its output and the read-out
    ``y`` before the gate. ``state`` is the type the recurrent state is carried in
    (float32; bfloat16 in the control)."""
    t, e, n, r, kw = h.shape[0], s["e"], s["n"], s["r"], s["k"]
    xz = h @ p["in_proj"]
    xt, z = xz[:, :e], xz[:, e:]
    # depthwise causal convolution of width kw: the last tap is the current token
    pad = jnp.concatenate([jnp.zeros((kw - 1, e), xt.dtype), xt])
    conv = sum(pad[j:j + t] * p["conv_w"][j].astype(h.dtype) for j in range(kw))
    x = _silu(conv + p["conv_b"].astype(h.dtype))
    dbc = x @ p["x_proj"]
    dr, b_, c_ = dbc[:, :r], dbc[:, r:r + n], dbc[:, r + n:]
    dt = jax.nn.softplus((dr @ p["dt_proj"]).astype(jnp.float32) + p["dt_bias"])    # [t, e]
    a = -jnp.exp(p["A_log"])                                                         # [e, n]

    def step(S, xs):
        x_t, b_t, c_t, dt_t = xs
        S = (jnp.exp(dt_t[:, None] * a) * S.astype(jnp.float32)
             + (dt_t * x_t)[:, None] * b_t[None, :]).astype(state)
        return S, jnp.sum(S * c_t.astype(state)[None, :], -1)

    _, y = jax.lax.scan(step, jnp.zeros((e, n), state),
                        (x.astype(jnp.float32), b_.astype(jnp.float32), c_, dt), unroll=8)
    y = y.astype(h.dtype) + p["D"].astype(h.dtype) * x
    return (y * _silu(z)) @ p["out_proj"], y


def _diff_attention(s, p, l, h, k, v, window):
    """Differential attention of the queries of ``h [t, d]`` over ``k``/``v`` ``[t, hkv,
    dh]``, causal, and within ``window`` positions where that is given."""
    t, dh = h.shape[0], s["dh"]
    q = (h @ p["wq"] + p["bq"].astype(h.dtype)).reshape(t, s["hq"], dh)
    pairs, kv_pairs = s["hq"] // 2, s["hkv"] // 2
    rep = pairs // kv_pairs                                  # pair i reads KV pair i // rep
    q1, q2 = q[:, 0::2], q[:, 1::2]                          # [t, pairs, dh]
    k1, k2 = jnp.repeat(k[:, 0::2], rep, 1), jnp.repeat(k[:, 1::2], rep, 1)
    vv = jnp.repeat(v.reshape(t, kv_pairs, 2 * dh), rep, 1)  # [t, pairs, 2 dh]
    i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    mask = (j <= i) if window is None else (j <= i) & (i - j < window)

    def softmax_map(qs, ks):
        sc = jnp.einsum("qhd,khd->hqk", qs, ks) / jnp.asarray(dh ** 0.5, h.dtype)
        sc = jnp.where(mask[None], sc, jnp.asarray(-1e30, sc.dtype))
        return jax.nn.softmax(sc.astype(jnp.float32), -1).astype(h.dtype)

    lam0 = lambda_init(l)
    lam = (jnp.exp(jnp.sum(p["lq1"] * p["lk1"])) - jnp.exp(jnp.sum(p["lq2"] * p["lk2"])) + lam0)
    a = softmax_map(q1, k1) - lam.astype(h.dtype) * softmax_map(q2, k2)
    o = jnp.einsum("hqk,khe->qhe", a, vv)                    # [t, pairs, 2 dh]
    o32 = o.astype(jnp.float32)
    o = (o32 * jax.lax.rsqrt(jnp.mean(jnp.square(o32), -1, keepdims=True) + s["eps"])
         * p["subln"] * (1.0 - lam0)).astype(h.dtype)
    return o.reshape(t, -1) @ p["wo"] + p["bo"].astype(h.dtype)


def _kv(s, p, h):
    t = h.shape[0]
    return ((h @ p["wk"] + p["bk"].astype(h.dtype)).reshape(t, s["hkv"], s["dh"]),
            (h @ p["wv"] + p["bv"].astype(h.dtype)).reshape(t, s["hkv"], s["dh"]))


def hidden(cfg, params, tokens, act=jnp.float32):
    """One sequence through every layer, activations (and the recurrent state) in
    ``act``: the last LayerNorm's output ``[t, d]``."""
    s = sizes(cfg)
    x = params["embed"][tokens].astype(act)
    memory = shared = None
    for l, p in enumerate(params["layers"]):
        # the weights of this layer in the activations' type (float32: widened here)
        p = {k: (w.astype(act) if w.dtype == jnp.bfloat16 else w) for k, w in p.items()}
        kind = kind_of(cfg, l)
        h = _ln(x, p["ln1_g"], p["ln1_b"], s["eps"])
        if kind == "mamba":
            out, memory = _mamba(s, p, h, act)      # the last one's read-out is the memory
        elif kind == "gmu":
            out = (memory * _silu(h @ p["in_proj"])) @ p["out_proj"]
        elif kind == "cross":
            out = _diff_attention(s, p, l, h, *shared, None)
        else:
            kv = _kv(s, p, h)
            if kind == "full":
                shared = kv
            out = _diff_attention(s, p, l, h, *kv, s["w"] if kind == "window" else None)
        x = x + out
        h = _ln(x, p["ln2_g"], p["ln2_b"], s["eps"])
        x = x + (_silu(h @ p["gate"]) * (h @ p["up"])) @ p["down"]
    return _ln(x, params["final_g"], params["final_b"], s["eps"])


def _head_blocks(params):
    embed = params["embed"]
    v, d = embed.shape
    nb = VOCAB_BLOCKS if v % VOCAB_BLOCKS == 0 else 1
    return embed.reshape(nb, v // nb, d), v // nb


def forward(cfg, params, tokens, act=jnp.float32):
    """Logits ``[t, vocab]`` at every position, float32: for the tests' small sizes."""
    h = hidden(cfg, params, tokens, act)
    return (h @ params["embed"].astype(act).T).astype(jnp.float32)


def _best_and_at(params, h, judged, act):
    """Over the vocabulary in blocks: each position's largest logit, where it is, and
    the logit of the token ``judged`` there."""
    blocks, width = _head_blocks(params)

    def block(carry, xs):
        best, where, at = carry
        w, lo = xs
        logits = (h @ w.astype(act).T).astype(jnp.float32)
        top = jnp.max(logits, -1)
        where = jnp.where(top > best, lo + jnp.argmax(logits, -1), where)
        inside = (judged >= lo) & (judged < lo + width)
        mine = jnp.take_along_axis(logits, jnp.clip(judged - lo, 0, width - 1)[:, None], 1)[:, 0]
        return (jnp.maximum(best, top), where, jnp.where(inside, mine, at)), None

    t = h.shape[0]
    init = (jnp.full((t,), -jnp.inf, jnp.float32), jnp.zeros((t,), jnp.int32),
            jnp.zeros((t,), jnp.float32))
    los = jnp.arange(blocks.shape[0], dtype=jnp.int32) * width
    (best, where, at), _ = jax.lax.scan(block, init, (blocks, los))
    return best, where, at


def served_gaps(cfg, params, tokens, control=False):
    """For one request, ``tokens`` being its prompt and served ids (padded): ``[t - 1]``
    gaps in the float32 reference's logits between its best token at position ``i`` and
    the one judged there. That is ``tokens[i + 1]``, the served one; with ``control`` it is
    the first choice of the same forward with activations and recurrent state in
    bfloat16, the precision below the float32 the configuration states (a program of its
    own, so that the two forwards' temporaries are never held together)."""
    frozen, tokens = _Frozen(cfg), jnp.asarray(tokens)
    judged = _first_choice_bf16(frozen, params, tokens) if control else tokens[1:]
    return _gaps(frozen, params, tokens, judged)


class _Frozen:
    """A configuration as a static argument of ``jax.jit``: hashed by its contents,
    which are plain JSON."""

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
