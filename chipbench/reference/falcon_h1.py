"""Plain reference for a Falcon-H1 decoder: weights from the seed and the full causal
forward from the published equations, in jax.numpy. Every block runs a Mamba-2 mixer and
grouped-query attention side by side on one normed input and adds both in one residual
step, then a SwiGLU MLP; fourteen fixed scalar multipliers (muP) sit on the embedding,
the two branch inputs and outputs, the keys, the five segments of the mixer's input
projection, the MLP's gate and output, and the logits. Imports nothing of the program.

Forms as in the public ``transformers`` ``modeling_falcon_h1.py``, written from the
catalog row's ``config``. Departures from the published description, each for the chip's
16 GB:
- the weights are the bfloat16 values the checkpoint's type holds (the program gets the
  same arrays); they are widened to float32 a layer at a time, where they are used;
- the layers are a Python loop over per-layer weights and not one stacked scan: stacking
  10 GB of weights would hold them twice;
- the recurrence is a ``lax.scan`` over tokens (no chunks), the head is taken in blocks
  of the vocabulary, and at every position: the driver's call does not name the
  positions that served a token.
"""
import functools
import json

import jax
import jax.numpy as jnp

#: blocks the vocabulary is cut into wherever all of it is touched at once
VOCAB_BLOCKS = 8
#: the draw of the mixer's input projection. At 0.02, like the other projections, the
#: five ssm multipliers (0.18-0.5, on an input already times 0.25) leave x, B and C so
#: small that the state's read-out S C is a thousandth of the skip D x beside it (a count
#: on the CPU at the published widths: rms 2.1e-5 against 0.026), and no comparison of
#: logits could tell a sound recurrent state from a broken one. At 0.25 a column's norm
#: undoes the multipliers (1 / (0.25 * 0.25) = 16 = 0.22 * sqrt(5120)), x is of order 1,
#: dt spans 1e-6 to 3, and S C is of the order of D x
IN_PROJ_STD = 0.25


def key_of(seed):
    """A PRNG key from any whole number up to 2**63 (a seed may pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


def sizes(cfg):
    """The derived sizes of a configuration, under the names used below."""
    d_ssm, g, n = cfg["mamba_d_ssm"], cfg["mamba_n_groups"], cfg["mamba_d_state"]
    return {"d": cfg["hidden_size"], "f": cfg["intermediate_size"], "v": cfg["vocab_size"],
            "hq": cfg["num_attention_heads"], "hkv": cfg["num_key_value_heads"],
            "dh": cfg["head_dim"], "d_ssm": d_ssm, "g": g, "n": n,
            "h": cfg["mamba_n_heads"], "p": cfg["mamba_d_head"], "k": cfg["mamba_d_conv"],
            "conv_dim": d_ssm + 2 * g * n, "in_dim": 2 * d_ssm + 2 * g * n + cfg["mamba_n_heads"]}


def _normal(key, shape, std):
    """normal(0, std) rounded to bfloat16, the checkpoint's type."""
    return (jax.random.normal(key, shape, jnp.float32) * jnp.float32(std)).astype(jnp.bfloat16)


def _rounded(a):
    """A small float32 vector holding bfloat16 values, as the checkpoint would."""
    return a.astype(jnp.bfloat16).astype(jnp.float32)


def generate_layer(cfg, key):
    """One block's weights (traceable): projections bfloat16 ``[in, out]``, the small
    vectors float32 holding bfloat16 values. Projections normal(0, 0.02) (``init_std``
    in a test's configuration widens them; ``in_proj`` normal(0, ``IN_PROJ_STD``)), norms 1, ``D`` 1, ``A_log = log(uniform(1,
    16))``, ``dt_bias`` the inverse softplus of a log-uniform draw in [0.001, 0.1], the
    convolution uniform(-0.5, 0.5) with bias 0 (Mamba-2's own initialisation, assumed)."""
    s = sizes(cfg)
    d, f, h = s["d"], s["f"], s["h"]
    ks = jax.random.split(key, 12)
    w = functools.partial(_normal, std=cfg.get("init_std", 0.02))
    dt = jnp.exp(jax.random.uniform(ks[9], (h,), jnp.float32,
                                    jnp.log(0.001), jnp.log(0.1)))
    return {"wq": w(ks[0], (d, s["hq"] * s["dh"])),
            "wk": w(ks[1], (d, s["hkv"] * s["dh"])),
            "wv": w(ks[2], (d, s["hkv"] * s["dh"])),
            "wo": w(ks[3], (s["hq"] * s["dh"], d)),
            "in_proj": _normal(ks[4], (d, s["in_dim"]), IN_PROJ_STD),
            "out_proj": w(ks[5], (s["d_ssm"], d)),
            "gate": w(ks[6], (d, f)), "up": w(ks[7], (d, f)),
            "down": w(ks[8], (f, d)),
            "dt_bias": _rounded(dt + jnp.log(-jnp.expm1(-dt))),
            "A_log": _rounded(jnp.log(jax.random.uniform(ks[10], (h,), jnp.float32, 1.0, 16.0))),
            "conv_w": _rounded(jax.random.uniform(ks[11], (s["k"], s["conv_dim"]),
                                                  jnp.float32, -0.5, 0.5)),
            "conv_b": jnp.zeros((s["conv_dim"],), jnp.float32),
            "D": jnp.ones((h,), jnp.float32),
            "ssm_norm": jnp.ones((s["d_ssm"],), jnp.float32),
            "norm1": jnp.ones((d,), jnp.float32), "norm2": jnp.ones((d,), jnp.float32)}


def generate_ends(cfg, key):
    """Embedding ``[vocab, d]``, untied head ``[d, vocab]`` and the last norm (traceable).
    The two big tables are drawn a block of the vocabulary at a time: drawn whole, the
    float32 draw of one (5.3 GB) would not fit beside the layers."""
    s = sizes(cfg)
    d, v = s["d"], s["v"]
    nb = VOCAB_BLOCKS if v % VOCAB_BLOCKS == 0 else 1
    ke, kh = jax.random.split(key)
    std = cfg.get("init_std", 0.02)
    embed = jax.lax.map(lambda k: _normal(k, (v // nb, d), std), jax.random.split(ke, nb))
    head = jax.lax.map(lambda k: _normal(k, (d, v // nb), std), jax.random.split(kh, nb))
    return {"embed": embed.reshape(v, d),
            "head": jnp.moveaxis(head, 0, 1).reshape(d, v),
            "final_norm": jnp.ones((d,), jnp.float32)}


def generate(cfg, key):
    """All weights: ``{"embed", "head", "final_norm", "layers": [one dict a block]}``. Each
    block and the ends are made by a call of their own, so that no draw's float32
    temporaries outlive it."""
    n = cfg["num_hidden_layers"]
    layer = jax.jit(functools.partial(generate_layer, cfg))
    out = jax.jit(functools.partial(generate_ends, cfg))(jax.random.fold_in(key, n))
    out["layers"] = [layer(jax.random.fold_in(key, i)) for i in range(n)]
    return out


def make_params(cfg, seed):
    return generate(cfg, key_of(seed))


# ------------------------------------------------------------------- the equations
def _rms(x, w, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(jnp.square(x32), -1, keepdims=True) + eps)
    return (y * w).astype(x.dtype)


def _silu(x):
    return x * jax.nn.sigmoid(x)


def _rope(x, theta):
    """Rotary positions on ``x [t, heads, dh]``, half-rotation pairs over all of ``dh``."""
    t, _, dh = x.shape
    inv = 1.0 / (float(theta) ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = (jnp.concatenate([f(ang), f(ang)], -1)[:, None, :].astype(x.dtype)
                for f in (jnp.cos, jnp.sin))
    half = jnp.concatenate([-x[..., dh // 2:], x[..., :dh // 2]], -1)
    return x * cos + half * sin


def _attention(cfg, s, p, h):
    t = h.shape[0]
    q = (h @ p["wq"]).reshape(t, s["hq"], s["dh"])
    k = ((h @ p["wk"]) * jnp.asarray(cfg["key_multiplier"], h.dtype)).reshape(t, s["hkv"], s["dh"])
    v = (h @ p["wv"]).reshape(t, s["hkv"], s["dh"])
    q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
    rep = s["hq"] // s["hkv"]                   # query head i reads KV head i // rep
    k, v = jnp.repeat(k, rep, 1), jnp.repeat(v, rep, 1)
    sc = jnp.einsum("qhd,khd->hqk", q, k) / jnp.asarray(s["dh"] ** 0.5, h.dtype)
    causal = jnp.tril(jnp.ones((t, t), bool))
    sc = jnp.where(causal[None], sc, jnp.asarray(-1e30, sc.dtype))
    a = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc.astype(jnp.float32), -1).astype(h.dtype), v)
    return a.reshape(t, -1) @ p["wo"]


def _mamba(cfg, s, p, h, state):
    """The Mamba-2 mixer over ``h [t, d]``, token by token. ``state`` is the type the
    recurrent state is carried in (float32; bfloat16 in the control)."""
    t = h.shape[0]
    d_ssm, g, n, nh, hp, kw = s["d_ssm"], s["g"], s["n"], s["h"], s["p"], s["k"]
    m = cfg["ssm_multipliers"]
    mup = jnp.concatenate([jnp.full((w,), m[i], jnp.float32) for i, w in
                           enumerate((d_ssm, d_ssm, g * n, g * n, nh))]).astype(h.dtype)
    u = (h @ p["in_proj"]) * mup
    z, xbc, dt = u[:, :d_ssm], u[:, d_ssm:d_ssm + s["conv_dim"]], u[:, -nh:]
    # depthwise causal convolution of width kw: the last tap is the current token
    pad = jnp.concatenate([jnp.zeros((kw - 1, xbc.shape[1]), xbc.dtype), xbc])
    conv = sum(pad[j:j + t] * p["conv_w"][j].astype(h.dtype) for j in range(kw))
    xbc = _silu(conv + p["conv_b"].astype(h.dtype))
    x = xbc[:, :d_ssm].reshape(t, nh, hp)
    b_, c_ = (xbc[:, lo:lo + g * n].reshape(t, g, n) for lo in (d_ssm, d_ssm + g * n))
    b_, c_ = jnp.repeat(b_, nh // g, 1), jnp.repeat(c_, nh // g, 1)     # head i: group i // (nh/g)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])         # [t, nh]
    decay = jnp.exp(dt * -jnp.exp(p["A_log"]))

    def step(S, xs):
        x_t, b_t, c_t, dt_t, a_t = xs
        S = (a_t[:, None, None] * S.astype(jnp.float32)
             + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]).astype(state)
        return S, jnp.einsum("hpn,hn->hp", S, c_t.astype(state))

    S0 = jnp.zeros((nh, hp, n), state)
    _, y = jax.lax.scan(step, S0, (x.astype(jnp.float32), b_.astype(jnp.float32), c_,
                                   dt, decay))
    y = y.astype(h.dtype) + p["D"].astype(h.dtype)[None, :, None] * x
    # mamba_rms_norm, not before the gate: gate first, then RMSNorm over each group
    y = y.reshape(t, d_ssm) * _silu(z)
    y = _rms(y.reshape(t, g, d_ssm // g), p["ssm_norm"].reshape(g, -1), cfg["rms_norm_eps"])
    return y.reshape(t, d_ssm) @ p["out_proj"]


def _mlp(cfg, p, h):
    m = cfg["mlp_multipliers"]
    gate = (h @ p["gate"]) * jnp.asarray(m[0], h.dtype)
    return ((_silu(gate) * (h @ p["up"])) @ p["down"]) * jnp.asarray(m[1], h.dtype)


def hidden(cfg, params, tokens, act=jnp.float32):
    """One sequence through every block, activations (and the recurrent state) in
    ``act``: the last norm's output ``[t, d]``."""
    s, eps = sizes(cfg), cfg["rms_norm_eps"]
    mul = lambda name: jnp.asarray(cfg[name], act)  # noqa: E731
    x = params["embed"][tokens].astype(act) * mul("embedding_multiplier")
    for p in params["layers"]:
        # the weights of this block in the activations' type (float32: widened here)
        p = {k: (w.astype(act) if w.dtype == jnp.bfloat16 else w) for k, w in p.items()}
        h = _rms(x, p["norm1"], eps)
        x = (x + _mamba(cfg, s, p, h * mul("ssm_in_multiplier"), act) * mul("ssm_out_multiplier")
             + _attention(cfg, s, p, h * mul("attention_in_multiplier"))
             * mul("attention_out_multiplier"))
        x = x + _mlp(cfg, p, _rms(x, p["norm2"], eps))
    return _rms(x, params["final_norm"], eps)


def _head_blocks(params):
    head = params["head"]
    d, v = head.shape
    nb = VOCAB_BLOCKS if v % VOCAB_BLOCKS == 0 else 1
    return jnp.moveaxis(head.reshape(d, nb, v // nb), 1, 0), v // nb


def forward(cfg, params, tokens, act=jnp.float32):
    """Logits ``[t, vocab]`` at every position, float32: for the tests' small sizes."""
    h = hidden(cfg, params, tokens, act)
    logits = (h @ params["head"].astype(act)) * jnp.asarray(cfg["lm_head_multiplier"], act)
    return logits.astype(jnp.float32)


def _best_and_at(cfg, params, h, judged, act):
    """Over the vocabulary in blocks: each position's largest logit, where it is, and
    the logit of the token ``judged`` there."""
    blocks, width = _head_blocks(params)
    mult = jnp.asarray(cfg["lm_head_multiplier"], act)

    def block(carry, xs):
        best, where, at = carry
        w, lo = xs
        logits = ((h @ w.astype(act)) * mult).astype(jnp.float32)
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
    return _best_and_at(frozen.cfg, params, low, tokens[1:], jnp.bfloat16)[1]


@functools.partial(jax.jit, static_argnums=0)
def _gaps(frozen, params, tokens, judged):
    with jax.default_matmul_precision("highest"):
        h = hidden(frozen.cfg, params, tokens, jnp.float32)[:-1]
        best, _, at = _best_and_at(frozen.cfg, params, h, judged, jnp.float32)
    return best - at
