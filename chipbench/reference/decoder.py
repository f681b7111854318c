"""Plain reference for a GPT-2-shaped decoder: weights from the seed and the full
causal forward from the GPT-2 equations (pre-LN, learned positions, MHA, tanh GELU,
untied head, no attention biases), in jax.numpy. Imports nothing of the program."""
import functools

import jax
import jax.numpy as jnp


def key_of(seed):
    """A PRNG key from any whole number up to 2**63 (a seed may pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


def generate(cfg, key):
    """Stacked float32 weights ``{name: [n_layers, ...]}`` (traceable)."""
    n, d, f, v = cfg["n_layers"], cfg["d_model"], cfg["d_ff"], cfg["vocab_size"]
    ks = jax.random.split(key, 9)

    def w(k, *shape):
        return jax.random.normal(k, shape, jnp.float32) * jnp.float32(cfg["init_std"])

    return {"tok": w(ks[0], v, d), "pos": w(ks[1], cfg["max_len"], d),
            "wq": w(ks[2], n, d, d), "wk": w(ks[3], n, d, d),
            "wv": w(ks[4], n, d, d), "wo": w(ks[5], n, d, d),
            "w1": w(ks[6], n, d, f), "w2": w(ks[7], n, f, d),
            "b1": jnp.zeros((n, f)), "b2": jnp.zeros((n, d)),
            "ln1_g": jnp.ones((n, d)), "ln1_b": jnp.zeros((n, d)),
            "ln2_g": jnp.ones((n, d)), "ln2_b": jnp.zeros((n, d)),
            "ln_g": jnp.ones((d,)), "ln_b": jnp.zeros((d,)),
            "head": w(ks[8], d, v)}


LAYER_KEYS = ("wq", "wk", "wv", "wo", "w1", "w2", "b1", "b2",
              "ln1_g", "ln1_b", "ln2_g", "ln2_b")


def make_params(cfg, seed):
    return jax.jit(functools.partial(generate, cfg))(key_of(seed))


def _ln(x, g, b):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + 1e-5) * g + b


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(0.7978845608028654 * (x + 0.044715 * x ** 3)))


def _bf16(params):
    """The control's weights: the nearest precision below the float32 the file states."""
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16), params)


def forward(h_, params, tokens):
    """One sequence through a model with ``h_`` heads, in the type of ``params``: the logits
    ``[t, vocab]``, as float32."""
    t = tokens.shape[0]
    dh = params["tok"].shape[1] // h_
    x = params["tok"][tokens] + params["pos"][:t]
    causal = jnp.tril(jnp.ones((t, t), bool))

    def layer(x, p):
        h = _ln(x, p["ln1_g"], p["ln1_b"])
        qh, k, v = ((h @ p[n]).reshape(t, h_, dh) for n in ("wq", "wk", "wv"))
        s = jnp.einsum("qhd,khd->hqk", qh, k) / jnp.asarray(dh ** 0.5, x.dtype)
        s = jnp.where(causal[None], s, jnp.asarray(-1e30, s.dtype))
        a = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v)
        x = x + a.reshape(t, -1) @ p["wo"]
        h = _ln(x, p["ln2_g"], p["ln2_b"])
        return x + _gelu(h @ p["w1"] + p["b1"]) @ p["w2"] + p["b2"], None

    x, _ = jax.lax.scan(layer, x, {k: params[k] for k in LAYER_KEYS})
    return (_ln(x, params["ln_g"], params["ln_b"]) @ params["head"]).astype(jnp.float32)


def served_gaps(cfg, params, tokens, control=False):
    return _served_gaps(cfg["n_heads"], params, tokens, control)


@functools.partial(jax.jit, static_argnums=(0, 3))
def _served_gaps(heads, params, tokens, control):
    """For one request, ``tokens`` being its prompt and served ids (padded): ``[t - 1]`` gaps
    in the float32 reference's logits between its best token at position ``i`` and the
    one judged there. That is ``tokens[i + 1]``, the served one; with ``control`` it is the
    first choice of the same forward with weights and activations in bfloat16."""
    with jax.default_matmul_precision("highest"):
        ref = forward(heads, params, tokens)[:-1]
    judged = (jnp.argmax(forward(heads, _bf16(params), tokens)[:-1], -1) if control
              else tokens[1:])
    return jnp.max(ref, -1) - jnp.take_along_axis(ref, judged[:, None], 1)[:, 0]
