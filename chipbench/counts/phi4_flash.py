"""Operations and bytes of a Phi-4-mini-flash decoder, from its sizes alone. What the
algorithm needs: no padding, no bucket rows, no recomputation; the window layers read
``min(context, window)`` positions, the one full K/V layer is read by the full layer and
every cross layer, the prefill runs the layers from the full one up on one position, and
the recurrence is counted token by token."""
BYTES = {"float32": 4, "bf16": 2, "bfloat16": 2}
KINDS = ("mamba", "window", "full", "gmu", "cross")


def sizes(cfg):
    d, hq = cfg["hidden_size"], cfg["num_attention_heads"]
    dh = d // hq
    return {"n": cfg["num_hidden_layers"], "d": d, "f": cfg["intermediate_size"], "v": cfg["vocab_size"],
            "q": hq * dh, "kv": cfg["num_key_value_heads"] * dh, "w": cfg["sliding_window"],
            "e": cfg.get("mamba_expand", 2) * d, "s": cfg.get("mamba_d_state", 16),
            "k": cfg.get("mamba_d_conv", 4), "r": cfg.get("mamba_dt_rank") or -(-d // 16)}


def kinds(cfg):
    """How many layers of each kind: mamba, window, full, gmu, cross."""
    n = cfg["num_hidden_layers"]
    half = n // 2
    names = [("mamba" if l <= half else "gmu") if l % 2 == 0 else
             ("window" if l < half else "full" if l == half + 1 else "cross") for l in range(n)]
    return {k: names.count(k) for k in KINDS}


def mixer_params(cfg):
    """Weights of one mixer of each kind that a token is multiplied through."""
    s = sizes(cfg)
    d, e, q, kv = s["d"], s["e"], s["q"], s["kv"]
    return {"mamba": d * 2 * e + e * (s["r"] + 2 * s["s"]) + s["r"] * e + e * d,
            "window": d * q + 2 * d * kv + q * d, "full": d * q + 2 * d * kv + q * d,
            "gmu": 2 * d * e, "cross": 2 * d * q}


def matmul_params(cfg):
    """Weights every decoded token is multiplied through, and the tied head's."""
    s, per = sizes(cfg), mixer_params(cfg)
    body = s["n"] * 3 * s["d"] * s["f"] + sum(c * per[k] for k, c in kinds(cfg).items())
    return body, s["d"] * s["v"]


def n_params(cfg):
    """Every parameter, the tied table once: with the products' weights the LayerNorms,
    the linear biases, the convolution with its bias, ``dt_bias``, ``D`` and ``A_log``,
    the four lambda vectors and the pair norm."""
    s, c = sizes(cfg), kinds(cfg)
    body, head = matmul_params(cfg)
    dh = s["q"] // cfg["num_attention_heads"]
    attention = c["window"] + c["full"] + c["cross"]
    small = (s["n"] * 4 * s["d"] + 2 * s["d"]
             + c["mamba"] * ((s["k"] + 3) * s["e"] + s["e"] * s["s"])
             + attention * (s["q"] + s["d"] + 6 * dh) + (c["window"] + c["full"]) * 2 * s["kv"])
    return head + body + small


def _recurrence_flops(cfg):
    """One token through every Mamba layer outside its projections: the convolution,
    and for each of ``d_inner x d_state`` numbers the decay's product and exponential,
    the update and the read-out."""
    s = sizes(cfg)
    return kinds(cfg)["mamba"] * (7 * s["e"] * s["s"] + 2 * s["k"] * s["e"])


def _attention_flops(cfg, keys):
    """One query position of one differential attention layer over ``keys`` positions:
    scores of every query head (2 q keys) and each head's map times the pair's V, twice
    a head's width (4 q keys)."""
    return 6 * sizes(cfg)["q"] * keys


def decode_flops(cfg, context):
    """One new token whose full layer holds ``context`` tokens (itself included)."""
    s, c = sizes(cfg), kinds(cfg)
    body, head = matmul_params(cfg)
    attn = ((c["full"] + c["cross"]) * _attention_flops(cfg, context)
            + c["window"] * _attention_flops(cfg, min(context, s["w"])))
    return 2 * (body + head) + attn + _recurrence_flops(cfg)


def prefill_flops(cfg, prompt):
    """A prompt of ``prompt`` tokens as the model is built: every token through the
    layers below the full one and through the full layer's K/V projection; the layers
    from the full one up, and the head, for the last position only."""
    s, c, per = sizes(cfg), kinds(cfg), mixer_params(cfg)
    mlp = 3 * s["d"] * s["f"]
    lower = c["mamba"] * (per["mamba"] + mlp) + c["window"] * (per["window"] + mlp)
    upper = (per["full"] - 2 * s["d"] * s["kv"] + mlp + c["gmu"] * (per["gmu"] + mlp)
             + c["cross"] * (per["cross"] + mlp))
    window = sum(min(t + 1, s["w"]) for t in range(prompt))
    attn = (c["window"] * _attention_flops(cfg, window)
            + (c["full"] + c["cross"]) * _attention_flops(cfg, prompt))
    return (2 * (lower + 2 * s["d"] * s["kv"]) + _recurrence_flops(cfg)) * prompt \
        + 2 * (upper + s["d"] * s["v"]) + attn


def kv_bytes_per_token(cfg):
    """K and V of one token in the one layer that grows with the context."""
    return 2 * sizes(cfg)["kv"] * BYTES[cfg["engine"]["kv_dtype"]]


def paged_attention_bytes(cfg, contexts):
    """One decode step's attention over live rows with these context lengths: the full
    layer's K and V of every context token once for each layer that reads it (the full
    layer and the cross layers), a ring of ``min(context, window)`` tokens in each window
    layer, and q in (heads x head_dim) and out (heads x 2 head_dim) in float32."""
    s, c = sizes(cfg), kinds(cfg)
    tok = kv_bytes_per_token(cfg)
    grown = (c["full"] + c["cross"]) * tok * sum(contexts)
    rings = c["window"] * tok * sum(min(n, s["w"]) for n in contexts)
    return grown + rings + (c["window"] + c["full"] + c["cross"]) * len(contexts) * 3 * s["q"] * 4


def ssm_state_bytes(cfg, rows):
    """The Mamba-1 state of ``rows`` live rows read and written once in every Mamba
    layer, float32: what one decode step's state update has to move."""
    s = sizes(cfg)
    return rows * kinds(cfg)["mamba"] * 2 * s["e"] * s["s"] * 4


def selective_scan_bytes(cfg, prompt):
    """The prefill scans of one prompt of ``prompt`` tokens: in every Mamba layer the
    convolved input and ``dt`` read and the read-out written (``d_inner`` float32 each a
    token), ``B`` and ``C`` read (``d_state`` each), the final state written. The states
    in between never leave the chip."""
    s = sizes(cfg)
    return kinds(cfg)["mamba"] * 4 * (prompt * (3 * s["e"] + 2 * s["s"]) + s["e"] * s["s"])
