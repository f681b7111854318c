"""Operations and bytes of a MiMo-V2 decoder cut to one chip's share, from its sizes alone.
What the algorithm needs: no padding, no bucket rows, no recomputation; a window layer reads
``min(context, window)`` positions of its ring, a full layer its whole growing K/V; of the
sparse experts only the held share is this chip's work: a token sends ``top x held /
experts`` of its pairs here on average (8 x 16 / 256 of an expert a token a layer)."""
BYTES = {"float32": 4, "bf16": 2, "bfloat16": 2}


def sizes(cfg):
    n = cfg["num_hidden_layers"]
    kinds = cfg["hybrid_layer_pattern"][:n]
    return {"n": n, "d": cfg["hidden_size"], "hq": cfg["num_attention_heads"], "dk": cfg["head_dim"],
            "dv": cfg["v_head_dim"], "hkv": cfg["num_key_value_heads"], "hkv_w": cfg["swa_num_key_value_heads"],
            "f": cfg["intermediate_size"], "fe": cfg["moe_intermediate_size"], "v": cfg["vocab_size"],
            "w": cfg["sliding_window"], "experts": cfg.get("router_experts", cfg["n_routed_experts"]),
            "held": cfg["n_routed_experts"], "top": cfg["num_experts_per_tok"],
            "full": kinds.count(0), "window": kinds.count(1),
            "moe": sum(cfg["moe_layer_freq"][:n]), "dense": n - sum(cfg["moe_layer_freq"][:n])}


def attention_params(cfg, window):
    s = sizes(cfg)
    hkv = s["hkv_w"] if window else s["hkv"]
    return s["d"] * (s["hq"] * s["dk"] + hkv * (s["dk"] + s["dv"])) + s["hq"] * s["dv"] * s["d"]


def expert_params(cfg):
    """One expert's SwiGLU."""
    s = sizes(cfg)
    return 3 * s["d"] * s["fe"]


def matmul_params(cfg):
    """Weights a token is multiplied through on this chip on average, and the head's: of the
    experts, the held share of its ``top`` chosen."""
    s = sizes(cfg)
    share = s["top"] * s["held"] / s["experts"]
    body = (s["full"] * attention_params(cfg, False) + s["window"] * attention_params(cfg, True)
            + s["dense"] * 3 * s["d"] * s["f"]
            + s["moe"] * (s["d"] * s["experts"] + share * expert_params(cfg)))
    return body, s["d"] * s["v"]


def n_params(cfg):
    """Every parameter this chip holds: embedding and head (the slice), attention, the dense
    MLP, routers with their bias, all held experts, norms and sinks."""
    s = sizes(cfg)
    return (2 * s["d"] * s["v"] + s["full"] * attention_params(cfg, False)
            + s["window"] * (attention_params(cfg, True) + s["hq"]) + s["dense"] * 3 * s["d"] * s["f"]
            + s["moe"] * (s["d"] * s["experts"] + s["experts"] + s["held"] * expert_params(cfg))
            + (2 * s["n"] + 1) * s["d"])


def _attention_flops(cfg, full_keys, window_keys):
    """Scores (2 hq dk keys) and values (2 hq dv keys) of one query position in every layer."""
    s = sizes(cfg)
    per_key = 2 * s["hq"] * (s["dk"] + s["dv"])
    return per_key * (s["full"] * full_keys + s["window"] * window_keys)


def decode_flops(cfg, context):
    """One new token whose full layers hold ``context`` tokens (itself included)."""
    body, head = matmul_params(cfg)
    return 2 * (body + head) + _attention_flops(cfg, context, min(context, sizes(cfg)["w"]))


def prefill_flops(cfg, prompt):
    """A prompt of ``prompt`` tokens: every token through the body, causal attention (a band in
    the window layers), the head for the last position only."""
    body, head = matmul_params(cfg)
    w = sizes(cfg)["w"]
    full = prompt * (prompt + 1) // 2
    band = sum(min(t + 1, w) for t in range(prompt))
    return 2 * body * prompt + 2 * head + _attention_flops(cfg, full, band)


def kv_bytes_per_token(cfg):
    """K and V of one token in the full layers (the pool that grows with the context)."""
    s = sizes(cfg)
    return s["full"] * s["hkv"] * (s["dk"] + s["dv"]) * BYTES[cfg["engine"]["kv_dtype"]]


def ring_bytes_per_token(cfg):
    """K and V of one token in every window layer's ring."""
    s = sizes(cfg)
    return s["window"] * s["hkv_w"] * (s["dk"] + s["dv"]) * BYTES[cfg["engine"]["kv_dtype"]]


def paged_attention_bytes(cfg, contexts):
    """One decode step's attention over live rows with these context lengths: each full layer's
    K (192 a head) and V (128) of every context token, a ring of ``min(context, window)`` tokens in
    each window layer, and q in (heads x 192) and out (heads x 128) in float32 in every layer."""
    s = sizes(cfg)
    grown = kv_bytes_per_token(cfg) * sum(contexts)
    rings = ring_bytes_per_token(cfg) * sum(min(n, s["w"]) for n in contexts)
    return grown + rings + s["n"] * len(contexts) * s["hq"] * (s["dk"] + s["dv"]) * 4


def expert_bytes(cfg, experts_hit, rows):
    """What the grouped products of the expert layers have to move: the weights of every held
    expert that a row was routed to (bfloat16), each routed row in (bfloat16, ``d`` wide) and
    its result out (float32). ``experts_hit`` and ``rows`` are sums over the expert layers, as
    the program's ``moe_experts_hit`` and ``moe_rows`` count them."""
    s = sizes(cfg)
    return experts_hit * expert_params(cfg) * 2 + rows * s["d"] * (2 + 4)


def expert_flops(cfg, rows):
    """One routed row through one expert: three products of ``d x fe``."""
    return rows * 2 * expert_params(cfg)
