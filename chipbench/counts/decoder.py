"""Operations and bytes of a GPT-2-shaped decoder, from its sizes alone.
What the algorithm needs: no padding, no bucket rows, no recomputation."""
BYTES = {"float32": 4, "bf16": 2, "bfloat16": 2}


def n_params(cfg):
    n, d, f, v = cfg["n_layers"], cfg["d_model"], cfg["d_ff"], cfg["vocab_size"]
    per_layer = 4 * d * d + 2 * d * f + f + d + 4 * d
    return v * d + cfg["max_len"] * d + n * per_layer + 2 * d + d * v


def matmul_params(cfg):
    """Weights every token is multiplied through (embeddings are looked up)."""
    n, d, f, v = cfg["n_layers"], cfg["d_model"], cfg["d_ff"], cfg["vocab_size"]
    return n * (4 * d * d + 2 * d * f), d * v


def kv_bytes_per_token(cfg):
    return 2 * cfg["n_layers"] * cfg["d_model"] * BYTES[cfg["engine"]["kv_dtype"]]


def decode_flops(cfg, context):
    """One new token whose attention reads ``context`` tokens (itself included)."""
    body, head = matmul_params(cfg)
    return 2 * (body + head) + 4 * cfg["n_layers"] * cfg["d_model"] * context


def prefill_flops(cfg, prompt):
    """A prompt of ``prompt`` tokens: every token through the body, causal
    attention, and the head for the last position only."""
    body, head = matmul_params(cfg)
    attn = 4 * cfg["n_layers"] * cfg["d_model"] * prompt * (prompt + 1) // 2
    return 2 * body * prompt + 2 * head + attn


def paged_attention_bytes(cfg, contexts):
    """One decode step's attention over live rows with these context lengths:
    K and V of every context token, q in and out back, in every layer."""
    d, n = cfg["d_model"], cfg["n_layers"]
    kv = kv_bytes_per_token(cfg) * sum(contexts)
    return kv + n * len(contexts) * 2 * d * 4
