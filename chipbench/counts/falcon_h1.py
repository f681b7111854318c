"""Operations and bytes of a Falcon-H1 decoder, from its sizes alone. What the
algorithm needs: no padding, no bucket rows, no recomputation, and the recurrence
counted token by token (not the chunked form's extra products)."""
BYTES = {"float32": 4, "bf16": 2, "bfloat16": 2}


def _sizes(cfg):
    return (cfg["num_hidden_layers"], cfg["hidden_size"], cfg["num_attention_heads"] * cfg["head_dim"],
            cfg["num_key_value_heads"] * cfg["head_dim"])


def conv_dim(cfg):
    return cfg["mamba_d_ssm"] + 2 * cfg["mamba_n_groups"] * cfg["mamba_d_state"]


def state_numbers(cfg):
    """Numbers in one layer's recurrent state of one sequence."""
    return cfg["mamba_n_heads"] * cfg["mamba_d_head"] * cfg["mamba_d_state"]


def matmul_params(cfg):
    """Weights every token is multiplied through (the embedding is looked up)."""
    n, d, q, kv = _sizes(cfg)
    attn = d * q + 2 * d * kv + q * d
    mixer = d * (cfg["mamba_d_ssm"] + conv_dim(cfg) + cfg["mamba_n_heads"]) + cfg["mamba_d_ssm"] * d
    return n * (attn + mixer + 3 * d * cfg["intermediate_size"]), d * cfg["vocab_size"]


def n_params(cfg):
    n, d, _, _ = _sizes(cfg)
    body, head = matmul_params(cfg)
    small = (cfg["mamba_d_conv"] + 1) * conv_dim(cfg) + 3 * cfg["mamba_n_heads"] + cfg["mamba_d_ssm"] + 2 * d
    return cfg["vocab_size"] * d + body + n * small + d + head


def _recurrence_flops(cfg):
    """One token through every layer's mixer outside its projections: the convolution,
    decay, outer product and read-out of the state."""
    return cfg["num_hidden_layers"] * (5 * state_numbers(cfg) + 2 * cfg["mamba_d_conv"] * conv_dim(cfg))


def kv_bytes_per_token(cfg):
    n, _, _, kv = _sizes(cfg)
    return 2 * n * kv * BYTES[cfg["engine"]["kv_dtype"]]


def decode_flops(cfg, context):
    """One new token whose attention reads ``context`` tokens (itself included)."""
    n, _, q, _ = _sizes(cfg)
    body, head = matmul_params(cfg)
    return 2 * (body + head) + 4 * n * q * context + _recurrence_flops(cfg)


def prefill_flops(cfg, prompt):
    """A prompt of ``prompt`` tokens: every token through the body and the recurrence,
    causal attention, and the head for the last position only."""
    n, _, q, _ = _sizes(cfg)
    body, head = matmul_params(cfg)
    attn = 4 * n * q * prompt * (prompt + 1) // 2
    return (2 * body + _recurrence_flops(cfg)) * prompt + 2 * head + attn


def paged_attention_bytes(cfg, contexts):
    """One decode step's attention over live rows with these context lengths: K and V
    (4 KV heads) of every context token, q in and out back (20 heads), in every layer."""
    n, _, q, _ = _sizes(cfg)
    return kv_bytes_per_token(cfg) * sum(contexts) + n * len(contexts) * 2 * q * 4


def ssm_state_bytes(cfg, rows):
    """The recurrent state of ``rows`` live rows read and written once in every layer,
    float32: what one decode step's state update has to move."""
    return rows * cfg["num_hidden_layers"] * 2 * state_numbers(cfg) * 4
