"""Operations and bytes the algorithm needs, from shapes alone. These are the
least a correct implementation does: no recompute, no padding, no gather of
dead positions, so a share computed from them cannot pass 1.

``cfg`` is a configuration file's dict (the published key names)."""


def layer_matmul_params(cfg) -> int:
    """Weights of one decoder layer that take part in a matmul."""
    e, f = cfg["hidden_size"], cfg["intermediate_size"]
    d = cfg.get("head_dim") or e // cfg["num_attention_heads"]
    q = cfg["num_attention_heads"] * d
    kv = cfg["num_key_value_heads"] * d
    return e * q + 2 * e * kv + q * e + 3 * e * f


def matmul_params(cfg) -> int:
    """Matmul weights of the whole model: layers and the output head. The
    embedding table is a gather, not a matmul, and norms are vectors."""
    head = cfg["hidden_size"] * cfg["vocab_size"]
    return cfg["num_hidden_layers"] * layer_matmul_params(cfg) + head


def total_params(cfg) -> int:
    e = cfg["hidden_size"]
    table = cfg["vocab_size"] * e
    head = 0 if cfg.get("tie_word_embeddings") else table
    norms = (2 * cfg["num_hidden_layers"] + 1) * e
    return cfg["num_hidden_layers"] * layer_matmul_params(cfg) + table + head + norms


def attention_flops_fwd(cfg, batch, seq) -> float:
    """Causal self-attention forward, QK^T and PV, half the square."""
    d = cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]
    return 4.0 * batch * cfg["num_attention_heads"] * seq * seq * d * 0.5


def train_flops_per_token(cfg, seq) -> float:
    """Forward + backward model FLOPs per trained token: 6 per matmul weight,
    plus causal attention (forward once, backward twice) in every layer."""
    attn = 3.0 * attention_flops_fwd(cfg, 1, seq) / seq * cfg["num_hidden_layers"]
    return 6.0 * matmul_params(cfg) + attn


def kv_bytes_per_token(cfg, itemsize=2) -> int:
    d = cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]
    return 2 * cfg["num_key_value_heads"] * d * itemsize * cfg["num_hidden_layers"]


def decode_bytes_per_iteration(cfg, live_context_tokens, itemsize=2) -> float:
    """HBM bytes one decode iteration must read: every matmul weight once
    (whatever the batch), and the keys and values of the live contexts."""
    return (matmul_params(cfg) * itemsize
            + live_context_tokens * kv_bytes_per_token(cfg, itemsize))
