"""Matmul parameters per token of a dense decoder: per layer the q, k, v
and output projections and the MLP (two matrices, three when gated),
plus the LM head. The embedding lookup, attention's score and value
products, the norms and the biases are not counted."""


def matmul_params(cfg) -> int:
    d = cfg.d_model
    attn = (d * cfg.n_heads * cfg.head_dim
            + 2 * d * cfg.n_kv_heads * cfg.head_dim
            + cfg.n_heads * cfg.head_dim * d)
    mlp = (3 if cfg.gated_mlp else 2) * d * cfg.d_ff
    return cfg.n_layers * (attn + mlp) + cfg.vocab_size * d
