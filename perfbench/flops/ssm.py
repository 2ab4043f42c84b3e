"""Matmul parameters per token of a Mamba-2 model (arXiv:2405.21060):
per layer the input projection to (z, x, B, C, dt) and the output
projection, plus the LM head. The depthwise conv, the SSD scan and the
norms are not matmuls over parameters and are not counted."""


def matmul_params(cfg) -> int:
    d = cfg.d_model
    s = cfg.ssm
    d_in = s.expand * d
    n_heads = d_in // s.head_dim
    in_proj = d * (2 * d_in + 2 * s.n_groups * s.d_state + n_heads)
    out_proj = d_in * d
    return cfg.n_layers * (in_proj + out_proj) + cfg.vocab_size * d
