"""Model FLOPs of a training token of a granite-4.0-h stack (Mamba2 layers
interleaved with attention, each followed by its own MLP), from the
configuration file's shapes.

Kept with the benchmark, beside ``counts.py``, so that the work a metric
credits cannot change with the program under test.
"""
from __future__ import annotations

SSD_CHUNK = 256  # the published mamba_chunk_size


def _layers(c: dict) -> list:
    return c["layer_types"][: c["num_hidden_layers"]]


def matmul_params(c: dict) -> int:
    """Parameters that enter a matrix product: each Mamba2 layer's in_proj
    (z, xBC, dt) and out_proj, each attention layer's projections, every
    layer's SwiGLU MLP, and the tied LM head."""
    d, hd = c["hidden_size"], c["head_dim"]
    di = c["mamba_n_heads"] * c["mamba_d_head"]
    xbc = di + 2 * c["mamba_n_groups"] * c["mamba_d_state"]
    mixer = {
        "mamba": d * (di + xbc + c["mamba_n_heads"]) + di * d,
        "attention": d * hd * 2 * (c["num_attention_heads"] + c["num_key_value_heads"]),
    }
    mlp = 3 * d * c["shared_intermediate_size"]
    return sum(mixer[t] + mlp for t in _layers(c)) + c["vocab_size"] * d


def ssd_flops_per_token(c: dict, chunk: int = SSD_CHUNK) -> float:
    """Forward FLOPs of one Mamba2 layer's chunked scan per token (2 per
    multiply-add): the causal ``C B^T`` within the chunk once per group, its
    masked product with ``dt x`` per head, the chunk's end state and the
    read-out of the state entering the chunk through C."""
    h, p, n, g = (c["mamba_n_heads"], c["mamba_d_head"], c["mamba_d_state"],
                  c["mamba_n_groups"])
    causal = (chunk + 1) / 2  # positions a query reads within its chunk, on average
    return 2 * (g * causal * n + h * causal * p + 2 * h * p * n)


def train_flops_per_token(c: dict, seq_len: int) -> float:
    """6 per matrix-product parameter, plus the attention layers' causal QK and
    PV and the Mamba2 layers' scans, forward once and backward twice.
    Recomputation is not work."""
    layers = _layers(c)
    attn = 2 * 2 * layers.count("attention") * c["num_attention_heads"] * c["head_dim"] \
        * (seq_len + 1) / 2
    return 6.0 * matmul_params(c) + 3 * (attn + layers.count("mamba") * ssd_flops_per_token(c))
