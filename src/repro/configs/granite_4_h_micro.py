"""granite-4.0-h-micro — Mamba2 layers interleaved 9:1 with NoPE GQA attention,
each layer followed by its own SwiGLU MLP, muP multipliers
[hf:ibm-granite/granite-4.0-h-micro].

Layer ``i`` is attention where ``i % 10 == 5`` (layers 5, 15, 25 and 35 of 40)
and a Mamba2 layer elsewhere: 64 heads of 64 (``d_inner`` 4096), state 128,
one group, conv 4 with bias. The tied embedding holds 100,352 rows.
"""
from .base import ArchConfig, register

GRANITE_4_H_MICRO = register(ArchConfig(
    name="granite-4.0-h-micro",
    family="interleaved",
    source="hf:ibm-granite/granite-4.0-h-micro",
    n_layers=40,
    d_model=2048,
    n_heads=32,
    n_kv_heads=8,
    head_dim=64,
    d_ff=8192,
    vocab=100352,
    max_seq=131072,
    use_rope=False,
    layer_pattern="MMMMMAMMMM",
    ssm_state=128,
    ssm_version=2,
    ssm_groups=1,
    d_inner_mult=2,
    conv_width=4,
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    attention_multiplier=1 / 64,
    logits_scaling=8.0,
    node_axes=("pod", "data"),
))

# The share one chip holds when the tied embedding and LM head are split by
# vocabulary rows over eight chips: an eighth of the rows, and the loss over
# them. The one-chip benchmark cell trains this share.
GRANITE_4_H_MICRO_VOCAB8 = register(GRANITE_4_H_MICRO.replace(
    name="granite-4.0-h-micro-vocab8", vocab=100352 // 8))
