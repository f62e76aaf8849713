"""Operation and byte counts the metrics divide by, from shapes and HLO.

Kept with the benchmark so that the work a metric credits cannot change with
the program under test.
"""
from __future__ import annotations

import re

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1, "s16": 2, "u16": 2,
    "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
    "f64": 8, "c64": 8, "c128": 16,
}
_SHAPE = re.compile(r"(\w+)\[([\d,]*)\]")
# "%cp = f32[8,128]{1,0} collective-permute(%x), ..., source_target_pairs={{0,1},{1,0}}",
# or the async "-start" form, whose result is a tuple led by the operand's shape
_PERMUTE = re.compile(r"=\s*(.*?)\s+collective-permute(?:-start)?\(")
_PAIRS = re.compile(r"source_target_pairs=\{((?:\{\d+,\d+\},?)*)\}")


def matmul_params(c: dict) -> int:
    """Parameters of a dense GQA decoder that enter a matrix product: the
    projections and SwiGLU MLP of every layer, and the tied LM head."""
    d, hd = c["hidden_size"], c["head_dim"]
    per_layer = (d * c["num_attention_heads"] * hd * 2  # q, o
                 + d * c["num_key_value_heads"] * hd * 2  # k, v
                 + 3 * d * c["intermediate_size"])  # gate, up, down
    return c["num_hidden_layers"] * per_layer + c["vocab_size"] * d


def dense_train_flops_per_token(c: dict, seq_len: int) -> float:
    """Model FLOPs of one training token: 6 per matrix-product parameter, plus
    causal attention's QK and PV (query i attends to i + 1 keys, 2 FLOPs per
    multiply-add, forward once and backward twice). Recomputation is not work."""
    attn = 3 * 2 * 2 * c["num_hidden_layers"] * c["num_attention_heads"] * c["head_dim"] \
        * (seq_len + 1) / 2
    return 6.0 * matmul_params(c) + attn


def dissemination_bytes_per_node(n_nodes: int, tree_bytes: int) -> int:
    """Bytes each node must receive in one dissemination round: the other
    ``n - 1`` nodes' trees."""
    return (n_nodes - 1) * tree_bytes


def shape_bytes(type_str: str) -> int:
    total = 0
    for dtype, dims in _SHAPE.findall(type_str):
        n = 1
        for x in filter(None, dims.split(",")):
            n *= int(x)
        total += n * _DTYPE_BYTES[dtype]
    return total


def collective_permute_bytes(hlo_text: str) -> int:
    """Bytes sent by all devices in one run of a compiled SPMD module: for every
    collective-permute, its operand's bytes times its source-target pairs.
    The module must hold no loop around its permutes."""
    total = 0
    for line in hlo_text.splitlines():
        m = _PERMUTE.search(line)
        if m is None:
            continue
        pairs = _PAIRS.search(line)
        if pairs is None:
            raise ValueError(f"a collective-permute without source_target_pairs: {line[:200]}")
        operand = _SHAPE.search(m.group(1)).group(0)
        total += shape_bytes(operand) * len(re.findall(r"\{\d+,\d+\}", pairs.group(1)))
    return total
