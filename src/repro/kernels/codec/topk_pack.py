"""Pallas block-local top-k select + pack kernel.

The top-k wire format keeps the ``k`` largest-magnitude entries of every
``block`` consecutive elements, packed as (value, index) pairs. Block-local
selection is what keeps every shape static — a hard requirement both for
``pallas_call`` and for ppermuting the packed buffers through the compiled
gossip collectives.

Each grid program owns a ``(block_c, block)`` tile of block-rows and runs two
fused O(k·block) vector phases with no HBM round-trips in between:

1. **select** — k iterations of masked argmax (first-maximum semantics, so
   ties go to the lower index, matching ``lax.top_k`` in the oracle): the
   first maximum is the smallest column index where the row max is reached;
2. **pack** — k iterations that extract the smallest still-unpacked selected
   column, so the pairs come out in ascending index order; the value is
   taken with a where-reduction (no gather/scatter inside the kernel).

Both phases use only lane reductions (max, min, sum) and selects, which
Mosaic lowers; ``cumsum`` has no TPU lowering.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl


def _topk_kernel(x_ref, v_ref, i_ref, *, k: int):
    x = x_ref[...].astype(jnp.float32)  # (block_c, block)
    block = x.shape[1]
    mag = jnp.abs(x)
    cols = lax.broadcasted_iota(jnp.int32, x.shape, 1)

    def first_col(mask):  # (block_c, 1): lowest column where mask holds
        return jnp.min(jnp.where(mask, cols, block), axis=1, keepdims=True)

    # phase 1: k rounds of "first position achieving the row max"
    sel = jnp.zeros(x.shape, jnp.bool_)
    for _ in range(k):
        first = cols == first_col(mag == jnp.max(mag, axis=1, keepdims=True))
        sel = sel | first
        mag = jnp.where(first, -1.0, mag)
    # phase 2: pack in ascending index order (next-smallest selected column)
    slot = lax.broadcasted_iota(jnp.int32, (x.shape[0], k), 1)
    vals = jnp.zeros((x.shape[0], k), jnp.float32)
    idx = jnp.zeros((x.shape[0], k), jnp.int32)
    for j in range(k):
        col = first_col(sel)
        hit = cols == col
        v = jnp.sum(jnp.where(hit, x, 0.0), axis=1, keepdims=True)
        vals = jnp.where(slot == j, v, vals)
        idx = jnp.where(slot == j, col, idx)
        sel = sel & ~hit
    v_ref[...] = vals
    i_ref[...] = idx


def topk_select_blocks(
    x: jax.Array,  # (C, block) block-rows of consecutive flat elements
    *,
    k: int,
    block_c: int = 8,
    interpret: bool = False,
):
    """Per-row top-k by |value|: (values f32 (C, k), indices i32 (C, k))."""
    c, block = x.shape
    if not (1 <= k <= block):
        raise ValueError(f"need 1 <= k <= block, got k={k}, block={block}")
    block_c = min(block_c, c)
    pad = (-c) % block_c
    xp = jnp.pad(x, ((0, pad), (0, 0))) if pad else x
    cp = xp.shape[0]
    vals, idx = pl.pallas_call(
        functools.partial(_topk_kernel, k=k),
        grid=(cp // block_c,),
        in_specs=[pl.BlockSpec((block_c, block), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((block_c, k), lambda i: (i, 0)),
            pl.BlockSpec((block_c, k), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((cp, k), jnp.float32),
            jax.ShapeDtypeStruct((cp, k), jnp.int32),
        ],
        interpret=interpret,
    )(xp)
    return vals[:c], idx[:c]
