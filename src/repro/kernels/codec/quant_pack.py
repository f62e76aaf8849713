"""Pallas quantize/dequantize kernels for the gossip payload codecs.

The wire format (:mod:`repro.compress`) is symmetric uniform quantization
with one float32 absmax scale per ``chunk`` consecutive elements. Both
directions are bandwidth-bound element-wise passes, so each grid program
streams a ``(block_c, chunk)`` tile of chunk-rows through VMEM and emits the
codes and scales in one read of the input: HBM traffic is exactly
input + output, with the absmax reduction and the scale divide fused.

Scales travel through the kernels as a ``(C, 1)`` column: Mosaic takes a 2-D
``(block_c, 1)`` block (the last dim equals the array's), while a rank-1
``(block_c,)`` block must be the whole array or a multiple of 128.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _quant_kernel(x_ref, q_ref, s_ref, *, qmax: float):
    x = x_ref[...].astype(jnp.float32)  # (block_c, chunk)
    absmax = jnp.max(jnp.abs(x), axis=1, keepdims=True)
    scale = jnp.where(absmax > 0, absmax / qmax, 1.0)  # (block_c, 1)
    q = jnp.clip(jnp.round(x / scale), -qmax, qmax)
    q_ref[...] = q.astype(jnp.int8)
    s_ref[...] = scale


def _dequant_kernel(q_ref, s_ref, o_ref):
    q = q_ref[...].astype(jnp.float32)  # (block_c, chunk)
    o_ref[...] = q * s_ref[...].astype(jnp.float32)  # scales (block_c, 1)


def _pad_rows(a: jax.Array, block_c: int) -> jax.Array:
    pad = (-a.shape[0]) % block_c
    if pad:
        a = jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
    return a


def quantize_chunks(
    x: jax.Array,  # (C, chunk) chunk-rows of consecutive flat elements
    *,
    qmax: float,
    block_c: int = 8,
    interpret: bool = False,
):
    """Per-row absmax quantization: returns (codes int8 (C, chunk), scales f32 (C,))."""
    c, chunk = x.shape
    block_c = min(block_c, c)
    xp = _pad_rows(x, block_c)
    cp = xp.shape[0]
    codes, scales = pl.pallas_call(
        functools.partial(_quant_kernel, qmax=qmax),
        grid=(cp // block_c,),
        in_specs=[pl.BlockSpec((block_c, chunk), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((block_c, chunk), lambda i: (i, 0)),
            pl.BlockSpec((block_c, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((cp, chunk), jnp.int8),
            jax.ShapeDtypeStruct((cp, 1), jnp.float32),
        ],
        interpret=interpret,
    )(xp)
    return codes[:c], scales[:c, 0]


def dequantize_chunks(
    codes: jax.Array,  # (C, chunk) int8
    scales: jax.Array,  # (C,) f32
    *,
    block_c: int = 8,
    interpret: bool = False,
) -> jax.Array:
    c, chunk = codes.shape
    block_c = min(block_c, c)
    qp, sp = _pad_rows(codes, block_c), _pad_rows(scales[:, None], block_c)
    cp = qp.shape[0]
    out = pl.pallas_call(
        _dequant_kernel,
        grid=(cp // block_c,),
        in_specs=[
            pl.BlockSpec((block_c, chunk), lambda i: (i, 0)),
            pl.BlockSpec((block_c, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_c, chunk), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((cp, chunk), jnp.float32),
        interpret=interpret,
    )(qp, sp)
    return out[:c]
