"""Mamba1 (S6) and Mamba2-style blocks: chunked selective scan + decode step.

The naive selective scan materializes (batch, seq, d_inner, state) — tens of
GB at 7B scale — so the sequence is processed in chunks: an outer `lax.scan`
carries the (batch, d_inner, state) SSM state across chunks while an inner
`associative_scan` parallelizes within the chunk; each chunk body is
`jax.checkpoint`ed so backward recomputes instead of storing. This mirrors
the memory discipline of the CUDA kernel the paper's ecosystem uses, adapted
to XLA/TPU (and re-expressed as a Pallas kernel in kernels/scan/).

Projections are kept as separate weights (wz/wx/wB/wC/wdt) rather than one
fused in_proj: fused layouts would have to be split at boundaries that do not
align with "model"-axis shards, forcing GSPMD re-gathers. Separate weights
shard cleanly: d_inner over "model", the small B/C/dt heads replicated.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from ..obs import scopes
from .layers import Params, dense_init, shard_hint

# ---------------------------------------------------------------------------
# generic chunked linear-recurrence scan: h_t = a_t * h_{t-1} + b_t
# ---------------------------------------------------------------------------


def _assoc_combine(left, right):
    a1, b1 = left
    a2, b2 = right
    return a2 * a1, a2 * b1 + b2


def _to_chunks(x: jax.Array, n_chunks: int, chunk: int) -> jax.Array:
    B, S = x.shape[0], x.shape[1]
    return x.reshape(B, n_chunks, chunk, *x.shape[2:]).swapaxes(0, 1)


def chunked_selective_scan(
    inputs: Any,
    make_ab: Any,
    h0: jax.Array,
    chunk: int,
    emit: Any,
    sequential: bool = False,
):
    """Memory-disciplined linear-recurrence scan.

    ``inputs`` is a pytree of (B, S, ...) tensors; per chunk, ``make_ab``
    builds the recurrence terms (a, b) — a broadcastable to b — so the big
    (B, S, inner, state) tensors are only ever materialized chunk-sized.
    ``emit(h_all_chunk, chunk_inputs)`` maps chunk states to the per-step
    output. Returns (y (B, S, ...), h_last).
    """
    leaves = jax.tree.leaves(inputs)
    B, S = leaves[0].shape[0], leaves[0].shape[1]
    chunk = min(chunk, S)
    while S % chunk:
        chunk -= 1
    n_chunks = S // chunk
    xs = jax.tree.map(lambda t: _to_chunks(t, n_chunks, chunk), inputs)

    @jax.checkpoint
    def body(h, chunk_inputs):
        a, b = make_ab(chunk_inputs)  # a broadcastable to b: (B, chunk, ...)
        if sequential:
            # kernel-style: O(1) live state, no log-depth level buffers.
            # This is the HBM-traffic profile of kernels/scan/mamba_scan.py;
            # the associative form trades ~2·log2(chunk) extra full-chunk
            # buffers of HBM traffic for parallel depth.
            def step(hc, ab_t):
                a_t, b_t = ab_t
                hc = a_t * hc + b_t
                return hc, hc

            a = jnp.broadcast_to(a, b.shape)
            h_last, h_seq = jax.lax.scan(
                step, h, (a.swapaxes(0, 1), b.swapaxes(0, 1)))
            h_all = h_seq.swapaxes(0, 1)
        else:
            a = jnp.broadcast_to(a, b.shape)
            aa, bb = jax.lax.associative_scan(_assoc_combine, (a, b), axis=1)
            h_all = aa * h[:, None] + bb  # inject carry
        y = emit(h_all, chunk_inputs)
        return h_all[:, -1], y

    h_last, y_chunks = jax.lax.scan(body, h0, xs)
    y = y_chunks.swapaxes(0, 1).reshape(B, S, *y_chunks.shape[3:])
    return y, h_last


def chunked_linear_scan(a: jax.Array, b: jax.Array, h0: jax.Array, chunk: int):
    """Scan h_t = a_t*h_{t-1} + b_t along axis 1 (seq). Returns (h_all, h_last).

    Thin wrapper over :func:`chunked_selective_scan` for pre-built (a, b).
    """
    h_all, h_last = chunked_selective_scan(
        (a, b),
        make_ab=lambda ab: ab,
        h0=h0,
        chunk=chunk,
        emit=lambda h, _: h,
    )
    return h_all, h_last


def pick_chunk(batch: int, inner_elems: int, budget_bytes: int = 256 << 20) -> int:
    """Largest power-of-two chunk whose f32 scan intermediates fit the budget."""
    c = 256
    while c > 8 and batch * c * inner_elems * 4 * 2 > budget_bytes:
        c //= 2
    return c


# ---------------------------------------------------------------------------
# Mamba1 (falcon-mamba)
# ---------------------------------------------------------------------------


def init_mamba1(key: jax.Array, d_model: int, d_inner: int, d_state: int,
                dt_rank: int, conv_width: int, dtype: Any) -> Params:
    ks = jax.random.split(key, 8)
    return {
        "wx": dense_init(ks[0], (d_model, d_inner), dtype),
        "wz": dense_init(ks[1], (d_model, d_inner), dtype),
        "conv_w": dense_init(ks[2], (conv_width, d_inner), dtype, scale=0.5),
        "wdt_in": dense_init(ks[3], (d_inner, dt_rank), dtype),
        "wB": dense_init(ks[4], (d_inner, d_state), dtype),
        "wC": dense_init(ks[5], (d_inner, d_state), dtype),
        "dt_proj": dense_init(ks[6], (dt_rank, d_inner), dtype),
        "dt_bias": jnp.zeros((d_inner,), jnp.float32),
        "A_log": jnp.log(jnp.broadcast_to(jnp.arange(1, d_state + 1, dtype=jnp.float32),
                                          (d_inner, d_state))),
        "D": jnp.ones((d_inner,), jnp.float32),
        "out_proj": dense_init(ks[7], (d_inner, d_model), dtype),
    }


def _causal_conv(x: jax.Array, w: jax.Array, cache: jax.Array = None):
    """Depthwise causal conv along seq. x: (b, s, di); w: (width, di)."""
    width = w.shape[0]
    if cache is None:
        pad = jnp.zeros((x.shape[0], width - 1, x.shape[2]), x.dtype)
    else:
        pad = cache.astype(x.dtype)
    xp = jnp.concatenate([pad, x], axis=1)  # (b, s+w-1, di)
    out = sum(xp[:, i : i + x.shape[1], :] * w[i] for i in range(width))
    new_cache = xp[:, -(width - 1):, :] if width > 1 else xp[:, :0, :]
    return out, new_cache


def _mamba1_ssm_inputs(params: Params, xc: jax.Array):
    """Pre-scan tensors (all (b, s, ·) — the big (·, di, n) terms are built
    per-chunk inside the scan). xc: (b, s, di) post-conv activations."""
    dt_low = jnp.einsum("bsd,dr->bsr", xc, params["wdt_in"]).astype(jnp.float32)
    dt = jax.nn.softplus(
        jnp.einsum("bsr,rd->bsd", dt_low, params["dt_proj"].astype(jnp.float32))
        + params["dt_bias"]
    )  # (b, s, di)
    Bm = jnp.einsum("bsd,dn->bsn", xc, params["wB"]).astype(jnp.float32)
    Cm = jnp.einsum("bsd,dn->bsn", xc, params["wC"]).astype(jnp.float32)
    return dt, Bm, Cm


@scopes.scoped(scopes.SSM)
def mamba1_forward(params: Params, x: jax.Array, d_state: int, dt_rank: int,
                   chunk: int = 64, sequential: bool = False) -> jax.Array:
    """Full-sequence Mamba1 block. x: (b, s, d_model)."""
    di = params["out_proj"].shape[0]
    xi = jnp.einsum("bsd,dk->bsk", x, params["wx"])
    z = jnp.einsum("bsd,dk->bsk", x, params["wz"])
    xc, _ = _causal_conv(xi, params["conv_w"])
    xc = shard_hint(jax.nn.silu(xc), "batch", None, "model")
    dt, Bm, Cm = _mamba1_ssm_inputs(params, xc)
    dt = shard_hint(dt, "batch", None, "model")
    A = -jnp.exp(params["A_log"])  # (di, n)

    def make_ab(ci):
        dt_c, B_c, _, x_c = ci  # (b, c, di), (b, c, n), ·, (b, c, di)
        dA = jnp.exp(dt_c[..., None] * A)  # (b, c, di, n)
        dBx = (dt_c * x_c.astype(jnp.float32))[..., None] * B_c[..., None, :]
        return shard_hint(dA, "batch", None, "model", None), \
            shard_hint(dBx, "batch", None, "model", None)

    def emit(h_all, ci):
        _, _, C_c, _ = ci
        return shard_hint(jnp.einsum("bsdn,bsn->bsd", h_all, C_c),
                          "batch", None, "model")

    h0 = shard_hint(jnp.zeros((x.shape[0], di, d_state), jnp.float32),
                    "batch", "model", None)
    y, _ = chunked_selective_scan((dt, Bm, Cm, xc), make_ab, h0, chunk, emit,
                                  sequential=sequential)
    y = y + params["D"] * xc.astype(jnp.float32)
    y = (y * jax.nn.silu(z.astype(jnp.float32))).astype(x.dtype)
    return jnp.einsum("bsd,dk->bsk", y, params["out_proj"])


def init_mamba1_cache(batch: int, d_inner: int, d_state: int, conv_width: int,
                      dtype: Any) -> Dict[str, jax.Array]:
    return {
        "conv": jnp.zeros((batch, conv_width - 1, d_inner), dtype),
        "ssm": jnp.zeros((batch, d_inner, d_state), jnp.float32),
    }


@scopes.scoped(scopes.SSM)
def mamba1_decode(params: Params, x: jax.Array, cache: Dict[str, jax.Array],
                  d_state: int, dt_rank: int) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One-token step. x: (b, 1, d_model)."""
    xi = jnp.einsum("bsd,dk->bsk", x, params["wx"])
    z = jnp.einsum("bsd,dk->bsk", x, params["wz"])
    xc, new_conv = _causal_conv(xi, params["conv_w"], cache["conv"])
    xc = jax.nn.silu(xc)
    dt, Bm, Cm = _mamba1_ssm_inputs(params, xc)
    A = -jnp.exp(params["A_log"])  # (di, n)
    dA = jnp.exp(dt[..., None] * A)  # (b, 1, di, n)
    dBx = (dt * xc.astype(jnp.float32))[..., None] * Bm[..., None, :]
    h = dA[:, 0] * cache["ssm"] + dBx[:, 0]  # (b, di, n)
    y = jnp.einsum("bdn,bn->bd", h, Cm[:, 0])[:, None, :]
    y = y + params["D"] * xc.astype(jnp.float32)
    y = (y * jax.nn.silu(z.astype(jnp.float32))).astype(x.dtype)
    out = jnp.einsum("bsd,dk->bsk", y, params["out_proj"])
    return out, {"conv": new_conv.astype(cache["conv"].dtype), "ssm": h}


# ---------------------------------------------------------------------------
# Mamba2-style block (zamba2): scalar decay per head, SSD-lite
# ---------------------------------------------------------------------------


def init_mamba2(key: jax.Array, d_model: int, d_inner: int, d_state: int,
                conv_width: int, dtype: Any, head_dim: int = 64) -> Params:
    ks = jax.random.split(key, 6)
    n_heads = d_inner // head_dim
    return {
        "wx": dense_init(ks[0], (d_model, d_inner), dtype),
        "wz": dense_init(ks[1], (d_model, d_inner), dtype),
        "wB": dense_init(ks[2], (d_model, d_state), dtype),
        "wC": dense_init(ks[3], (d_model, d_state), dtype),
        "wdt": dense_init(ks[4], (d_model, n_heads), dtype),
        "conv_w": dense_init(ks[5], (conv_width, d_inner), dtype, scale=0.5),
        "A_log": jnp.zeros((n_heads,), jnp.float32),
        "dt_bias": jnp.zeros((n_heads,), jnp.float32),
        "D": jnp.ones((n_heads,), jnp.float32),
        "out_proj": dense_init(jax.random.fold_in(key, 7), (d_inner, d_model), dtype),
    }


def _mamba2_inputs(params: Params, x: jax.Array, conv_cache=None):
    xi = jnp.einsum("bsd,dk->bsk", x, params["wx"])
    xc, new_conv = _causal_conv(xi, params["conv_w"], conv_cache)
    xc = jax.nn.silu(xc)
    z = jnp.einsum("bsd,dk->bsk", x, params["wz"])
    Bm = jnp.einsum("bsd,dn->bsn", x, params["wB"]).astype(jnp.float32)
    Cm = jnp.einsum("bsd,dn->bsn", x, params["wC"]).astype(jnp.float32)
    dt = jax.nn.softplus(
        jnp.einsum("bsd,dh->bsh", x, params["wdt"]).astype(jnp.float32) + params["dt_bias"]
    )  # (b, s, h)
    return xc, z, Bm, Cm, dt, new_conv


@scopes.scoped(scopes.SSM)
def mamba2_forward(params: Params, x: jax.Array, d_state: int, head_dim: int = 64,
                   chunk: int = 16, sequential: bool = False) -> jax.Array:
    b, s, _ = x.shape
    d_inner = params["out_proj"].shape[0]
    n_heads = d_inner // head_dim
    xc, z, Bm, Cm, dt, _ = _mamba2_inputs(params, x)
    xc = shard_hint(xc, "batch", None, "model")
    A = -jnp.exp(params["A_log"])  # (h,)

    def make_ab(ci):
        x_c, B_c, _, dt_c = ci  # (b,c,di), (b,c,n), ·, (b,c,h)
        dA = jnp.exp(dt_c * A)[..., None, None]  # (b, c, h, 1, 1)
        xh = x_c.reshape(*x_c.shape[:2], n_heads, head_dim).astype(jnp.float32)
        xh = shard_hint(xh, "batch", None, "model", None)
        dBx = (dt_c[..., None] * xh)[..., None] * B_c[:, :, None, None, :]
        return shard_hint(dA, "batch", None, "model", None, None), \
            shard_hint(dBx, "batch", None, "model", None, None)

    def emit(h_all, ci):
        x_c, _, C_c, _ = ci
        xh = x_c.reshape(*x_c.shape[:2], n_heads, head_dim).astype(jnp.float32)
        xh = shard_hint(xh, "batch", None, "model", None)
        y = jnp.einsum("bshdn,bsn->bshd", h_all, C_c)
        y = y + params["D"][:, None] * xh
        return shard_hint(y.reshape(*x_c.shape[:2], d_inner), "batch", None, "model")

    h0 = shard_hint(jnp.zeros((b, n_heads, head_dim, d_state), jnp.float32),
                    "batch", "model", None, None)
    y, _ = chunked_selective_scan((xc, Bm, Cm, dt), make_ab, h0, chunk, emit,
                                  sequential=sequential)
    y = (y * jax.nn.silu(z.astype(jnp.float32))).astype(x.dtype)
    return jnp.einsum("bsd,dk->bsk", y, params["out_proj"])


def init_mamba2_cache(batch: int, d_inner: int, d_state: int, conv_width: int,
                      dtype: Any, head_dim: int = 64) -> Dict[str, jax.Array]:
    n_heads = d_inner // head_dim
    return {
        "conv": jnp.zeros((batch, conv_width - 1, d_inner), dtype),
        "ssm": jnp.zeros((batch, n_heads, head_dim, d_state), jnp.float32),
    }


@scopes.scoped(scopes.SSM)
def mamba2_decode(params: Params, x: jax.Array, cache: Dict[str, jax.Array],
                  d_state: int, head_dim: int = 64) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    b = x.shape[0]
    d_inner = params["out_proj"].shape[0]
    n_heads = d_inner // head_dim
    xc, z, Bm, Cm, dt, new_conv = _mamba2_inputs(params, x, cache["conv"])
    A = -jnp.exp(params["A_log"])
    dA = jnp.exp(dt[:, 0] * A)  # (b, h)
    xh = xc[:, 0].reshape(b, n_heads, head_dim).astype(jnp.float32)
    dBx = (dt[:, 0, :, None] * xh)[..., None] * Bm[:, 0][:, None, None, :]
    h = dA[..., None, None] * cache["ssm"] + dBx
    y = jnp.einsum("bhdn,bn->bhd", h, Cm[:, 0])
    y = y + params["D"][:, None] * xh
    y = y.reshape(b, 1, d_inner)
    y = (y * jax.nn.silu(z.astype(jnp.float32))).astype(x.dtype)
    out = jnp.einsum("bsd,dk->bsk", y, params["out_proj"])
    return out, {"conv": new_conv.astype(cache["conv"].dtype), "ssm": h}
