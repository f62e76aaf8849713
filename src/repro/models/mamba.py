"""Mamba1 (S6) and Mamba2 blocks: full-sequence scans and a decode step.

Mamba1's naive selective scan materializes (batch, seq, d_inner, state) —
tens of GB at 7B scale — so the sequence is processed in chunks: an outer
`lax.scan` carries the (batch, d_inner, state) SSM state across chunks while
an inner `associative_scan` parallelizes within the chunk; each chunk body is
`jax.checkpoint`ed so backward recomputes instead of storing. This mirrors
the memory discipline of the CUDA kernel the paper's ecosystem uses, adapted
to XLA/TPU (and re-expressed as a Pallas kernel in kernels/scan/).

Mamba2's decay is one scalar per head, so its scan is computed by the
state-space duality (:func:`ssd`): per chunk, matrix products on the MXU, and
a sequential pass over the chunks' states alone.

Projections are kept as separate weights rather than one fused in_proj:
fused layouts would have to be split at boundaries that do not align with
"model"-axis shards, forcing GSPMD re-gathers. Separate weights shard
cleanly: d_inner over "model", the small B/C/dt heads replicated.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

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
# Mamba2 (granite-4.0-h, zamba2): scalar decay per head, chunked SSD
# ---------------------------------------------------------------------------

MAMBA2_HEAD_DIM = 64
SSD_CHUNK = 256  # the published chunk of both models


def init_mamba2(key: jax.Array, d_model: int, d_inner: int, d_state: int, n_groups: int,
                conv_width: int, dtype: Any, head_dim: int = MAMBA2_HEAD_DIM) -> Params:
    """The published Mamba2 mixer, its in_proj kept as three matrices: z,
    xBC (x, then each group's B, then each group's C) and dt. Head ``h``
    decays by ``A = -h - 1``; dt_bias spreads softplus(dt_bias) log-evenly
    over [1e-3, 1e-1] across the heads, the published range."""
    n_heads = d_inner // head_dim
    conv_dim = d_inner + 2 * n_groups * d_state
    kz, kx, kdt, kc, ko = jax.random.split(key, 5)
    dt = np.exp(np.linspace(np.log(1e-3), np.log(1e-1), n_heads))
    return {
        "wz": dense_init(kz, (d_model, d_inner), dtype),
        "wxBC": dense_init(kx, (d_model, conv_dim), dtype),
        "wdt": dense_init(kdt, (d_model, n_heads), dtype),
        "conv_w": dense_init(kc, (conv_width, conv_dim), dtype, scale=0.5),
        "conv_b": jnp.zeros((conv_dim,), jnp.float32),
        "A_log": jnp.asarray(np.log(np.arange(1, n_heads + 1)), jnp.float32),
        "dt_bias": jnp.asarray(dt + np.log(-np.expm1(-dt)), jnp.float32),  # softplus^-1(dt)
        "D": jnp.ones((n_heads,), jnp.float32),
        "norm": jnp.zeros((d_inner,), jnp.float32),
        "out_proj": dense_init(ko, (d_inner, d_model), dtype),
    }


def _mamba2_in(params: Params, u: jax.Array, n_groups: int, conv_cache=None):
    """Projections and conv of the normed input ``u`` (b, s, d): the gate z,
    the heads' x (b, s, H, P), each group's B and C (b, s, G, N) in ``u``'s
    dtype, the step dt (b, s, H) in f32, and the conv's new state."""
    b, s, _ = u.shape
    n_heads = params["A_log"].shape[0]
    d_inner = params["out_proj"].shape[0]
    z = jnp.einsum("bsd,dk->bsk", u, params["wz"])
    xBC = jnp.einsum("bsd,dk->bsk", u, params["wxBC"])
    dt = jax.nn.softplus(jnp.einsum("bsd,dh->bsh", u, params["wdt"]).astype(jnp.float32)
                         + params["dt_bias"])
    xBC, new_conv = _causal_conv(xBC.astype(jnp.float32),
                                 params["conv_w"].astype(jnp.float32), conv_cache)
    xBC = jax.nn.silu(xBC + params["conv_b"]).astype(u.dtype)
    gn = (xBC.shape[-1] - d_inner) // 2
    x = xBC[..., :d_inner].reshape(b, s, n_heads, d_inner // n_heads)
    Bm = xBC[..., d_inner:d_inner + gn].reshape(b, s, n_groups, gn // n_groups)
    Cm = xBC[..., d_inner + gn:].reshape(b, s, n_groups, gn // n_groups)
    return z, x, Bm, Cm, dt, new_conv


def _mamba2_out(params: Params, y: jax.Array, z: jax.Array, n_groups: int,
                eps: float = 1e-6) -> jax.Array:
    """out_proj of RMSNorm(y * silu(z)), normed over each group's part of
    d_inner. y: (b, s, d_inner) f32."""
    g = y * jax.nn.silu(z.astype(jnp.float32))
    gs = g.reshape(*g.shape[:-1], n_groups, -1)
    gs = gs * jax.lax.rsqrt(jnp.mean(jnp.square(gs), axis=-1, keepdims=True) + eps)
    g = gs.reshape(g.shape) * (1.0 + params["norm"])
    return jnp.einsum("bsk,kd->bsd", g.astype(z.dtype), params["out_proj"])


@scopes.scoped(scopes.SSM_SSD)
def ssd(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array, C: jax.Array,
        chunk: int = SSD_CHUNK) -> Tuple[jax.Array, jax.Array]:
    """The scan ``h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T``, ``y_t = h_t C_t``
    from a zero state, by the state-space duality over chunks of ``chunk``.

    x: (b, s, H, P); dt: (b, s, H) f32; A: (H,) f32; B, C: (b, s, G, N),
    head ``h`` reading group ``h // (H / G)``. Per chunk the output is the
    masked product ``(L o C B^T) (dt x)`` with ``L_ij = exp(sum_{k=j+1..i}
    dt_k A)``, plus the state entering the chunk read through C and decayed;
    the chunks' end states are products too, and only they pass through a
    sequential recurrence, over ``s / chunk`` steps. Decays and their sums are
    f32; the products take ``x``'s dtype with f32 accumulation. A short last
    chunk is padded with ``dt = 0``, which neither decays nor adds. Returns y
    (b, s, H, P) f32 and the last state (b, H, P, N) f32.
    """
    f32 = jnp.float32
    b, s, H, P = x.shape
    G, N = B.shape[-2:]
    hg, L = H // G, min(chunk, s)
    pad = -s % L
    if pad:
        x, dt, B, C = (jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))
                       for t in (x, dt, B, C))
    nc = (s + pad) // L
    mm = x.dtype
    xdt = (x.astype(f32) * dt[..., None]).reshape(b, nc, L, G, hg, P)
    Bc, Cc = B.reshape(b, nc, L, G, N), C.reshape(b, nc, L, G, N)
    # cumulative log-decay within each chunk, heads before positions
    acum = jnp.cumsum((dt * A).reshape(b, nc, L, G, hg).transpose(0, 1, 3, 4, 2), axis=-1)

    # within the chunk: (L o C B^T) (dt x)
    seg = acum[..., :, None] - acum[..., None, :]  # (b, c, G, hg, L_i, L_j)
    causal = jnp.tril(jnp.ones((L, L), bool))
    cb = jnp.einsum("bcign,bcjgn->bcgij", Cc, Bc, preferred_element_type=f32)
    m = (jnp.exp(jnp.where(causal, seg, -jnp.inf)) * cb[:, :, :, None]).astype(mm)
    y = jnp.einsum("bcghij,bcjghp->bcighp", m, xdt.astype(mm), preferred_element_type=f32)

    # each chunk's end state, from a zero state at its start
    to_end = jnp.exp(acum[..., -1:] - acum).transpose(0, 1, 4, 2, 3)  # (b, c, L, G, hg)
    states = jnp.einsum("bcjgn,bcjghp->bcghpn", Bc, (xdt * to_end[..., None]).astype(mm),
                        preferred_element_type=f32)

    # the states entering each chunk: a recurrence over the chunks
    def carry(h, inputs):
        state, decay = inputs
        return decay[..., None, None] * h + state, h

    h_last, h_in = jax.lax.scan(carry, jnp.zeros((b, G, hg, P, N), f32),
                                (states.swapaxes(0, 1), jnp.exp(acum[..., -1]).swapaxes(0, 1)))
    # ... read at each position through C, decayed from the chunk's start
    y_in = jnp.einsum("bcign,cbghpn->bcighp", Cc, h_in.astype(mm), preferred_element_type=f32)
    y = y + y_in * jnp.exp(acum).transpose(0, 1, 4, 2, 3)[..., None]
    return y.reshape(b, nc * L, H, P)[:, :s], h_last.reshape(b, H, P, N)


@scopes.scoped(scopes.SSM)
def mamba2_forward(params: Params, u: jax.Array, n_groups: int) -> jax.Array:
    """The Mamba2 mixer over a whole sequence of normed inputs u (b, s, d)."""
    b, s, _ = u.shape
    z, x, Bm, Cm, dt, _ = _mamba2_in(params, u, n_groups)
    y, _ = ssd(x, dt, -jnp.exp(params["A_log"]), Bm, Cm)
    y = y + params["D"][:, None] * x.astype(jnp.float32)
    return _mamba2_out(params, y.reshape(b, s, -1), z, n_groups)


def init_mamba2_cache(batch: int, d_inner: int, d_state: int, n_groups: int,
                      conv_width: int, dtype: Any,
                      head_dim: int = MAMBA2_HEAD_DIM) -> Dict[str, jax.Array]:
    return {
        "conv": jnp.zeros((batch, conv_width - 1, d_inner + 2 * n_groups * d_state), dtype),
        "ssm": jnp.zeros((batch, d_inner // head_dim, head_dim, d_state), jnp.float32),
    }


@scopes.scoped(scopes.SSM)
def mamba2_decode(params: Params, u: jax.Array, cache: Dict[str, jax.Array],
                  n_groups: int) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One token of the Mamba2 mixer, u (b, 1, d), one recurrence step."""
    b = u.shape[0]
    z, x, Bm, Cm, dt, new_conv = _mamba2_in(params, u, n_groups, cache["conv"])
    _, _, H, P = x.shape
    N = Bm.shape[-1]
    x = x[:, 0].astype(jnp.float32).reshape(b, n_groups, H // n_groups, P)
    decay = jnp.exp(dt[:, 0] * -jnp.exp(params["A_log"])).reshape(b, n_groups, -1)
    h = cache["ssm"].reshape(b, n_groups, H // n_groups, P, N)
    h = (decay[..., None, None] * h + (dt[:, 0].reshape(decay.shape)[..., None] * x)[..., None]
         * Bm[:, 0, :, None, None, :].astype(jnp.float32))
    y = jnp.einsum("bghpn,bgn->bghp", h, Cm[:, 0].astype(jnp.float32))
    y = (y + params["D"].reshape(n_groups, -1, 1) * x).reshape(b, 1, H * P)
    out = _mamba2_out(params, y, z, n_groups)
    return out, {"conv": new_conv.astype(cache["conv"].dtype), "ssm": h.reshape(b, H, P, N)}
