"""GQA attention: full / sliding-window / softcapped, train + cached decode.

Long sequences use a query-block scan so the score matrix is never
materialized at (seq × seq): per block the footprint is (block × seq), which
keeps 32k-prefill lowering memory-sane. Decode attends one token against the
(possibly ring-buffered) KV cache; with a sequence-sharded cache the softmax
reductions become GSPMD collectives automatically.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..obs import scopes
from .layers import Params, apply_rope, dense_init, shard_hint

Q_BLOCK = 256  # query-block size for chunked attention
NEG_INF = -2.0e38


def init_attention(
    key: jax.Array, d_model: int, n_heads: int, n_kv_heads: int, head_dim: int, dtype: Any
) -> Params:
    kq, kk, kv, ko = jax.random.split(key, 4)
    return {
        "wq": dense_init(kq, (d_model, n_heads, head_dim), dtype),
        "wk": dense_init(kk, (d_model, n_kv_heads, head_dim), dtype),
        "wv": dense_init(kv, (d_model, n_kv_heads, head_dim), dtype),
        "wo": dense_init(ko, (n_heads, head_dim, d_model), dtype),
    }


def _expand_kv(k: jax.Array, n_heads: int) -> jax.Array:
    """(b, s, kv, hd) -> (b, s, H, hd) by repeating groups."""
    n_kv = k.shape[-2]
    if n_kv == n_heads:
        return k
    return jnp.repeat(k, n_heads // n_kv, axis=-2)


def _softcap(scores: jax.Array, cap: float) -> jax.Array:
    if cap > 0:
        return cap * jnp.tanh(scores / cap)
    return scores


def _attend_block(
    q: jax.Array,  # (b, qb, H, hd)
    k: jax.Array,  # (b, s, H, hd)
    v: jax.Array,  # (b, s, H, hd)
    mask: jax.Array,  # (b, qb, s) or (1, qb, s) boolean
    softcap: float,
) -> jax.Array:
    scale = q.shape[-1] ** -0.5
    scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)) * scale
    # heads over "model" when divisible (Megatron TP); otherwise attention is
    # replicated within the node (scores keep whatever q/k/v carry)
    if _divides(scores.shape[1]):
        scores = shard_hint(scores, "batch", "model", None, None)
    scores = _softcap(scores, softcap)
    scores = jnp.where(mask[:, None, :, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v.astype(jnp.float32)).astype(q.dtype)


def _divides(n_heads: int) -> bool:
    from .layers import get_mesh_ctx

    mesh, _ = get_mesh_ctx()
    return bool(mesh is not None and "model" in mesh.shape
                and n_heads % mesh.shape["model"] == 0)


@scopes.scoped(scopes.ATTENTION)
def attention(
    params: Params,
    x: jax.Array,  # (b, s, d)
    positions: jax.Array,  # (b, s)
    *,
    causal: bool = True,
    sliding_window: int = 0,
    softcap: float = 0.0,
    rope_theta: float = 10_000.0,
    use_rope: bool = True,
    kv_override: Optional[Tuple[jax.Array, jax.Array]] = None,  # cross-attention
    kv_positions: Optional[jax.Array] = None,
    prefix_len: int = 0,  # vlm: first `prefix_len` positions attend bidirectionally
) -> jax.Array:
    """Full-sequence attention (train / prefill / encoder / cross)."""
    b, s, _ = x.shape
    n_heads = params["wq"].shape[1]
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    if kv_override is None:
        k = jnp.einsum("bsd,dhk->bshk", x, params["wk"])
        v = jnp.einsum("bsd,dhk->bshk", x, params["wv"])
        kv_pos = positions
    else:
        k, v = kv_override
        kv_pos = kv_positions
    if use_rope:
        q = apply_rope(q, positions, rope_theta)
        if kv_override is None:
            k = apply_rope(k, kv_pos, rope_theta)
    k = _expand_kv(k, n_heads)
    v = _expand_kv(v, n_heads)
    # Resolve seq-parallel -> attention sharding ONCE per layer on q/k/v
    # (gathering inside the q-block scan repeats the transfer nb times).
    h_ax = "model" if _divides(n_heads) else None
    q = shard_hint(q, "batch", None, h_ax, None)
    k = shard_hint(k, "batch", None, h_ax, None)
    v = shard_hint(v, "batch", None, h_ax, None)

    s_kv = k.shape[1]

    def mask_for(qpos: jax.Array) -> jax.Array:  # (b, qb) -> (b, qb, s_kv)
        if kv_pos is None:
            return jnp.ones((qpos.shape[0], qpos.shape[1], s_kv), bool)
        m = jnp.ones((qpos.shape[0], qpos.shape[1], s_kv), bool)
        if causal:
            c = kv_pos[:, None, :] <= qpos[:, :, None]
            if prefix_len > 0:  # paligemma: prefix tokens are mutually visible
                c = c | (kv_pos[:, None, :] < prefix_len)
            m = m & c
        if sliding_window > 0:
            w = kv_pos[:, None, :] > qpos[:, :, None] - sliding_window
            if prefix_len > 0:
                w = w | (kv_pos[:, None, :] < prefix_len)
            m = m & w
        return m

    # largest block <= Q_BLOCK dividing s (e.g. whisper's 1500 frames -> 300)
    qblk = Q_BLOCK
    while s % qblk:
        qblk -= 1
    if s <= qblk or qblk < 32:
        out = _attend_block(q, k, v, mask_for(positions), softcap)
    else:
        nb = s // qblk
        qb = q.reshape(b, nb, qblk, n_heads, -1).transpose(1, 0, 2, 3, 4)
        pb = positions.reshape(b, nb, qblk).transpose(1, 0, 2)

        # checkpoint per q-block: backward re-computes scores/probs per block
        # instead of stashing (nb, b, h, Q_BLOCK, s_kv) f32 residuals at once.
        @jax.checkpoint
        def body(_, qp):
            qi, pi = qp
            return None, _attend_block(qi, k, v, mask_for(pi), softcap)

        _, ob = jax.lax.scan(body, None, (qb, pb))
        out = ob.transpose(1, 0, 2, 3, 4).reshape(b, s, n_heads, -1)
    return jnp.einsum("bshk,hkd->bsd", out, params["wo"])


# ---------------------------------------------------------------------------
# KV cache (decode)
# ---------------------------------------------------------------------------


def init_kv_cache(
    batch: int, cache_len: int, n_kv_heads: int, head_dim: int, dtype: Any
) -> Dict[str, jax.Array]:
    return {
        "k": jnp.zeros((batch, cache_len, n_kv_heads, head_dim), dtype),
        "v": jnp.zeros((batch, cache_len, n_kv_heads, head_dim), dtype),
    }


@scopes.scoped(scopes.ATTENTION)
def decode_attention(
    params: Params,
    x: jax.Array,  # (b, 1, d)
    position: jax.Array,  # (b,) absolute position of the new token
    cache: Dict[str, jax.Array],
    *,
    sliding_window: int = 0,
    softcap: float = 0.0,
    rope_theta: float = 10_000.0,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One-token decode against a (ring-buffered when windowed) KV cache.

    The cache stores *rotated* keys, so softmax over cache slots is
    permutation-invariant and a ring buffer needs no unrotation.
    """
    b = x.shape[0]
    n_heads = params["wq"].shape[1]
    cache_len = cache["k"].shape[1]
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    k_new = jnp.einsum("bsd,dhk->bshk", x, params["wk"])
    v_new = jnp.einsum("bsd,dhk->bshk", x, params["wv"])
    q = apply_rope(q, position[:, None], rope_theta)
    k_new = apply_rope(k_new, position[:, None], rope_theta)

    slot = position % cache_len if sliding_window > 0 else position
    onehot = jax.nn.one_hot(slot, cache_len, dtype=cache["k"].dtype)  # (b, L)
    k = cache["k"] * (1 - onehot[:, :, None, None]) + onehot[:, :, None, None] * k_new.astype(cache["k"].dtype)
    v = cache["v"] * (1 - onehot[:, :, None, None]) + onehot[:, :, None, None] * v_new.astype(cache["v"].dtype)
    new_cache = {"k": k, "v": v}

    kh = _expand_kv(k, n_heads)
    vh = _expand_kv(v, n_heads)
    scale = q.shape[-1] ** -0.5
    scores = jnp.einsum("bqhk,blhk->bhql", q.astype(jnp.float32), kh.astype(jnp.float32)) * scale
    scores = _softcap(scores, softcap)
    idx = jnp.arange(cache_len)
    if sliding_window > 0:
        # Ring buffer: once wrapped, every slot holds a within-window entry;
        # before that, only slots <= position are warm.
        wrapped = position + 1 > cache_len
        valid = jnp.where(wrapped[:, None], True, idx[None, :] <= position[:, None])
    else:
        valid = idx[None, :] <= position[:, None]
    scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhql,blhk->bqhk", probs, vh.astype(jnp.float32)).astype(x.dtype)
    return jnp.einsum("bshk,hkd->bsd", out, params["wo"]), new_cache
