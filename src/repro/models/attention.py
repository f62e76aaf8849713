"""GQA attention: full / sliding-window / softcapped, train + cached decode.

Plain causal self-attention on a TPU (no window, softcap, prefix or external
K/V, positions equal to the row index, a length the kernel's blocks divide,
a head_dim the kernel takes)
runs through JAX's fused Pallas flash-attention kernel, forward and backward,
which skips the key blocks above the diagonal. Every other case uses a
query-block scan so the score matrix is never materialized at (seq × seq):
per block the footprint is (block × seq), which keeps 32k-prefill lowering
memory-sane. Decode attends one token against the (possibly ring-buffered)
KV cache; with a sequence-sharded cache the softmax reductions become GSPMD
collectives automatically.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu import flash_attention as tpu_flash
from jax.sharding import PartitionSpec as P

from ..obs import scopes
from .layers import Params, apply_rope, dense_init, get_mesh_ctx, shard_hint

Q_BLOCK = 256  # query-block size for chunked attention
NEG_INF = -2.0e38
FLASH_TILES = (1024, 512, 256, 128)  # the fused kernel's tile sizes, largest first


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


def _expand_kv(k: jax.Array, n_heads: int, axis: int = -2) -> jax.Array:
    """(b, s, kv, hd) -> (b, s, H, hd) by repeating groups (heads on ``axis``)."""
    n_kv = k.shape[axis]
    if n_kv == n_heads:
        return k
    return jnp.repeat(k, n_heads // n_kv, axis=axis)


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
    scale: float,
) -> jax.Array:
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
    mesh, _ = get_mesh_ctx()
    return bool(mesh is not None and "model" in mesh.shape
                and n_heads % mesh.shape["model"] == 0)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _tile(s: int, cap: int) -> int:
    return next(b for b in FLASH_TILES if b <= cap and s % b == 0)


def flash_block_sizes(s: int, fwd: Tuple[int, int] = (1024, 1024),
                      dkv: Tuple[int, int] = (512, 1024),
                      dq: Tuple[int, int] = (1024, 256)) -> tpu_flash.BlockSizes:
    """The fused kernel's tiles for sequence length ``s``: for the forward,
    dK/dV and dQ kernels, a (query, key) tile each, every side the largest of
    :data:`FLASH_TILES` up to its cap that divides ``s``. The caps, forward
    1024 by 1024, dK/dV 512 by 1024 and dQ 1024 by 256 (the dQ kernel's f32
    row statistic is broadcast to its key tile in HBM), were set from a sweep
    on a v5e at the train cells' shapes (``tools/flash_sweep.py``; PERF.md)."""
    (fq, fk), (dkv_q, dkv_k), (dq_q, dq_k) = (
        tuple(_tile(s, cap) for cap in caps) for caps in (fwd, dkv, dq))
    return tpu_flash.BlockSizes(
        block_q=fq, block_k_major=fk, block_k=fk, block_b=1,
        block_q_major_dkv=dkv_q, block_q_dkv=dkv_q, block_k_major_dkv=dkv_k, block_k_dkv=dkv_k,
        block_q_dq=dq_q, block_k_major_dq=dq_k, block_k_dq=dq_k,
    )


def _flash_causal(q: jax.Array, k: jax.Array, v: jax.Array, scale: float) -> jax.Array:
    """Causal attention of head-major (b, H, s, hd) q, k, v, scores scaled by
    ``scale``, by the fused kernel. Under a mesh the kernel runs per shard
    inside ``shard_map`` (batch over the batch axes, heads over ``model``
    where they divide), so GSPMD never has to partition the kernel's custom
    call."""
    b, n_heads, s, _ = q.shape

    def fn(q_, k_, v_):
        return tpu_flash.flash_attention(q_, k_, v_, causal=True, sm_scale=scale,
                                         block_sizes=flash_block_sizes(s))

    mesh, batch_axes = get_mesh_ctx()
    with jax.named_scope(scopes.ATTENTION_FLASH):
        if mesh is None:
            return fn(q, k, v)
        n_batch = math.prod(mesh.shape[a] for a in batch_axes)
        spec = P(batch_axes if batch_axes and b % n_batch == 0 else None,
                 "model" if _divides(n_heads) else None, None, None)
        return jax.shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
                             check_vma=False)(q, k, v)


def _fused_attention(params: Params, x: jax.Array, positions: jax.Array,
                     rope_theta: float, use_rope: bool, scale: float) -> jax.Array:
    """Plain causal self-attention, head-major from the projections on."""
    n_heads = params["wq"].shape[1]
    q = jnp.einsum("bsd,dhk->bhsk", x, params["wq"])
    k = jnp.einsum("bsd,dhk->bhsk", x, params["wk"])
    v = jnp.einsum("bsd,dhk->bhsk", x, params["wv"])
    if use_rope:
        q = apply_rope(q, positions, rope_theta, heads_first=True)
        k = apply_rope(k, positions, rope_theta, heads_first=True)
    k = _expand_kv(k, n_heads, axis=1)
    v = _expand_kv(v, n_heads, axis=1)
    out = _flash_causal(q, k, v, scale)
    return jnp.einsum("bhsk,hkd->bsd", out, params["wo"])


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
    positions_are_rows: bool = False,
    scale: Optional[float] = None,
) -> jax.Array:
    """Full-sequence attention (train / prefill / encoder / cross).

    ``positions_are_rows`` is the caller's word that ``positions`` is
    ``arange(s)`` on every row: only then may the fused kernel, which masks
    by index, take a causal self-attention call. Scores are scaled by
    ``scale``, ``head_dim ** -0.5`` where it is None.
    """
    b, s, _ = x.shape
    hd = params["wq"].shape[-1]
    scale = hd ** -0.5 if scale is None else scale
    # the kernel masks causally by row and column index, and by nothing else;
    # it takes a head_dim of at most 128 or a multiple of 128
    if (causal and positions_are_rows and kv_override is None and prefix_len == 0
            and sliding_window == 0 and softcap == 0 and s % FLASH_TILES[-1] == 0
            and (hd <= 128 or hd % 128 == 0) and _on_tpu()):
        return _fused_attention(params, x, positions, rope_theta, use_rope, scale)
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
        out = _attend_block(q, k, v, mask_for(positions), softcap, scale)
    else:
        nb = s // qblk
        qb = q.reshape(b, nb, qblk, n_heads, -1).transpose(1, 0, 2, 3, 4)
        pb = positions.reshape(b, nb, qblk).transpose(1, 0, 2)

        # checkpoint per q-block: backward re-computes scores/probs per block
        # instead of stashing (nb, b, h, Q_BLOCK, s_kv) f32 residuals at once.
        @jax.checkpoint
        def body(_, qp):
            qi, pi = qp
            return None, _attend_block(qi, k, v, mask_for(pi), softcap, scale)

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
    use_rope: bool = True,
    scale: Optional[float] = None,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One-token decode against a (ring-buffered when windowed) KV cache.

    The cache stores *rotated* keys, so softmax over cache slots is
    permutation-invariant and a ring buffer needs no unrotation. Scores are
    scaled by ``scale``, ``head_dim ** -0.5`` where it is None.
    """
    b = x.shape[0]
    n_heads = params["wq"].shape[1]
    cache_len = cache["k"].shape[1]
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    k_new = jnp.einsum("bsd,dhk->bshk", x, params["wk"])
    v_new = jnp.einsum("bsd,dhk->bshk", x, params["wv"])
    if use_rope:
        q = apply_rope(q, position[:, None], rope_theta)
        k_new = apply_rope(k_new, position[:, None], rope_theta)

    slot = position % cache_len if sliding_window > 0 else position
    onehot = jax.nn.one_hot(slot, cache_len, dtype=cache["k"].dtype)  # (b, L)
    k = cache["k"] * (1 - onehot[:, :, None, None]) + onehot[:, :, None, None] * k_new.astype(cache["k"].dtype)
    v = cache["v"] * (1 - onehot[:, :, None, None]) + onehot[:, :, None, None] * v_new.astype(cache["v"].dtype)
    new_cache = {"k": k, "v": v}

    kh = _expand_kv(k, n_heads)
    vh = _expand_kv(v, n_heads)
    scale = q.shape[-1] ** -0.5 if scale is None else scale
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
