"""Shared neural building blocks (pure JAX, functional, dict params)."""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import scopes

Params = Dict[str, Any]

VOCAB_PAD_MULTIPLE = 128  # pad embedding rows so vocab shards over "model"

# ---------------------------------------------------------------------------
# mesh context: lets layer internals pin shardings GSPMD propagation loses
# (e.g. head dims inside scan bodies after a seq-concat). No-op off-mesh.
# ---------------------------------------------------------------------------

_MESH_CTX: Dict[str, Any] = {"mesh": None, "batch_axes": ()}


def set_mesh_ctx(mesh: Any, batch_axes: Tuple[str, ...] = ()) -> None:
    _MESH_CTX["mesh"] = mesh
    _MESH_CTX["batch_axes"] = tuple(batch_axes)


def get_mesh_ctx() -> Tuple[Any, Tuple[str, ...]]:
    return _MESH_CTX["mesh"], _MESH_CTX["batch_axes"]


def shard_hint(t: jax.Array, *dims: Optional[str]) -> jax.Array:
    """with_sharding_constraint by per-dim axis names.

    Entries: a mesh axis name, "batch" (the configured batch axes), or None.
    Every entry is divisibility-checked and silently dropped when invalid, so
    hints are safe on smoke meshes and reduced shapes.
    """
    mesh = _MESH_CTX["mesh"]
    if mesh is None:
        return t
    from jax.sharding import NamedSharding, PartitionSpec as P

    spec = []
    for size, ax in zip(t.shape, dims):
        if ax == "batch":
            ba = _MESH_CTX["batch_axes"]
            n = 1
            for a in ba:
                n *= mesh.shape.get(a, 1)
            ax = ba if (ba and n > 1 and size % n == 0) else None
        elif ax is not None:
            if ax not in mesh.shape or mesh.shape[ax] == 1 or size % mesh.shape[ax]:
                ax = None
        spec.append(ax)
    spec += [None] * (t.ndim - len(spec))
    if all(s is None for s in spec):
        return t
    return jax.lax.with_sharding_constraint(t, NamedSharding(mesh, P(*spec)))


def padded_vocab(vocab: int) -> int:
    return ((vocab + VOCAB_PAD_MULTIPLE - 1) // VOCAB_PAD_MULTIPLE) * VOCAB_PAD_MULTIPLE


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------


def dense_init(key: jax.Array, shape: Tuple[int, ...], dtype: Any, scale: float = 0.02) -> jax.Array:
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def zeros_init(shape: Tuple[int, ...], dtype: Any) -> jax.Array:
    return jnp.zeros(shape, dtype)


def ones_init(shape: Tuple[int, ...], dtype: Any) -> jax.Array:
    return jnp.ones(shape, dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * (1.0 + scale.astype(jnp.float32))).astype(dtype)


def layer_norm(x: jax.Array, scale: jax.Array, bias: jax.Array, eps: float = 1e-5) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    x = (x - mu) * jax.lax.rsqrt(var + eps)
    return (x * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dtype)


# ---------------------------------------------------------------------------
# embeddings + logits
# ---------------------------------------------------------------------------


def init_embedding(key: jax.Array, vocab: int, d_model: int, dtype: Any) -> Params:
    return {"table": dense_init(key, (padded_vocab(vocab), d_model), dtype)}


@scopes.scoped(scopes.EMBED)
def embed(params: Params, tokens: jax.Array) -> jax.Array:
    return jnp.take(params["table"], tokens, axis=0)


def logits_from_embedding(params: Params, x: jax.Array, vocab: int,
                          final_softcap: float = 0.0) -> jax.Array:
    """Tied-embedding readout with padded-vocab masking."""
    logits = jnp.einsum("...d,vd->...v", x, params["table"]).astype(jnp.float32)
    if final_softcap > 0:
        logits = final_softcap * jnp.tanh(logits / final_softcap)
    pv = params["table"].shape[0]
    if pv != vocab:
        mask = jnp.arange(pv) < vocab
        logits = jnp.where(mask, logits, -1e9)
    return logits


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float = 10_000.0, *,
               heads_first: bool = False) -> jax.Array:
    """x: (..., seq, heads, head_dim), or (..., heads, seq, head_dim) with
    ``heads_first``; positions: (..., seq).

    Interleaved-pair convention (rotates (x[2i], x[2i+1]) pairs) rather than
    rotate-half: adjacent pairs stay inside a "model"-axis shard when head_dim
    is sharded, so RoPE never mixes values across shards.
    """
    freqs = rope_frequencies(x.shape[-1], theta)  # (hd/2,)
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (..., seq, hd/2)
    angles = angles[..., None, :, :] if heads_first else angles[..., :, None, :]  # over heads
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    xf = x.astype(jnp.float32)
    pairs = xf.reshape(*xf.shape[:-1], xf.shape[-1] // 2, 2)
    x1, x2 = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


# ---------------------------------------------------------------------------
# MLP (SwiGLU)
# ---------------------------------------------------------------------------


def init_mlp(key: jax.Array, d_model: int, d_ff: int, dtype: Any) -> Params:
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "wg": dense_init(k1, (d_model, d_ff), dtype),
        "wi": dense_init(k2, (d_model, d_ff), dtype),
        "wo": dense_init(k3, (d_ff, d_model), dtype),
    }


@scopes.scoped(scopes.MLP)
def mlp(params: Params, x: jax.Array) -> jax.Array:
    g = jax.nn.silu(jnp.einsum("...d,df->...f", x, params["wg"]))
    u = jnp.einsum("...d,df->...f", x, params["wi"])
    return jnp.einsum("...f,fd->...d", g * u, params["wo"])


def cross_entropy_loss(logits: jax.Array, labels: jax.Array,
                       mask: Optional[jax.Array] = None) -> jax.Array:
    """Mean CE over valid positions. logits f32 (..., V); labels int (...)."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    if mask is None:
        mask = (labels >= 0).astype(jnp.float32)
    else:
        mask = mask.astype(jnp.float32)
    return -(ll * mask).sum() / jnp.maximum(mask.sum(), 1.0)
