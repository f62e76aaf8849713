"""Sharding recipes: ArchConfig + mesh -> PartitionSpec pytrees.

Rules (see DESIGN.md §4):
  * within a DFL node, tensor-parallel over the "model" axis: attention heads
    when divisible, otherwise head_dim (RoPE is interleaved-pair, so head_dim
    shards cleanly); d_ff, d_inner, and the padded vocab always shard;
  * experts shard over `cfg.expert_axis` (MoE archs give up per-16-chip
    replicas and use the data axis for expert parallelism);
  * batch shards over ("pod","data") whenever divisible;
  * decode caches: batch over node axes, head_dim (or kv-heads) over "model",
    and — when batch is unshardable (long_500k) — cache sequence over "data".

Anything not matched is replicated. Every rule checks divisibility against
the actual mesh, so one recipe serves the 1-device smoke mesh, the 256-chip
pod, and the 512-chip multi-pod mesh.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..configs.base import ArchConfig


def _axis_size(mesh: Mesh, name: str) -> int:
    return mesh.shape[name] if name in mesh.shape else 1


def _div(n: int, mesh: Mesh, axis: str) -> bool:
    return axis in mesh.shape and n % _axis_size(mesh, axis) == 0 and _axis_size(mesh, axis) > 1


def batch_axes(mesh: Mesh, batch: int) -> Tuple[str, ...]:
    """Largest prefix of ("pod","data") that divides the batch."""
    axes = [a for a in ("pod", "data") if a in mesh.shape]
    chosen: Tuple[str, ...] = ()
    size = 1
    for a in axes:
        if batch % (size * mesh.shape[a]) == 0:
            chosen += (a,)
            size *= mesh.shape[a]
    return chosen


def batch_spec(mesh: Mesh, batch: int, rank: int) -> P:
    ba = batch_axes(mesh, batch)
    return P(ba if ba else None, *([None] * (rank - 1)))


# ---------------------------------------------------------------------------
# parameter specs
# ---------------------------------------------------------------------------


def param_spec_tree(cfg: ArchConfig, params: Any, mesh: Mesh) -> Any:
    """PartitionSpec pytree mirroring `params` (which may be stacked)."""
    m = "model"
    e_ax = cfg.expert_axis if cfg.expert_axis in mesh.shape else None

    # Megatron rule: shard the HEAD dim when divisible, otherwise replicate
    # that projection. Never shard head_dim — hd-sharded QK^T psums the full
    # (b, h, s, s_kv) f32 scores every q-block (observed: 10x memory/collective
    # blowup at 32k sequences). GQA KV with few heads is simply replicated
    # (small weights, scores stay head-sharded via the repeat).
    model_n = _axis_size(mesh, m)

    def attn_head_spec(n_heads: int, hd: int) -> Tuple[Optional[str], Optional[str]]:
        """(heads_axis, hd_axis) for a (…, H, hd) weight."""
        if model_n > 1 and n_heads % model_n == 0:
            return m, None
        return None, None

    def rule(path: Tuple[Any, ...], leaf: Any) -> P:
        keys = [p.key for p in path if hasattr(p, "key")]
        name = keys[-1] if keys else ""
        parent = keys[-2] if len(keys) > 1 else ""
        rank = leaf.ndim
        trail: Tuple[Optional[str], ...]

        if name == "table":  # embedding (padded vocab, d)
            trail = (m if _div(leaf.shape[0], mesh, m) else None, None)
        elif parent in ("attn", "cross") and name in ("wq", "wk", "wv"):
            h_ax, d_ax = attn_head_spec(leaf.shape[-2], leaf.shape[-1])
            trail = (None, h_ax, d_ax)
        elif parent in ("attn", "cross") and name == "wo":
            h_ax, d_ax = attn_head_spec(leaf.shape[-3], leaf.shape[-2])
            trail = (h_ax, d_ax, None)
        elif parent in ("mlp", "dense") and name in ("wg", "wi"):
            trail = (None, m if _div(leaf.shape[-1], mesh, m) else None)
        elif parent in ("mlp", "dense") and name == "wo":
            trail = (m if _div(leaf.shape[-2], mesh, m) else None, None)
        elif parent == "moe" and name in ("wg", "wi"):  # (e, d, f)
            trail = (e_ax, None, m if _div(leaf.shape[-1], mesh, m) else None)
        elif parent == "moe" and name == "wo":  # (e, f, d)
            trail = (e_ax, m if _div(leaf.shape[-2], mesh, m) else None, None)
        elif name == "router":
            trail = (None, None)
        elif name in ("wx", "wz", "wxBC"):  # (d, di), mamba2's xBC (d, di + 2 G N)
            trail = (None, m if _div(leaf.shape[-1], mesh, m) else None)
        elif name == "conv_w":  # (w, di), mamba2 (w, di + 2 G N)
            trail = (None, m if _div(leaf.shape[-1], mesh, m) else None)
        elif name in ("wdt_in",):  # (di, r)
            trail = (m if _div(leaf.shape[-2], mesh, m) else None, None)
        elif name in ("wB", "wC"):  # (di|d, n)
            lead = m if (parent == "body" and _div(leaf.shape[-2], mesh, m)
                         and cfg.ssm_version == 1) else None
            trail = (lead, None)
        elif name == "dt_proj":  # (r, di)
            trail = (None, m if _div(leaf.shape[-1], mesh, m) else None)
        elif name in ("dt_bias", "D") and rank >= 1 and leaf.shape[-1] > 1024:
            trail = (m if _div(leaf.shape[-1], mesh, m) else None,)
        elif name == "A_log" and cfg.ssm_version == 1 and rank >= 2:  # (di, n)
            trail = (m if _div(leaf.shape[-2], mesh, m) else None, None)
        elif name == "out_proj":  # (di, d)
            trail = (m if _div(leaf.shape[-2], mesh, m) else None, None)
        elif name == "wdt":  # mamba2 (d, h)
            trail = (None, None)
        else:  # norms, scalars, biases
            trail = tuple(None for _ in range(min(rank, 1)))
            return P()
        n_lead = rank - len(trail)
        if n_lead < 0:
            return P()
        return P(*([None] * n_lead), *trail)

    return jax.tree_util.tree_map_with_path(rule, params)


# ---------------------------------------------------------------------------
# cache + batch specs
# ---------------------------------------------------------------------------


def cache_spec_tree(cfg: ArchConfig, cache: Any, mesh: Mesh, batch: int) -> Any:
    m = "model"
    ba = batch_axes(mesh, batch)
    b_ax = ba if ba else None
    shard_seq = not ba  # batch unshardable (long_500k): shard cache seq on data

    def rule(path: Tuple[Any, ...], leaf: Any) -> P:
        keys = [p.key for p in path if hasattr(p, "key")]
        name = keys[-1] if keys else ""
        rank = leaf.ndim
        if name in ("k", "v") or name.startswith("cross_"):
            # (L, b, c, K, hd) or (n_super, b, c, K, hd)
            kv, hd = leaf.shape[-2], leaf.shape[-1]
            h_ax = m if _div(kv, mesh, m) else None
            d_ax = m if (h_ax is None and _div(hd, mesh, m)) else None
            c_ax = "data" if (shard_seq and _div(leaf.shape[-3], mesh, "data")) else None
            return P(*([None] * (rank - 4)), b_ax, c_ax, h_ax, d_ax)
        if name == "conv":  # (L..., b, w-1, di)
            d_ax = m if _div(leaf.shape[-1], mesh, m) else None
            return P(*([None] * (rank - 3)), b_ax, None, d_ax)
        if name == "ssm":  # mamba1 (L, b, di, n) / mamba2 (L, b, h, hd, n)
            if cfg.ssm_version == 2 and rank >= 4:
                h_ax = m if _div(leaf.shape[-3], mesh, m) else None
                return P(*([None] * (rank - 4)), b_ax, h_ax, None, None)
            d_ax = m if _div(leaf.shape[-2], mesh, m) else None
            return P(*([None] * (rank - 3)), b_ax, d_ax, None)
        return P()

    return jax.tree_util.tree_map_with_path(rule, cache)


def named(mesh: Mesh, spec_tree: Any) -> Any:
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))
