"""Mixture-of-Experts layer: top-k routing with capacity-based einsum dispatch.

TPU-native formulation (T5X/MaxText style): tokens stay grouped per sequence,
dispatch/combine tensors are one-hot over (expert, capacity) so expert compute
is dense einsum — which shards cleanly with experts on the expert-parallel
mesh axis and per-expert d_ff on the "model" axis. Overflowing tokens are
dropped (standard capacity-factor semantics); an auxiliary load-balance loss
keeps the router near-uniform.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import scopes
from .layers import Params, dense_init


def init_moe(
    key: jax.Array, d_model: int, d_ff: int, n_experts: int, dtype: Any,
    dense_residual_ff: int = 0,
) -> Params:
    kr, kg, ki, ko, kd = jax.random.split(key, 5)
    p = {
        "router": dense_init(kr, (d_model, n_experts), jnp.float32),
        "wg": dense_init(kg, (n_experts, d_model, d_ff), dtype),
        "wi": dense_init(ki, (n_experts, d_model, d_ff), dtype),
        "wo": dense_init(ko, (n_experts, d_ff, d_model), dtype),
    }
    if dense_residual_ff:
        from .layers import init_mlp

        p["dense"] = init_mlp(kd, d_model, dense_residual_ff, dtype)
    return p


GROUP_SIZE = 256  # tokens per dispatch group


@scopes.scoped(scopes.MOE)
def moe_layer(
    params: Params,
    x: jax.Array,  # (b, s, d)
    top_k: int,
    capacity_factor: float = 1.25,
    expert_sharding=None,  # (mesh, e_axis, batch_axes): expert-parallel hints
) -> Tuple[jax.Array, jax.Array]:
    """Returns (output, aux_load_balance_loss).

    Grouped dispatch: tokens are split into groups of GROUP_SIZE and capacity
    is budgeted per group, so the one-hot dispatch/combine tensors scale as
    tokens x group_size x top_k x cf — *independent of the expert count* —
    instead of tokens x experts x capacity (which explodes at 128 experts and
    1M-token batches).
    """
    b, s, d = x.shape
    n_experts = params["router"].shape[1]
    # adaptive group size: target a small per-group capacity so the
    # (tokens, k, e, c) one-hot stays bounded even at top_k=8 / 128 experts
    c_target = 6
    gs = int(c_target * n_experts / max(top_k * capacity_factor, 1e-9))
    gs = max(16, min(gs, GROUP_SIZE, s))
    while s % gs:
        gs -= 1
    G = s // gs
    capacity = max(1, int(gs * top_k * capacity_factor / n_experts))

    def shard_moe(t: jax.Array, e_dim: int) -> jax.Array:
        """Expert dim on the EP axis; batch keeps the remaining node axes."""
        if expert_sharding is None:
            return t
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh, e_axis, b_axes = expert_sharding
        if t.shape[e_dim] % mesh.shape[e_axis]:
            return t
        rem = tuple(a for a in b_axes if a != e_axis and a in mesh.shape)
        n_b = int(np.prod([mesh.shape[a] for a in rem])) if rem else 1
        if rem and t.shape[0] % n_b:
            rem = ()
        spec = [None] * t.ndim
        spec[0] = rem if rem else None
        spec[e_dim] = e_axis
        return jax.lax.with_sharding_constraint(t, NamedSharding(mesh, P(*spec)))

    from .layers import shard_hint

    xg = shard_hint(x.reshape(b, G, gs, d), "batch", None, None, None)
    # cast the (tiny) router rather than the activations: an f32 copy of the
    # full (b, G, gs, d) activations dominated peak memory at 480B scale
    logits = jnp.einsum("bgsd,de->bgse", xg,
                        params["router"].astype(x.dtype)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)  # (b, G, gs, e)

    gate_vals, expert_idx = jax.lax.top_k(probs, top_k)  # (b, G, gs, k)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

    sel = jax.nn.one_hot(expert_idx, n_experts, dtype=jnp.float32)  # (b, G, gs, k, e)
    flat_sel = sel.reshape(b, G, gs * top_k, n_experts)
    pos_in_expert = jnp.cumsum(flat_sel, axis=2) * flat_sel - 1.0
    pos_in_expert = pos_in_expert.reshape(b, G, gs, top_k, n_experts)
    within_cap = (pos_in_expert >= 0) & (pos_in_expert < capacity)

    cap_oh = jax.nn.one_hot(
        jnp.clip(pos_in_expert, 0, capacity - 1).astype(jnp.int32), capacity,
        dtype=jnp.float32,
    )  # (b, G, gs, k, e, c)
    keep = (sel * within_cap.astype(jnp.float32))[..., None]
    dispatch = (keep * cap_oh).sum(axis=3)  # (b, G, gs, e, c)
    combine = (gate_vals[..., None, None] * keep * cap_oh).sum(axis=3)

    # dispatch/combine stay fully batch-sharded (resharding them would drag
    # the much larger xg with them); only xe — the EP all-to-all payload —
    # moves to (batch-minus-EP-axis, experts@EP)
    dispatch = shard_hint(dispatch.astype(x.dtype), "batch", None, None, None, None)
    combine = shard_hint(combine.astype(x.dtype), "batch", None, None, None, None)
    # The wsc *sandwich* (batch-spec then EP-spec) makes the expert-parallel
    # all-to-all happen on xe itself — in both directions. With only the EP
    # constraint, the einsum VJP reshards the much larger xg/cotangent chain
    # to pod-only sharding (observed: 4x15GiB f32 buffers at 480B scale).
    xe = jnp.einsum("bgsd,bgsec->bgecd", xg, dispatch)  # (b, G, e, c, d)
    xe = shard_moe(shard_hint(xe, "batch", None, None, None, None), 2)
    gt = jax.nn.silu(jnp.einsum("bgecd,edf->bgecf", xe, params["wg"]))
    u = jnp.einsum("bgecd,edf->bgecf", xe, params["wi"])
    ye = shard_moe(jnp.einsum("bgecf,efd->bgecd", gt * u, params["wo"]), 2)
    ye = shard_hint(ye, "batch", None, None, None, None)
    y = jnp.einsum("bgecd,bgsec->bgsd", ye, combine)
    y = y.reshape(b, s, d)

    if "dense" in params:  # arctic: dense MLP residual in parallel
        from .layers import mlp

        y = y + mlp(params["dense"], x)

    # load-balance aux loss (Switch): e * sum_e f_e * P_e
    token_frac = sel.sum(axis=3).reshape(-1, n_experts).mean(axis=0)  # f_e
    prob_frac = probs.reshape(-1, n_experts).mean(axis=0)  # P_e
    aux = n_experts * jnp.sum(token_frac * prob_frac) / top_k
    return y, aux
