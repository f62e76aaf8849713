"""MOSGU gossip as compiled TPU collectives.

The moderator's host-side plan (MST + BFS 2-coloring -> slot plan, see
repro.core.schedule) lowers to a static sequence of `lax.ppermute` steps over
the DFL node axis inside `shard_map`. One colored slot becomes one-or-more
matchings (collective-permute needs unique sources and targets); nodes of the
inactive color simply pass zeros.

Modes (DESIGN.md §6):
  * dissemination  — paper-faithful: every node ends the round holding all N
                     models in a (N, …) buffer, then aggregates (FedAvg).
                     O(N·|θ|) memory; lowered for small archs.
  * segmented      — segmented gossip (Hu et al.): each model is split into S
                     segments gossiped independently; buffer has N·S segment
                     slots, S× the permute steps at 1/S the payload each.
  * tree_allreduce — beyond-paper: reduce partial sums up the colored MST and
                     broadcast the mean down. Produces *exactly* the FedAvg
                     mean the paper's round produces (tested), with O(2·depth)
                     slots and O(1) buffers.
  * mixing         — beyond-paper: 1-hop pairwise gossip averaging over MST
                     edge matchings (gossip-SGD, doubly-stochastic).
  * flooding       — baseline: all_gather over the node axis + mean (what the
                     naive broadcast round computes).
  * allreduce_ref  — reference: XLA's native psum (the centralized-collective
                     upper bound MOSGU is compared against).

All compiled modes consume the same communication-plan IR
(:mod:`repro.core.plan`): a policy is compiled once into a ``SlotPlan`` and
lowered here via ``plan_to_perm_steps``.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from functools import partial
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from ..core.graph import Graph, build_mst, color_graph
from ..core.plan import SegmentedGossipPolicy, compile_policy
from ..core.schedule import (
    PermStep,
    SlotPlan,
    compile_dissemination,
    compile_tree_allreduce,
    decompose_matchings,
    plan_to_perm_steps,
)
from ..obs import scopes

PyTree = Any


# ---------------------------------------------------------------------------
# node topology on the TPU mesh
# ---------------------------------------------------------------------------


def make_node_graph(mesh: Mesh, node_axes: Sequence[str],
                    inter_pod_cost: float = 10.0, intra_pod_cost: float = 1.0) -> Graph:
    """Complete cost graph over DFL nodes.

    Node id is row-major over `node_axes`. Links crossing the "pod" axis model
    DCN (the paper's router hop); links within a pod model ICI. Tiny
    deterministic jitter makes MST/coloring unique.
    """
    sizes = [mesh.shape[a] for a in node_axes if a in mesh.shape]
    n = int(np.prod(sizes)) if sizes else 1
    pod_size = 1
    if "pod" in node_axes and "pod" in mesh.shape:
        pod_size = n // mesh.shape["pod"]
    adj = np.zeros((n, n))
    for u in range(n):
        for v in range(u + 1, n):
            same_pod = (u // pod_size) == (v // pod_size) if pod_size > 1 else True
            base = intra_pod_cost if same_pod else inter_pod_cost
            adj[u, v] = adj[v, u] = base + 1e-3 * ((u * 31 + v * 17) % 97) / 97.0
    return Graph(adj)


@dataclass
class GossipPlan:
    """Everything the compiled collectives need, all static."""

    n_nodes: int
    node_axes: Tuple[str, ...]
    mst: Graph
    colors: np.ndarray
    dissemination: SlotPlan
    tree: SlotPlan
    diss_steps: List[PermStep]
    tree_steps: List[PermStep]
    n_tree_reduce_steps: int
    mixing_matchings: List[List[Tuple[int, int]]]
    # segmented gossip (model split into n_segments independently gossiped
    # pieces); compiled from the same IR policy as the host-side executors
    segmented: Optional[SlotPlan] = None
    seg_steps: List[PermStep] = field(default_factory=list)
    n_segments: int = 1
    # Physical node id -> buffer row (= plan-payload owner id). None means
    # identity (full membership). Under churn the compiled plans index
    # payloads by *subgraph* position, so masked meshes need this remap
    # (-1 = node outside the healthy subgraph).
    node_slot: Optional[np.ndarray] = None

    @classmethod
    def build(cls, mesh: Mesh, node_axes: Sequence[str],
              n_segments: int = 4) -> "GossipPlan":
        node_axes = tuple(a for a in node_axes if a in mesh.shape)
        g = make_node_graph(mesh, node_axes)
        mst = build_mst(g, "prim")
        colors = color_graph(mst, "bfs")
        diss = compile_dissemination(mst, colors)
        tree = compile_tree_allreduce(mst, colors)
        seg = compile_policy(
            SegmentedGossipPolicy(mst, colors, segments=n_segments),
            record_traces=False) if g.n > 1 else None
        # count perm steps belonging to the reduce phase
        n_red_slots = tree.n_reduce_slots  # type: ignore[attr-defined]
        red_steps = sum(
            len([m for m in decompose_matchings(s.sends) if m])
            for s in tree.slots[:n_red_slots]
        )
        matchings = decompose_matchings(
            [(u, v, 0) for u, v, _ in mst.edges()]
        )
        return cls(
            n_nodes=g.n,
            node_axes=node_axes,
            mst=mst,
            colors=colors,
            dissemination=diss,
            tree=tree,
            diss_steps=plan_to_perm_steps(diss),
            tree_steps=plan_to_perm_steps(tree),
            n_tree_reduce_steps=red_steps,
            mixing_matchings=[[(u, v) for u, v, _ in m] for m in matchings],
            segmented=seg,
            seg_steps=plan_to_perm_steps(seg) if seg is not None else [],
            n_segments=n_segments,
        )


def _node_index(node_axes: Sequence[str]) -> jax.Array:
    idx = jnp.zeros((), jnp.int32)
    for a in node_axes:
        idx = idx * jax.lax.axis_size(a) + jax.lax.axis_index(a)
    return idx


def _axis_name(node_axes: Sequence[str]):
    return node_axes if len(node_axes) > 1 else node_axes[0]


# ---------------------------------------------------------------------------
# gossip bodies (run inside shard_map)
# ---------------------------------------------------------------------------


def _tree_allreduce_body(plan: GossipPlan, theta: PyTree,
                         wire_dtype=None, codec=None) -> PyTree:
    """Colored-MST reduce + broadcast; returns the FedAvg mean on every node.

    ``wire_dtype`` (e.g. bf16) compresses the on-wire payload: partial sums
    accumulate in f32 locally but each hop transfers the cast value — halving
    the collective roofline term at ~2^-8 relative quantization per hop.
    ``codec`` generalizes it: each hop permutes the codec's encoded buffers
    (quantized partial sums), decoded on receipt.
    """
    if plan.n_nodes == 1:
        return theta

    def tx(t):
        if wire_dtype is None:
            return t
        # the barrier stops XLA's convert-mover from hoisting the cast across
        # the collective-permute (which would put f32 back on the wire)
        return jax.lax.optimization_barrier(t.astype(wire_dtype))

    def rx(t):
        if wire_dtype is None:
            return t
        return jax.lax.optimization_barrier(t)

    def hop(t, perm):
        if codec is not None:
            return _ppermute_wire(t, ax, perm, codec)
        return rx(jax.lax.ppermute(tx(t), ax, perm))

    ax = _axis_name(plan.node_axes)
    nid = _node_index(plan.node_axes)
    n_red = plan.n_tree_reduce_steps
    acc = jax.tree.map(lambda t: t.astype(jnp.float32), theta)
    for i, step in enumerate(plan.tree_steps[:n_red]):
        with jax.named_scope(scopes.gossip_step(i)):
            recv = jax.tree.map(lambda t: hop(t, step.perm), acc)
            acc = jax.tree.map(lambda a, r: a + r.astype(jnp.float32), acc, recv)
    val = acc
    for i, step in enumerate(plan.tree_steps[n_red:], start=n_red):
        with jax.named_scope(scopes.gossip_step(i)):
            is_recv = jnp.take(jnp.asarray(step.recv_payload >= 0), nid)
            recv = jax.tree.map(lambda t: hop(t, step.perm), val)
            val = jax.tree.map(
                lambda r, v: jnp.where(is_recv, r.astype(jnp.float32), v), recv, val)
    # churn masking (dfl.session): nodes with color -1 are outside the healthy
    # subgraph — they keep their local params and neither send nor receive
    if (np.asarray(plan.colors) < 0).any():
        is_member = jnp.take(jnp.asarray(plan.colors >= 0), nid)
        return jax.tree.map(
            lambda v, t: jnp.where(is_member, (v / plan.n_nodes).astype(t.dtype), t),
            val, theta)
    return jax.tree.map(lambda v, t: (v / plan.n_nodes).astype(t.dtype), val, theta)


def _ppermute_wire(t, ax, perm, codec=None):
    """One hop: permute ``t``'s wire representation.

    With a codec the arrays that actually cross the collective are the
    *encoded* buffers (int8 codes + scales, packed top-k values + indices…);
    the receiver decodes. Without one this is a plain ``ppermute``.
    """
    if codec is None:
        return jax.lax.ppermute(t, ax, perm)
    enc = codec.jax_encode(t)
    got = jax.tree.map(lambda e: jax.lax.ppermute(e, ax, perm), enc)
    return codec.jax_decode(got, t.shape, t.dtype)


def _apply_perm_steps(steps: Sequence[PermStep], buf: PyTree, ax, nid,
                      codec=None) -> PyTree:
    """Run a compiled plan's ppermute steps over a slot-indexed buffer tree.

    Each leaf's leading dimension is the logical payload-slot axis the
    ``PermStep`` send/recv payload ids index into. Shared by every
    buffer-dissemination mode (dissemination, segmented, flooding plans).
    With a codec, each hop permutes encoded buffers (re-encoding a decoded
    payload is exact for every shipped codec, so forwarding pays the
    compression error only once — at the original sender).
    """
    for i, step in enumerate(steps):
        with jax.named_scope(scopes.gossip_step(i)):
            send_idx = jnp.take(jnp.asarray(step.send_payload), nid)
            recv_idx = jnp.take(jnp.asarray(step.recv_payload), nid)

            def one(b):
                payload = jax.lax.dynamic_index_in_dim(
                    b, jnp.maximum(send_idx, 0), 0, keepdims=False)
                got = _ppermute_wire(payload, ax, step.perm, codec)
                updated = jax.lax.dynamic_update_index_in_dim(
                    b, got.astype(b.dtype), jnp.maximum(recv_idx, 0), 0)
                return jnp.where(recv_idx >= 0, updated, b)

            buf = jax.tree.map(one, buf)
    return buf


def _buffer_row(plan: GossipPlan, nid) -> Tuple[jax.Array, Optional[jax.Array]]:
    """This node's buffer row (its owner id in the compiled plan's payload
    space) and, under churn masking, its membership predicate."""
    if plan.node_slot is None:
        return nid, None
    row = jnp.take(jnp.asarray(plan.node_slot, dtype=np.int32), nid)
    return jnp.maximum(row, 0), row >= 0


def _dissemination_body(plan: GossipPlan, theta: PyTree, codec=None,
                        ef: Optional[PyTree] = None
                        ) -> Tuple[PyTree, PyTree, Optional[PyTree]]:
    """Paper-faithful full dissemination: (fedavg_mean, buffer, new_ef).

    ``codec`` puts encoded buffers on every hop's wire. ``ef`` (a pytree of
    f32 residuals mirroring ``theta``) enables error feedback: the node's
    *own* contribution is ``decode(encode(theta + ef))`` and the leftovers
    become the next round's residual, so a sparsifying codec's dropped
    coordinates are compensated over rounds (EF-SGD). With EF every node
    contributes the same decoded tensor it transmits, keeping the computed
    mean identical across nodes.
    """
    if plan.n_nodes == 1:
        return theta, jax.tree.map(lambda t: t[None], theta), ef
    ax = _axis_name(plan.node_axes)
    nid = _node_index(plan.node_axes)
    row, is_member = _buffer_row(plan, nid)
    n = plan.n_nodes

    contrib, new_ef = theta, None
    if codec is not None and ef is not None:
        comp = jax.tree.map(lambda t, r: t.astype(jnp.float32) + r, theta, ef)
        dec = jax.tree.map(codec.jax_roundtrip, comp)
        new_ef = jax.tree.map(lambda c, d: c - d, comp, dec)
        contrib = jax.tree.map(lambda d, t: d.astype(t.dtype), dec, theta)

    def init_buf(t):
        buf = jnp.zeros((n, *t.shape), t.dtype)
        return jax.lax.dynamic_update_index_in_dim(buf, t, row, 0)

    buf = jax.tree.map(init_buf, contrib)
    buf = _apply_perm_steps(plan.diss_steps, buf, ax, nid, codec=codec)
    mean = jax.tree.map(
        lambda b, t: jnp.mean(b.astype(jnp.float32), axis=0).astype(t.dtype), buf, theta)
    if is_member is not None:  # masked nodes keep their local params
        mean = jax.tree.map(lambda m, t: jnp.where(is_member, m, t), mean, theta)
    return mean, buf, new_ef


def _segmented_body(plan: GossipPlan, theta: PyTree, codec=None) -> PyTree:
    """Segmented gossip: each leaf is split into S contiguous segments; the buffer
    holds N·S segment slots (slot k = owner k//S, segment k%S) and the
    compiled segmented plan moves one segment per transfer. After full
    dissemination every node reassembles all N models and takes the mean.
    With a codec, every per-segment hop permutes encoded buffers."""
    if plan.n_nodes == 1:
        return theta
    ax = _axis_name(plan.node_axes)
    nid = _node_index(plan.node_axes)
    row, is_member = _buffer_row(plan, nid)
    n, S = plan.n_nodes, plan.n_segments

    def split(t):
        # Segment k is the k-th of S equal contiguous ranges of the flat
        # leaf. Where the leading axis divides by S, split that axis: on the
        # TPU it moves no data, while flattening a tiled leaf relayouts it,
        # and the TPU compiler's time for a relayout grows with the leaf
        # (minutes per program at smollm-360m widths).
        if t.ndim and t.shape[0] % S == 0:
            return t.reshape(S, t.shape[0] // S, *t.shape[1:])
        flat = t.reshape(-1)
        pad = (-flat.shape[0]) % S
        if pad:
            flat = jnp.pad(flat, (0, pad))
        return flat.reshape(S, -1)

    def init_buf(t):
        segs = split(t)  # (S, *segment)
        buf = jnp.zeros((n * S, *segs.shape[1:]), segs.dtype)
        return jax.lax.dynamic_update_slice(
            buf, segs, (row * S,) + (0,) * (segs.ndim - 1))

    buf = jax.tree.map(init_buf, theta)
    buf = _apply_perm_steps(plan.seg_steps, buf, ax, nid, codec=codec)

    def reassemble_mean(b, t):
        models = b.reshape(n, S, *b.shape[1:]).astype(jnp.float32)
        mean = jnp.mean(models, axis=0)  # (S, *segment)
        if mean.size != t.size:  # flattened and padded
            mean = mean.reshape(-1)[: t.size]
        return mean.reshape(t.shape).astype(t.dtype)

    out = jax.tree.map(reassemble_mean, buf, theta)
    if is_member is not None:  # masked nodes keep their local params
        out = jax.tree.map(lambda m, t: jnp.where(is_member, m, t), out, theta)
    return out


def _mixing_body(plan: GossipPlan, theta: PyTree, lam: float = 1.0) -> PyTree:
    """One pairwise-averaging pass over the MST edge matchings."""
    if plan.n_nodes == 1:
        return theta
    ax = _axis_name(plan.node_axes)
    nid = _node_index(plan.node_axes)
    for i, matching in enumerate(plan.mixing_matchings):
        perm = [(u, v) for (u, v) in matching] + [(v, u) for (u, v) in matching]
        members = np.zeros(plan.n_nodes, bool)
        for u, v in matching:
            members[u] = members[v] = True
        with jax.named_scope(scopes.gossip_step(i)):
            in_match = jnp.take(jnp.asarray(members), nid)

            def one(t):
                recv = jax.lax.ppermute(t, ax, perm)
                mixed = (1 - lam / 2) * t.astype(jnp.float32) + (lam / 2) * recv.astype(jnp.float32)
                return jnp.where(in_match, mixed.astype(t.dtype), t)

            theta = jax.tree.map(one, theta)
    return theta


def _flooding_body(plan: GossipPlan, theta: PyTree, codec=None) -> PyTree:
    """Baseline: broadcast everything to everyone (all_gather), then mean.

    With a codec the gathered *values* are the decode(encode(·)) roundtrip
    (all_gather itself moves dense buffers; per-peer encoded transport needs
    the permute-based modes)."""
    if plan.n_nodes == 1:
        return theta
    ax = _axis_name(plan.node_axes)

    def one(t):
        tw = t if codec is None else codec.jax_roundtrip(t).astype(t.dtype)
        allm = jax.lax.all_gather(tw, ax)  # (N, ...)
        return jnp.mean(allm.astype(jnp.float32), axis=0).astype(t.dtype)

    with jax.named_scope(scopes.gossip_step(0)):  # one all-gather
        return jax.tree.map(one, theta)


def _allreduce_ref_body(plan: GossipPlan, theta: PyTree) -> PyTree:
    if plan.n_nodes == 1:
        return theta
    ax = _axis_name(plan.node_axes)
    return jax.tree.map(
        lambda t: (jax.lax.psum(t.astype(jnp.float32), ax) / plan.n_nodes).astype(t.dtype),
        theta,
    )


GOSSIP_BODIES: Dict[str, Callable] = {
    "tree_allreduce": _tree_allreduce_body,
    "dissemination": lambda plan, theta: _dissemination_body(plan, theta)[0],
    "segmented": _segmented_body,
    "mixing": _mixing_body,
    "flooding": _flooding_body,
    "allreduce_ref": _allreduce_ref_body,
}

# modes whose wire a payload codec can encode (per-hop or pre-gather)
CODEC_MODES = ("dissemination", "segmented", "tree_allreduce", "flooding")


def gossip_exchange(
    mode: str,
    plan: GossipPlan,
    mesh: Mesh,
    params: PyTree,
    param_specs: PyTree,
    wire_dtype=None,
    codec=None,
    ef_state: Optional[PyTree] = None,
) -> PyTree:
    """Apply one MOSGU communication round to a sharded parameter pytree.

    `param_specs` is the PartitionSpec tree the params carry under `jit`;
    shard_map re-exposes the per-device views so ppermute runs over the node
    axes while "model"-sharded dimensions stay device-local.

    ``codec`` (a :class:`repro.compress.Codec`) makes the collective permute
    *encoded* buffers (int8 codes + scales, packed top-k pairs) instead of
    raw tensors. ``ef_state`` — a pytree of f32 residuals mirroring
    ``params`` — enables error feedback for sparsifying codecs
    (dissemination mode only); the call then returns ``(out, new_ef_state)``.
    """
    if mode not in GOSSIP_BODIES:
        raise ValueError(f"unknown gossip mode {mode!r}; known: {sorted(GOSSIP_BODIES)}")
    if codec is not None and getattr(codec, "name", "") == "fp32":
        codec = None  # identity: the plain wire
    if codec is not None and mode not in CODEC_MODES:
        raise ValueError(
            f"gossip mode {mode!r} does not support a payload codec; "
            f"codec-capable modes: {CODEC_MODES}")
    if ef_state is not None:
        if codec is None:
            raise ValueError("ef_state needs a (lossy) payload codec")
        if mode != "dissemination":
            raise ValueError("error feedback is supported for the "
                             "dissemination mode only")

        def ef_body(theta, ef):
            mean, _, new_ef = _dissemination_body(plan, theta, codec=codec, ef=ef)
            return mean, new_ef

        fn = jax.shard_map(ef_body, mesh=mesh, in_specs=(param_specs, param_specs),
                           out_specs=(param_specs, param_specs), check_vma=False)
        return fn(params, ef_state)
    if mode == "tree_allreduce" and (wire_dtype is not None or codec is not None):
        body = partial(_tree_allreduce_body, plan, wire_dtype=wire_dtype,
                       codec=codec)
    elif codec is not None and mode == "dissemination":
        def body(theta):
            return _dissemination_body(plan, theta, codec=codec)[0]
    elif codec is not None and mode in ("segmented", "flooding"):
        body = partial(GOSSIP_BODIES[mode], plan, codec=codec)
    else:
        body = partial(GOSSIP_BODIES[mode], plan)
    fn = jax.shard_map(body, mesh=mesh, in_specs=(param_specs,),
                       out_specs=param_specs, check_vma=False)
    return fn(params)


def gossip_collective_bytes(mode: str, plan: GossipPlan, param_bytes: int,
                            codec=None) -> float:
    """Analytic bytes-on-wire per round (whole-network, one direction).

    With a codec each transfer carries the codec's exact encoding of its
    payload — the same :func:`repro.compress.per_send_wire_mb` formula the
    host executors use, so cross-executor byte accounting agrees.
    """
    from ..compress import per_send_wire_mb  # numpy-only, no cycle

    if plan.n_nodes == 1:
        return 0.0

    def total(transmissions: int, fraction: float = 1.0) -> float:
        return transmissions * per_send_wire_mb(
            codec, param_bytes / 1e6, fraction) * 1e6

    if mode == "dissemination":
        return total(plan.dissemination.total_transmissions())
    if mode == "segmented":
        if plan.segmented is None:
            return total(plan.dissemination.total_transmissions())
        # S× the transfers at 1/S the bytes each (same raw total; the codec's
        # per-chunk overhead applies per segment)
        return total(plan.segmented.total_transmissions(),
                     plan.segmented.payload_fraction)
    if mode == "tree_allreduce":
        return total(plan.tree.total_transmissions())
    if mode == "mixing":
        return total(2 * len(plan.mst.edges()))
    if mode == "flooding":
        # all_gather: every node receives N-1 replicas
        return total(plan.n_nodes * (plan.n_nodes - 1))
    if mode == "allreduce_ref":
        # ring all-reduce: 2(N-1)/N per node
        return total(2 * (plan.n_nodes - 1))
    raise ValueError(mode)
