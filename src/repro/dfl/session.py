"""DFL session: the paper's M-step wired into the device runtime.

`DFLSession` owns the moderator lifecycle around a `DFLTrainer`:

  * each communication round the moderator role rotates (paper III-A —
    votes tallied by the current moderator),
  * node churn (join/leave) marks the connection table dirty; the next
    round the moderator recomputes MST + coloring + slot plan and the
    session *re-compiles* the train step against the new `GossipPlan` —
    the TPU equivalent of re-broadcasting the neighbour table,
  * without churn the cached compiled step is reused (the paper's
    "moderator solely serves as the node keeping the connection
    information").

On a fixed TPU mesh, a "leaving" node's chips don't physically vanish;
the session models failed/drained replica groups by *masking* them out of
the gossip graph: the MST spans only healthy nodes, the FedAvg divides by
the healthy count, and masked nodes keep training locally but neither send
nor receive (they rejoin with the next churn event, as in the paper's
retransmission-on-reconnect story).
"""
from __future__ import annotations

import warnings
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Set, Tuple

import jax
import numpy as np

from ..core.graph import Graph, build_mst, color_graph
from ..core.moderator import ConnectivityReport, Moderator
from ..core.plan import SegmentedGossipPolicy, compile_policy
from ..scenario.spec import ChurnEvent, ScenarioSpec, applicable_churn
from ..core.schedule import compile_dissemination, compile_tree_allreduce, decompose_matchings, plan_to_perm_steps
from .collectives import GossipPlan, make_node_graph
from .trainer import DFLConfig, DFLTrainer


def _plan_for_members(mesh, node_axes, members: Set[int],
                      n_segments: int = 4,
                      full_graph: Optional[Graph] = None) -> GossipPlan:
    """GossipPlan over a *subset* of mesh nodes (churn masking).

    The MST/coloring runs on the healthy subgraph; perms are then relabelled
    back to physical node ids so ppermute still addresses real devices.
    ``full_graph`` overrides the mesh-derived cost graph — the scenario
    runner (:mod:`repro.scenario`) passes the declared overlay here so the
    compiled collectives execute the *scenario's* schedule, not a separate
    mesh-cost model.
    """
    full = (full_graph if full_graph is not None else
            make_node_graph(mesh, tuple(a for a in node_axes if a in mesh.shape)))
    members_sorted = sorted(members)
    index = {nid: i for i, nid in enumerate(members_sorted)}
    sub = Graph(full.adj[np.ix_(members_sorted, members_sorted)])
    mst_sub = build_mst(sub, "prim")
    colors_sub = color_graph(mst_sub, "bfs")
    # relabel to physical ids
    n_phys = full.n
    adj = np.zeros((n_phys, n_phys))
    for u, v, c in mst_sub.edges():
        pu, pv = members_sorted[u], members_sorted[v]
        adj[pu, pv] = adj[pv, pu] = c
    mst_phys = Graph(adj)
    colors_phys = -np.ones(n_phys, dtype=np.int64)
    for i, nid in enumerate(members_sorted):
        colors_phys[nid] = colors_sub[i]
    # compiled plans index payloads by subgraph position; buffer bodies need
    # the physical-id -> subgraph-row map (-1 = masked out of the round)
    node_slot = -np.ones(n_phys, dtype=np.int32)
    for i, nid in enumerate(members_sorted):
        node_slot[nid] = i

    # compile plans over the subgraph, then relabel slot endpoints to physical
    # node ids (payload ids stay subgraph-indexed — the buffer-row space; see
    # GossipPlan.node_slot). Re-homing plan.n to the physical axis makes the
    # lowered PermStep arrays physical-id indexed, as ppermute requires.
    def relabel(plan):
        for slot in plan.slots:
            slot.sends = [(members_sorted[s], members_sorted[d], p)
                          for (s, d, p) in slot.sends]
        plan.n = n_phys
        plan.colors = colors_phys
        return plan

    diss = relabel(compile_dissemination(mst_sub, colors_sub))
    tree = relabel(compile_tree_allreduce(mst_sub, colors_sub))
    seg = None
    if mst_sub.n > 1:
        seg = relabel(compile_policy(
            SegmentedGossipPolicy(mst_sub, colors_sub, segments=n_segments),
            record_traces=False))
    n_red_slots = tree.n_reduce_slots  # type: ignore[attr-defined]
    red_steps = sum(
        len([m for m in decompose_matchings(s.sends) if m])
        for s in tree.slots[:n_red_slots]
    )
    matchings = decompose_matchings(
        [(u, v, 0) for u, v, _ in mst_phys.edges()])
    plan = GossipPlan(
        n_nodes=len(members_sorted),
        node_axes=tuple(a for a in node_axes if a in mesh.shape),
        mst=mst_phys,
        colors=colors_phys,
        dissemination=diss,
        tree=tree,
        diss_steps=plan_to_perm_steps(diss),
        tree_steps=plan_to_perm_steps(tree),
        n_tree_reduce_steps=red_steps,
        mixing_matchings=[[(u, v) for u, v, _ in m] for m in matchings],
        segmented=seg,
        seg_steps=plan_to_perm_steps(seg) if seg is not None else [],
        n_segments=n_segments,
        node_slot=node_slot,
    )
    # ppermute still runs over the FULL physical axis; masked nodes simply
    # never appear as sources/targets, and the mean divides by len(members):
    plan.phys_n_nodes = _mesh_nodes(mesh, node_axes)  # type: ignore[attr-defined]
    return plan


def _mesh_nodes(mesh, node_axes) -> int:
    return int(np.prod([mesh.shape[a] for a in node_axes if a in mesh.shape]) or 1)


@dataclass
class DFLSession:
    """Training session with moderator rotation and churn handling.

    ``scenario`` (a :class:`repro.scenario.spec.ScenarioSpec`) makes churn
    declarative: the spec's ``leave``/``rejoin`` events fire automatically at
    their pinned rounds inside :meth:`train_round`, so an experiment's churn
    schedule is stated once and shared with the host-side executors
    (:func:`repro.scenario.run_scenario`) instead of being hand-scripted
    against :meth:`node_leaves` / :meth:`node_rejoins`.
    """

    trainer: DFLTrainer
    moderator: Moderator = None  # type: ignore[assignment]
    round_idx: int = 0
    members: Set[int] = field(default_factory=set)
    scenario: Optional[ScenarioSpec] = None
    steps: int = 0  # steps dispatched by this session (the profiler's step_num)
    _step_fn: Any = None
    _dirty: bool = True

    def __post_init__(self):
        n = _mesh_nodes(self.trainer.mesh, self.trainer.cfg.node_axes)
        self.members = set(range(n))
        self.moderator = Moderator(0)
        self._report_all()

    # -- M: manage connectivity ------------------------------------------------
    def _report_all(self) -> None:
        g = make_node_graph(self.trainer.mesh,
                            tuple(a for a in self.trainer.cfg.node_axes
                                  if a in self.trainer.mesh.shape))
        for u in sorted(self.members):
            costs = {v: float(g.adj[u, v]) for v in sorted(self.members) if v != u}
            self.moderator.receive_report(
                ConnectivityReport(u, f"node{u}", costs))
        self._dirty = True

    def node_leaves(self, node_id: int) -> None:
        if node_id not in self.members or len(self.members) <= 2:
            raise ValueError("cannot drop below 2 healthy nodes")
        self.members.discard(node_id)
        self.moderator.remove_node(node_id)
        self._dirty = True

    def node_rejoins(self, node_id: int) -> None:
        self.members.add(node_id)
        self._report_all()

    def apply_scheduled_churn(self) -> List[ChurnEvent]:
        """Fire the scenario's churn events pinned to the current round.

        Events that cannot fire on this mesh (node id beyond the mesh's node
        count, a leave that would drop below 2 healthy nodes, or a
        redundant leave/rejoin) are skipped with a warning so a partially
        applicable schedule is never silently misattributed.
        """
        if self.scenario is None:
            return []
        n = _mesh_nodes(self.trainer.mesh, self.trainer.cfg.node_axes)
        applied, skipped = applicable_churn(
            self.scenario.churn, self.round_idx, self.members, n_limit=n)
        for ev in skipped:
            warnings.warn(
                f"scenario {self.scenario.name!r}: churn event {ev} "
                f"skipped (mesh has {n} nodes, healthy={sorted(self.members)})",
                stacklevel=2)
        for ev in applied:
            if ev.action == "leave":
                self.node_leaves(ev.node)
            else:
                self.node_rejoins(ev.node)
        return applied

    def rotate_moderator(self, votes: Optional[Dict[int, int]] = None) -> int:
        votes = votes or {u: (self.round_idx + 1) % max(len(self.members), 1)
                          for u in self.members}
        nxt = self.moderator.elect_next(votes)
        self.moderator = self.moderator.handover(nxt)
        return nxt

    # -- O/S: replan + recompile on churn ---------------------------------------
    def _ensure_plan(self, state_shapes, batch_shapes) -> None:
        if not self._dirty and self._step_fn is not None:
            return
        n_segments, full_graph = 4, None
        if self.scenario is not None:
            n_segments = self.scenario.n_segments
            n = _mesh_nodes(self.trainer.mesh, self.trainer.cfg.node_axes)
            if self.scenario.n == n:
                # the declared overlay maps 1:1 onto the mesh nodes: compile
                # the scenario's schedule, not the mesh-derived cost model
                full_graph = self.scenario.overlay_graph()
        self.trainer.plan = _plan_for_members(
            self.trainer.mesh, self.trainer.cfg.node_axes, self.members,
            n_segments=n_segments, full_graph=full_graph)
        self._step_fn = self.trainer.jitted_train_step(state_shapes, batch_shapes)
        self._dirty = False

    # -- GU: one communication round --------------------------------------------
    def train_round(self, state, batch, local_steps: int = 1):
        """Run `local_steps` steps (each with gossip when interval==1), then
        rotate the moderator — one full paper round. Scenario-scheduled churn
        for this round fires first (replan + recompile happen below)."""
        from .. import obs

        rec = obs.get()
        self.apply_scheduled_churn()
        state_shapes = jax.eval_shape(lambda: state)
        batch_shapes = jax.eval_shape(lambda: batch)
        if rec.enabled and (self._dirty or self._step_fn is None):
            with rec.span("plan:recompile", cat="plan", track="train",
                          round=self.round_idx, members=len(self.members)):
                self._ensure_plan(state_shapes, batch_shapes)
        else:
            self._ensure_plan(state_shapes, batch_shapes)
        metrics = None
        every = max(self.trainer.dfl.gossip_interval, 1)
        # the step gossips when (state.step + 1) % every == 0; read the
        # device's step count only when a span must say so
        first = int(state.step) if rec.enabled and every > 1 else 0
        for i in range(local_steps):
            with jax.profiler.StepTraceAnnotation("train", step_num=self.steps):
                if rec.enabled:
                    # the span times the dispatch (the step runs on after it);
                    # the gossip exchange is fused into the jitted step
                    with rec.span("train:dispatch", cat="train", track="train",
                                  round=self.round_idx,
                                  gossip=(first + i + 1) % every == 0):
                        state, metrics = self._step_fn(state, batch)
                else:
                    state, metrics = self._step_fn(state, batch)
            self.steps += 1
        self.round_idx += 1
        self.rotate_moderator()
        return state, metrics


def run_scenario_rounds(session: DFLSession, state, batch,
                        make_batch: Optional[Callable[[], Any]] = None,
                        log: Callable[[str], None] = print):
    """Drive a session for its scenario's round count — the shared loop
    behind ``launch/train.py --scenario`` and ``examples/train_dfl.py
    --scenario`` (churn fires inside :meth:`DFLSession.train_round`)."""
    rounds = session.scenario.rounds if session.scenario is not None else 1
    metrics = None
    for i in range(rounds):
        state, metrics = session.train_round(state, batch)
        if make_batch is not None:
            batch = make_batch()
        log(f"round {i + 1:3d} loss={float(metrics['loss']):.4f} "
            f"members={sorted(session.members)} "
            f"moderator={session.moderator.moderator_id}")
    return state, metrics
