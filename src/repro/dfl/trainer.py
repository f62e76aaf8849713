"""DFL trainer: per-silo local steps + MOSGU gossip rounds, one jitted step.

Each DFL node (a model-replica group of chips) computes grads on its own
silo's batch shard — there is *no* cross-node gradient all-reduce; the only
cross-node traffic is the gossip exchange of parameters every
`gossip_interval` steps, exactly the paper's training paradigm. Within a
node, tensor parallelism over "model" is handled by GSPMD from the sharding
recipe.

When the optimizer keeps fp32 master weights, gossip averages the *masters*
(and re-casts the working copy); otherwise it averages the params directly.
Optimizer moments stay local to each silo (standard FedAvg practice).
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..compress import make_codec
from ..configs.base import ArchConfig
from ..models.model import Batch, Model
from ..obs import scopes
from ..optim.optimizers import Optimizer, clip_by_global_norm, global_norm, make_optimizer
from .collectives import GossipPlan, gossip_exchange
from .sharding import batch_axes, batch_spec, named, param_spec_tree

PyTree = Any


@jax.tree_util.register_dataclass
@dataclass
class TrainState:
    params: PyTree
    opt_state: PyTree
    step: jax.Array


@dataclass
class DFLConfig:
    gossip_mode: str = "tree_allreduce"  # see collectives.GOSSIP_BODIES
    gossip_interval: int = 1  # local steps between gossip rounds
    max_grad_norm: float = 1.0
    wire_dtype: str = ""  # "" = native; "bfloat16" compresses gossip payloads
    # payload codec for the gossip wire (repro.compress: "bf16", "int8",
    # "int4", "topk"; "" = raw). Sparsifying codecs carry an error-feedback
    # residual in opt_state["codec_ef"] so dropped coordinates are
    # compensated across rounds (dissemination mode).
    codec: str = ""
    lr: float = 3e-4
    warmup: int = 100
    total_steps: int = 10_000


class DFLTrainer:
    def __init__(
        self,
        model: Model,
        mesh: Mesh,
        dfl: Optional[DFLConfig] = None,
        optimizer: Optional[Optimizer] = None,
    ):
        self.model = model
        self.cfg: ArchConfig = model.cfg
        self.mesh = mesh
        self.dfl = dfl or DFLConfig()
        self.opt = optimizer or make_optimizer(
            self.cfg, self.dfl.lr, self.dfl.warmup, self.dfl.total_steps
        )
        self.plan = GossipPlan.build(mesh, self.cfg.node_axes)
        self.codec = make_codec(self.dfl.codec) if self.dfl.codec else None

    # -- sharding ----------------------------------------------------------
    def state_specs(self, state_shapes: TrainState) -> TrainState:
        pspec = param_spec_tree(self.cfg, state_shapes.params, self.mesh)
        ospec = jax.tree.map(
            lambda leaf: _opt_leaf_spec(leaf, state_shapes.params, pspec),
            state_shapes.opt_state,
        )
        # opt_state mirrors params per moment: map by matching structure
        ospec = _mirror_opt_specs(state_shapes.opt_state, state_shapes.params, pspec)
        return TrainState(params=pspec, opt_state=ospec, step=P())

    def batch_specs(self, batch_shapes: Batch) -> Batch:
        def spec(leaf):
            return batch_spec(self.mesh, leaf.shape[0], leaf.ndim) if leaf is not None else None

        return Batch(
            tokens=spec(batch_shapes.tokens),
            labels=spec(batch_shapes.labels),
            encoder_frames=spec(batch_shapes.encoder_frames),
            patch_embeddings=spec(batch_shapes.patch_embeddings),
        )

    # -- init ---------------------------------------------------------------
    def init_state(self, key: jax.Array) -> TrainState:
        def make(key):
            params = self.model.init(key)
            opt_state = self.opt.init(params)
            if (self.codec is not None and self.codec.error_feedback
                    and self.dfl.gossip_mode == "dissemination"):
                # per-node error-feedback residual: lives with the optimizer
                # state so it shards/donates/persists like the moments. Only
                # the dissemination collective supports EF; other codec modes
                # run the sparsifier without feedback.
                opt_state = dict(opt_state, codec_ef=jax.tree.map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), params))
            return TrainState(
                params=params,
                opt_state=opt_state,
                step=jnp.zeros((), jnp.int32),
            )

        shapes = jax.eval_shape(make, key)
        specs = self.state_specs(shapes)
        return jax.jit(make, out_shardings=named(self.mesh, specs))(key)

    # -- the step ------------------------------------------------------------
    def train_step_fn(self) -> Callable[[TrainState, Batch], Tuple[TrainState, Dict]]:
        model, opt, dfl, plan, mesh = self.model, self.opt, self.dfl, self.plan, self.mesh
        cfg, codec = self.cfg, self.codec

        def grads_of(params: PyTree, batch: Batch) -> Tuple[jax.Array, PyTree]:
            mb = max(int(cfg.microbatches), 1)
            if mb > 1 and batch.tokens.shape[0] % mb == 0:
                # gradient accumulation: sequential microbatches bound
                # activation memory; grads averaged in f32
                def split(t):
                    return (None if t is None else
                            t.reshape(mb, t.shape[0] // mb, *t.shape[1:]))

                micro = Batch(tokens=split(batch.tokens), labels=split(batch.labels),
                              encoder_frames=split(batch.encoder_frames),
                              patch_embeddings=split(batch.patch_embeddings))
                zero = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)

                def acc_body(carry, mb_batch):
                    loss_acc, g_acc = carry
                    l, g = jax.value_and_grad(model.train_loss)(params, mb_batch)
                    g_acc = jax.tree.map(
                        lambda a, x: a + x.astype(jnp.float32) / mb, g_acc, g)
                    return (loss_acc + l / mb, g_acc), None

                (loss, grads), _ = jax.lax.scan(
                    acc_body, (jnp.zeros((), jnp.float32), zero), micro)
                return loss, jax.tree.map(lambda g, p: g.astype(p.dtype), grads, params)
            return jax.value_and_grad(model.train_loss)(params, batch)

        def step_fn(state: TrainState, batch: Batch, param_specs: PyTree):
            with jax.named_scope(scopes.GRADS):
                loss, grads = grads_of(state.params, batch)
            with jax.named_scope(scopes.CLIP):
                grads, gnorm = clip_by_global_norm(grads, dfl.max_grad_norm)
            with jax.named_scope(scopes.UPDATE):
                params, opt_state = opt.update(state.params, grads, state.opt_state,
                                               state.step)
            if "codec_ef" in state.opt_state and "codec_ef" not in opt_state:
                # optimizers rebuild their state dict; carry the residual over
                opt_state = dict(opt_state, codec_ef=state.opt_state["codec_ef"])

            # MOSGU gossip round (every step when interval == 1; the common
            # dry-run/deployment configuration — interval > 1 wraps in cond)
            wire = jnp.bfloat16 if dfl.wire_dtype == "bfloat16" else None

            def exchange(theta, ef):
                if ef is not None:
                    return gossip_exchange(dfl.gossip_mode, plan, mesh, theta,
                                           param_specs, codec=codec, ef_state=ef)
                return gossip_exchange(dfl.gossip_mode, plan, mesh, theta,
                                       param_specs, wire_dtype=wire,
                                       codec=codec), None

            @scopes.scoped(scopes.GOSSIP)
            def do_gossip(params, opt_state):
                ef = opt_state.get("codec_ef")
                if "master" in opt_state:
                    master, new_ef = exchange(opt_state["master"], ef)
                    opt_state = dict(opt_state, master=master)
                    params = jax.tree.map(lambda m, p: m.astype(p.dtype), master, params)
                else:
                    params, new_ef = exchange(params, ef)
                if new_ef is not None:
                    opt_state = dict(opt_state, codec_ef=new_ef)
                return params, opt_state

            if dfl.gossip_interval <= 1:
                params, opt_state = do_gossip(params, opt_state)
            else:
                params, opt_state = jax.lax.cond(
                    (state.step + 1) % dfl.gossip_interval == 0,
                    lambda p, o: do_gossip(p, o),
                    lambda p, o: (p, o),
                    params,
                    opt_state,
                )
            new_state = TrainState(params=params, opt_state=opt_state, step=state.step + 1)
            return new_state, {"loss": loss, "grad_norm": gnorm}

        return step_fn

    def jitted_train_step(self, state_shapes: TrainState, batch_shapes: Batch):
        # sequence-parallel activations + expert-parallel dispatch constraints
        self.model.set_mesh_context(
            self.mesh, batch_axes(self.mesh, batch_shapes.tokens.shape[0])
        )
        specs = self.state_specs(state_shapes)
        bspecs = self.batch_specs(batch_shapes)
        pspec = specs.params
        fn = partial(self.train_step_fn(), param_specs=pspec)
        return jax.jit(
            fn,
            in_shardings=(named(self.mesh, specs), named(self.mesh, bspecs)),
            out_shardings=(named(self.mesh, specs), None),
            donate_argnums=(0,),
        )


def _opt_leaf_spec(leaf, params, pspec):  # pragma: no cover - replaced below
    return P()


def _mirror_opt_specs(opt_state: PyTree, params: PyTree, pspec: PyTree) -> PyTree:
    """Optimizer moments/master mirror the param tree -> reuse its specs."""
    param_treedef = jax.tree.structure(params)

    def mirror(sub):
        if jax.tree.structure(sub) == param_treedef:
            return pspec
        return jax.tree.map(lambda _: P(), sub)

    if isinstance(opt_state, dict):
        return {k: mirror(v) for k, v in opt_state.items()}
    return jax.tree.map(lambda _: P(), opt_state)


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------


def serve_step_fn(model: Model):
    """One decode step: (params, tokens(b,1), positions(b,), cache) -> logits."""

    def fn(params, tokens, positions, cache):
        return model.decode_step(params, tokens, positions, cache)

    return fn


def prefill_fn(model: Model):
    def fn(params, batch: Batch):
        logits, _ = model.forward(params, batch)
        return logits

    return fn
