"""Driver of the training cells of a stack of Mamba2 and attention layers
(granite-4.0-h): ``DFLTrainer.jitted_train_step`` on its mesh.

The contract and the timed window are those of ``drivers/train.py``, whose
window, facts, release, check and control this driver inherits. What differs
is the configuration's program arch, checked against the file's Mamba2 and
attention sizes, layer order and multipliers as well; the model FLOPs
(``counts_hybrid.py``); the plain reference (``reference/hybrid.py``); and the
faults planted in it, since a batch of one row has no half.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding

import checks
import counts_hybrid
import silos
from arch import program_config as dense_program_config
from drivers import train
from reference import dense, hybrid
from trees import seed_key

FAULTS = ("chunk_reset", "ungated_norm")


def program_config(c: dict):
    """The program's ``ArchConfig`` for the file ``c``: ``arch.program_config``'s
    checks, then the Mamba2 mixer's sizes, the order of the layers, positions
    and the multipliers; exits where one differs from the file's."""
    from repro.models.mamba import MAMBA2_HEAD_DIM

    arch = dense_program_config(c)
    n, pattern = c["num_hidden_layers"], arch.layer_pattern
    expect = {
        "layers": "".join("A" if t == "attention" else "M" for t in c["layer_types"][:n]),
        "ssm_version": 2, "ssm_state": c["mamba_d_state"], "ssm_groups": c["mamba_n_groups"],
        "ssm_heads": c["mamba_n_heads"], "ssm_head_dim": c["mamba_d_head"],
        "conv_width": c["mamba_d_conv"], "mlp": c["shared_intermediate_size"],
        "use_rope": c["position_embedding_type"] != "nope",
        "embedding_multiplier": c["embedding_multiplier"],
        "residual_multiplier": c["residual_multiplier"],
        "attention_multiplier": c["attention_multiplier"],
        "logits_scaling": c["logits_scaling"],
    }
    got = {
        "layers": pattern * (n // len(pattern)) if pattern and n % len(pattern) == 0 else None,
        "ssm_version": arch.ssm_version, "ssm_state": arch.ssm_state,
        "ssm_groups": arch.ssm_groups, "ssm_heads": arch.d_inner // MAMBA2_HEAD_DIM,
        "ssm_head_dim": MAMBA2_HEAD_DIM, "conv_width": arch.conv_width, "mlp": arch.d_ff,
        "use_rope": arch.use_rope, "embedding_multiplier": arch.embedding_multiplier,
        "residual_multiplier": arch.residual_multiplier,
        "attention_multiplier": arch.attention_multiplier,
        "logits_scaling": arch.logits_scaling,
    }
    wrong = {k: (got[k], v) for k, v in expect.items() if got[k] != v}
    if wrong:
        raise SystemExit(f"{c['program_arch']}: the program's config differs from the "
                         f"benchmark's file (program, file): {wrong}")
    return arch


class Driver(train.Driver):
    def __init__(self, cell, seed: int):
        from repro.dfl import DFLConfig, DFLTrainer
        from repro.launch.mesh import make_local_mesh
        from repro.models import Batch, build_model

        c, t = cell.config, cell.traffic
        o = t["optimizer"]
        self.cell, self.key = cell, seed_key(seed)
        trainer = DFLTrainer(
            build_model(program_config(c)), make_local_mesh(tuple(t["mesh"]), ("data", "model")),
            DFLConfig(gossip_mode=t["gossip_mode"], gossip_interval=t["gossip_interval"],
                      max_grad_norm=o["max_grad_norm"], lr=o["lr"], warmup=o["warmup"],
                      total_steps=o["total_steps"]))
        pool = silos.batch_pool(vocab=c["vocab_size"], seq_len=t["seq_len"],
                                batch_per_node=t["batch_per_node"],
                                n_nodes=trainer.plan.n_nodes, seed=seed, size=t["pool"],
                                **t["silos"])
        self.check_batches = pool[: t["check_steps"]]
        self.tokens_per_step = pool[0][0].size
        shapes = Batch(tokens=jax.ShapeDtypeStruct(pool[0][0].shape, jnp.int32),
                       labels=jax.ShapeDtypeStruct(pool[0][1].shape, jnp.int32))
        specs = trainer.batch_specs(shapes)
        mesh = trainer.mesh
        self.batches = [Batch(tokens=jax.device_put(tok, NamedSharding(mesh, specs.tokens)),
                              labels=jax.device_put(lab, NamedSharding(mesh, specs.labels)))
                        for tok, lab in pool]

        state = trainer.init_state(self.key)
        self.step = trainer.jitted_train_step(jax.eval_shape(lambda: state), shapes).lower(
            state, self.batches[0]).compile()
        change = jax.jit(lambda key, master: dense.leaf_norms(jax.tree.map(
            lambda m, p: m - p.astype(jnp.float32), master, trainer.model.init(key))))

        losses, first_grad = [], None
        for i in range(t["check_steps"]):
            state, metrics = self.step(state, self.batches[i])
            losses.append(metrics["loss"])
            if first_grad is None:
                first_grad = jax.tree.map(lambda g: g / (1 - o["b1"]),
                                          dense.leaf_norms(state.opt_state["m"]))
        self.readings = {"losses": [float(x) for x in losses],
                         "first_grad": dense.leaf_dict(first_grad),
                         "change": dense.leaf_dict(change(self.key, state.opt_state["master"]))}
        self.state, self.done = state, t["check_steps"]
        self.flops_per_step = counts_hybrid.train_flops_per_token(c, t["seq_len"]) \
            * self.tokens_per_step

    def _reference(self, variant: str = "f32") -> dict:
        return hybrid.run(self.key, self.check_batches, hybrid.Hybrid.from_config(self.cell.config),
                          dense.AdamW.from_traffic(self.cell.traffic), variant)

    def faults(self) -> dict:
        """The numbers of the faults planted in the reference; after ``check``."""
        return {f: checks.train_numbers(self._reference(f), self.ref) for f in FAULTS}
