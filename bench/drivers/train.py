"""Driver of the training cells: ``DFLTrainer.jitted_train_step`` on its mesh.

Set-up builds the trainer and its state from the seed, makes the traffic's
pool of batches and compiles the step. It then drives the first
``check_steps`` steps through the window's own compiled call, reading what
the check compares: each step's loss, each leaf's norm of the first gradient
as AdamW got it (its first moment over ``1 - b1``) and each leaf's norm of the
master weights' change over those steps. The window goes on from that state.
"""
from __future__ import annotations

import gc
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding

import checks
import counts
import silos
from arch import program_config
from reference import dense
from trees import seed_key


class Driver:
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
        self.flops_per_step = counts.dense_train_flops_per_token(c, t["seq_len"]) \
            * self.tokens_per_step

    def window(self, seconds: float) -> dict:
        """Steps back to back, one in flight behind the one being dispatched,
        until ``seconds`` have passed; every step ends inside the window."""
        losses, prev = [], None
        t0 = time.perf_counter()
        while True:
            with jax.profiler.TraceAnnotation("bench.dispatch"):
                self.state, metrics = self.step(
                    self.state, self.batches[self.done % len(self.batches)])
            self.done += 1
            losses.append(metrics["loss"])
            if prev is not None:
                with jax.profiler.TraceAnnotation("bench.wait"):
                    prev.block_until_ready()
            prev = metrics["loss"]
            if time.perf_counter() - t0 >= seconds:
                break
        with jax.profiler.TraceAnnotation("bench.wait"):
            jax.block_until_ready((self.state, prev))
        elapsed = time.perf_counter() - t0
        n = len(losses)
        failed = int(np.sum(~np.isfinite(np.asarray(jax.device_get(losses)))))
        return {"seconds": elapsed, "units": n, "failed": failed, "metrics": {
            "train_tokens_per_s": n * self.tokens_per_step / elapsed / self.cell.chips}}

    def facts(self) -> dict:
        return {"flops_per_step": self.flops_per_step}

    def release(self) -> None:
        del self.state, self.step, self.batches
        gc.collect()

    def _reference(self, variant: str = "f32") -> dict:
        return dense.run(self.key, self.check_batches, dense.Dense.from_config(self.cell.config),
                         dense.AdamW.from_traffic(self.cell.traffic), variant)

    def check(self) -> dict:
        self.ref = self._reference()
        return checks.train_numbers(self.readings, self.ref)

    def control(self) -> dict:
        """The numbers of the control (bench/reference/dense.py) in the
        program's place; after ``check``."""
        return checks.train_numbers(self._reference("fp8"), self.ref)

    def faults(self) -> dict:
        """The numbers of the faults planted in the reference; after ``check``."""
        return {"half_batch": checks.train_numbers(self._reference("half_batch"), self.ref)}
