"""Driver of the gossip cells: one ``gossip_exchange`` round after another.

Each DFL node holds its own float32 tree shaped as the configuration's master
weights, made on the device from the seed (``bench/trees.py``). The round is
jitted with its input donated, and each round's input is the previous round's
output, so the tree's values are the node mean from the first round on. The
check compares every node's final tree with the plain mean of the nodes'
inputs: a round that moved nothing, or the wrong thing, leaves it elsewhere.
"""
from __future__ import annotations

import gc
import time

import jax
import numpy as np
from jax.sharding import PartitionSpec as P

import counts
from arch import program_config
from reference import mean
from trees import node_tree, seed_key


class Driver:
    def __init__(self, cell, seed: int):
        from repro.dfl.collectives import GossipPlan, gossip_exchange
        from repro.dfl.sharding import named
        from repro.launch.mesh import make_local_mesh
        from repro.models import build_model

        t = cell.traffic
        self.n = n = t["nodes"]
        self.key = seed_key(seed)
        mesh = make_local_mesh((n, 1), ("data", "model"))
        plan = GossipPlan.build(mesh, ("data",))
        leaves, treedef = jax.tree.flatten(
            jax.eval_shape(build_model(program_config(cell.config)).init, jax.random.PRNGKey(0)))
        self.shapes = [tuple(x.shape) for x in leaves]
        self.tree_bytes = 4 * sum(int(np.prod(s)) for s in self.shapes)
        specs = treedef.unflatten([P("data")] * len(leaves))
        make = jax.jit(lambda key: treedef.unflatten(node_tree(key, self.shapes, n)),
                       out_shardings=named(mesh, specs))
        theta = make(self.key)
        self.round = jax.jit(lambda tree: gossip_exchange(t["mode"], plan, mesh, tree, specs),
                             donate_argnums=0).lower(theta).compile()
        self.wire_bytes = counts.collective_permute_bytes(self.round.as_text())
        for _ in range(t["warmup_rounds"]):
            theta = jax.block_until_ready(self.round(theta))
        self.theta = theta

    def window(self, seconds: float) -> dict:
        """Rounds back to back, each ended by ``block_until_ready``, until
        ``seconds`` have passed."""
        times = []
        t0 = time.perf_counter()
        while True:
            t1 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.dispatch"):
                theta = self.round(self.theta)
            with jax.profiler.TraceAnnotation("bench.wait"):
                self.theta = jax.block_until_ready(theta)
            times.append(time.perf_counter() - t1)
            if time.perf_counter() - t0 >= seconds:
                break
        elapsed = time.perf_counter() - t0
        return {"seconds": elapsed, "units": len(times), "failed": 0, "metrics": {
            "gossip_round_ms": 1e3 * elapsed / len(times),
            "gossip_round_ms_p90": 1e3 * float(np.percentile(times, 90))}}

    def facts(self) -> dict:
        return {"wire_bytes_per_node": self.wire_bytes / self.n,
                "required_bytes_per_node":
                    counts.dissemination_bytes_per_node(self.n, self.tree_bytes)}

    def release(self) -> None:
        del self.round
        gc.collect()

    def check(self) -> dict:
        gap = mean.mean_gap(self.key, jax.tree.leaves(self.theta), self.shapes, self.n)
        return {"mean_gap": gap}

    def control(self) -> dict:
        """The number of the control (bench/reference/mean.py) in the
        program's place."""
        return {"mean_gap": mean.control_gap(self.key, self.shapes, self.n)}

    def faults(self) -> dict:
        return {}
