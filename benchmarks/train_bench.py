"""End-to-end DFL step timing on the local (CPU) mesh with reduced configs:
gossip-mode overhead per step, which the paper's tables measure at the
network level."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.configs import get_arch
from repro.data import DataConfig, FederatedData
from repro.dfl import DFLConfig, DFLTrainer
from repro.launch.mesh import make_local_mesh
from repro.models import Batch, build_model


def run(csv_rows):
    cfg = get_arch("smollm-360m").smoke_variant()
    model = build_model(cfg)
    mesh = make_local_mesh((1, 1), ("data", "model"))
    data = FederatedData(DataConfig(vocab=cfg.vocab, seq_len=64,
                                    batch_per_node=4, n_nodes=1))
    tok, lab = data.global_batch()
    batch = Batch(tokens=jnp.asarray(tok), labels=jnp.asarray(lab))
    for mode in ("tree_allreduce", "dissemination", "flooding", "mixing"):
        trainer = DFLTrainer(model, mesh, DFLConfig(gossip_mode=mode))
        state = trainer.init_state(jax.random.PRNGKey(0))
        step = trainer.jitted_train_step(jax.eval_shape(lambda: state),
                                         jax.eval_shape(lambda: batch))
        state, m = step(state, batch)  # compile
        t0 = time.time()
        for _ in range(3):
            state, m = step(state, batch)
        jax.block_until_ready(m["loss"])
        us = (time.time() - t0) / 3 * 1e6
        csv_rows.append((f"train_step/smoke/{mode}", us,
                         f"loss{float(m['loss']):.3f}"))
