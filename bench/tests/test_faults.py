"""Whole runs of the harness at a CPU's size, without its look for a chip:
sound, and with the timed path broken underneath in each way the cell can
break, where ``correct`` has to come out false."""
import time

import jax
import jax.numpy as jnp
import pytest

import harness
import tiny

TRAIN = next(w for w in harness.load_json(harness.ROOT / "BENCHMARK.json")["workloads"]
             if w["name"] == "smollm-360m.train.s2048b8")
GOSSIP = tiny.GOSSIP


def run(workload, monkeypatch):
    cell = tiny.cell(workload, monkeypatch)
    return harness.execute(cell, 2**32 + 9, 0.5, False, jax.devices()[:cell.chips],
                           time.perf_counter())[0]


@pytest.mark.parametrize("workload", [TRAIN, GOSSIP], ids=lambda w: w["name"])
def test_sound_run_is_correct(workload, monkeypatch):
    r = run(workload, monkeypatch)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] >= 1
    assert list(r)[-1] == "checks"
    assert set(r["metrics"]) == set(tiny.END_TO_END[tiny.cell(workload, monkeypatch)
                                                    .traffic["driver"]]) | {"peak_hbm_gib", "setup_s"}


def _state_unchanged(monkeypatch):
    from repro.dfl.trainer import DFLTrainer

    build = DFLTrainer.train_step_fn

    def broken(self):
        fn = build(self)
        return lambda state, batch, param_specs: (state, fn(state, batch, param_specs)[1])

    monkeypatch.setattr(DFLTrainer, "train_step_fn", broken)


def _half_batch(monkeypatch):
    from repro.models import Batch, Model

    loss = Model.train_loss

    def broken(self, params, batch):
        h = batch.tokens.shape[0] // 2
        return loss(self, params, Batch(tokens=batch.tokens[:h], labels=batch.labels[:h]))

    monkeypatch.setattr(Model, "train_loss", broken)


def _gradient_altered(monkeypatch):
    """The largest leaf's gradient 5% off, where the step produces it."""
    from repro.dfl import trainer

    clip = trainer.clip_by_global_norm

    def broken(grads, max_norm):
        grads, norm = clip(grads, max_norm)
        leaves, treedef = jax.tree.flatten(grads)
        big = max(range(len(leaves)), key=lambda i: leaves[i].size)
        leaves[big] = leaves[big] * 1.05
        return treedef.unflatten(leaves), norm

    monkeypatch.setattr(trainer, "clip_by_global_norm", broken)


@pytest.mark.parametrize("plant", [_state_unchanged, _half_batch, _gradient_altered])
def test_broken_step_is_not_correct(plant, monkeypatch):
    plant(monkeypatch)
    assert not run(TRAIN, monkeypatch)["correct"]


def _round_unchanged(monkeypatch):
    from repro.dfl import collectives

    monkeypatch.setattr(collectives, "gossip_exchange", lambda mode, plan, mesh, t, specs: t)


def _half_the_nodes(monkeypatch):
    from repro.dfl import collectives

    def body(plan, theta):
        _, buf, _ = collectives._dissemination_body(plan, theta)
        return jax.tree.map(lambda b: jnp.mean(b[: plan.n_nodes // 2], axis=0), buf)

    monkeypatch.setitem(collectives.GOSSIP_BODIES, "dissemination", body)


def _exchange_left_out(monkeypatch):
    monkeypatch.setattr(jax.lax, "ppermute", lambda x, axis_name, perm: jnp.zeros_like(x))


def _answer_altered(monkeypatch):
    from repro.dfl import collectives

    exchange = collectives.gossip_exchange

    def broken(*args, **kw):
        out = exchange(*args, **kw)
        leaves, treedef = jax.tree.flatten(out)
        return treedef.unflatten([leaves[0].at[0].add(1e-2)] + leaves[1:])

    monkeypatch.setattr(collectives, "gossip_exchange", broken)


@pytest.mark.parametrize("plant", [_round_unchanged, _half_the_nodes, _exchange_left_out,
                                   _answer_altered])
def test_broken_round_is_not_correct(plant, monkeypatch):
    plant(monkeypatch)
    assert not run(GOSSIP, monkeypatch)["correct"]
