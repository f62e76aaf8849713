"""The plain references against the program, at a size the CPU runs."""
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

import checks
import silos
from reference import dense, mean
from tiny import PROGRAM_SIZES, TINY_SIZES
from trees import node_tree, seed_key

CONFIG = dict(TINY_SIZES, rope_theta=10000.0, rms_norm_eps=1e-6)
OPT = dense.AdamW(lr=3e-4, warmup=0, total_steps=10000, final_frac=0.1, b1=0.9, b2=0.95,
                  eps=1e-8, weight_decay=0.1, max_grad_norm=1.0)
M = dense.Dense.from_config(CONFIG)


def program(dtype: str):
    from repro.configs import get_arch
    from repro.dfl import DFLConfig, DFLTrainer
    from repro.launch.mesh import make_local_mesh
    from repro.models import build_model

    arch = get_arch("smollm-360m").replace(**PROGRAM_SIZES, dtype=dtype, remat=False)
    return DFLTrainer(build_model(arch), make_local_mesh((1, 1), ("data", "model")),
                      DFLConfig(lr=OPT.lr, warmup=OPT.warmup, total_steps=OPT.total_steps))


def batches(n, seed=3):
    return silos.batch_pool(vocab=M.vocab, seq_len=32, batch_per_node=4, n_nodes=1,
                            seed=seed, size=n, dirichlet_alpha=0.5, zipf_s=1.2)


def test_weights_drawn_as_the_program_draws_them():
    key = seed_key(2**33 + 1)
    prog = program("bfloat16").init_state(key).params
    ref = dense.init_params(key, M)
    assert jax.tree.structure(prog) == jax.tree.structure(ref)
    for a, b in zip(jax.tree.leaves(prog), jax.tree.leaves(ref)):
        np.testing.assert_array_equal(np.asarray(a, np.float32), np.asarray(b))


def test_three_steps_match_the_program_in_float32():
    """Same weights, same batches, the program's float32 path: losses, first
    gradients and changes agree to float32 rounding."""
    from repro.models import Batch

    trainer = program("float32")
    state = trainer.init_state(seed_key(7))
    params = jax.tree.map(jnp.copy, state.params)
    step = trainer.jitted_train_step(jax.eval_shape(lambda: state), Batch(
        tokens=jax.ShapeDtypeStruct((4, 32), jnp.int32),
        labels=jax.ShapeDtypeStruct((4, 32), jnp.int32)))
    prog = {"losses": [], "first_grad": None}
    ref = {"losses": [], "first_grad": None}
    mom, vel = (jax.tree.map(jnp.zeros_like, params) for _ in range(2))
    p0 = jax.tree.map(jnp.copy, params)
    for t, (tok, lab) in enumerate(batches(3)):
        state, metrics = step(state, Batch(tokens=jnp.asarray(tok), labels=jnp.asarray(lab)))
        prog["losses"].append(float(metrics["loss"]))
        loss, grads = dense.clipped_grads(params, jnp.asarray(tok), jnp.asarray(lab),
                                          m=M, opt=OPT, variant="f32")
        ref["losses"].append(float(loss))
        if t == 0:
            prog["first_grad"] = dense.leaf_dict(jax.tree.map(
                lambda g: g / (1 - OPT.b1), dense.leaf_norms(state.opt_state["m"])))
            ref["first_grad"] = dense.leaf_dict(dense.leaf_norms(grads))
        params, mom, vel = dense.adamw(params, grads, mom, vel, jnp.asarray(t), opt=OPT)
    prog["change"] = dense.leaf_dict(dense.leaf_norms(jax.tree.map(
        jnp.subtract, state.opt_state["master"], p0)))
    ref["change"] = dense.leaf_dict(dense.leaf_norms(jax.tree.map(jnp.subtract, params, p0)))
    gaps = checks.train_numbers(prog, ref)
    assert gaps["loss_gap"] < 1e-5
    assert gaps["grad_norm_gap"] < 1e-4
    assert gaps["update_norm_gap"] < 1e-3


def test_node_mean_matches_the_program_round():
    from repro.dfl.collectives import GossipPlan, gossip_exchange
    from repro.dfl.sharding import named
    from repro.launch.mesh import make_local_mesh

    mesh = make_local_mesh((4, 1), ("data", "model"))
    plan = GossipPlan.build(mesh, ("data",))
    shapes = [(8, 16), (3,), (2, 5, 4)]
    specs = [P("data")] * 3
    key = seed_key(11)
    theta = jax.jit(lambda k: node_tree(k, shapes, 4), out_shardings=named(mesh, specs))(key)
    out = jax.jit(lambda t: gossip_exchange("dissemination", plan, mesh, t, specs))(theta)
    assert mean.mean_gap(key, out, shapes, 4) < 1e-6
    assert mean.mean_gap(key, theta, shapes, 4) > 0.1  # the inputs differ per node
