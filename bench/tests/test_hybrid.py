"""granite-4.0-h's cell at a size the CPU runs: its plain reference
(``reference/hybrid.py``) against the program, the cell's ``Driver``
through the harness, the control and the faults planted in the reference,
each of which has to read ``correct`` false, and the FLOP count against a
hand count."""
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import checks
import counts_hybrid
import harness
import silos
from reference import dense, hybrid
from tiny import TINY_LIMITS
from trees import seed_key

CELL = "granite-4.0-h-micro.train.s8192b1"
B = harness.load_json(harness.ROOT / "BENCHMARK.json")
CONFIG = harness.load_json(harness.BENCH / "configs" / "granite-4.0-h-micro.json")
# two Mamba2 heads of the program's 64, state 16; attention as in tiny.py
TINY = {"hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "intermediate_size": 128, "shared_intermediate_size": 128, "vocab_size": 200,
        "mamba_n_heads": 2, "mamba_d_state": 16}
PROGRAM = {"n_layers": 10, "d_model": 64, "n_heads": 4, "n_kv_heads": 2, "head_dim": 16, "d_ff": 128,
           "vocab": 200, "ssm_state": 16}
SEQ = 320  # past the first 256-token chunk, where chunk_reset shows
M = hybrid.Hybrid.from_config(dict(CONFIG, **TINY))
OPT = dense.AdamW(lr=3e-4, warmup=0, total_steps=10000, final_frac=0.1, b1=0.9, b2=0.95,
                  eps=1e-8, weight_decay=0.1, max_grad_norm=1.0)


def program(dtype: str):
    from repro.configs import get_arch
    from repro.dfl import DFLConfig, DFLTrainer
    from repro.launch.mesh import make_local_mesh
    from repro.models import build_model

    arch = get_arch(CONFIG["program_arch"]).replace(**PROGRAM, dtype=dtype, remat=False)
    return DFLTrainer(build_model(arch), make_local_mesh((1, 1), ("data", "model")),
                      DFLConfig(lr=OPT.lr, warmup=OPT.warmup, total_steps=OPT.total_steps))


def batches(n, seed=3):
    return silos.batch_pool(vocab=M.vocab, seq_len=SEQ, batch_per_node=1, n_nodes=1,
                            seed=seed, size=n, dirichlet_alpha=0.5, zipf_s=1.2)


def test_weights_drawn_as_the_program_draws_them():
    key = seed_key(2**33 + 1)
    prog = program("bfloat16").init_state(key).params
    ref = jax.jit(partial(hybrid.init_params, m=M))(key)  # compiled, as ``hybrid.run`` draws them
    assert jax.tree.structure(prog) == jax.tree.structure(ref)
    for a, b in zip(jax.tree.leaves(prog), jax.tree.leaves(ref)):
        # the program stacks each run's layers under one more axis, of its periods
        np.testing.assert_array_equal(np.asarray(a, np.float32).reshape(b.shape), np.asarray(b))


def test_three_steps_match_the_program_in_float32():
    """Same weights, same batches, the program's float32 path (chunked SSD)
    against the reference (the recurrence step by step): losses, first
    gradients and changes agree to float32 rounding."""
    from repro.models import Batch

    trainer = program("float32")
    state = trainer.init_state(seed_key(7))
    # the program's float32 weights (not rounded to bfloat16), in the reference's shapes
    params = jax.tree.map(lambda p, r: jnp.copy(p.reshape(r.shape)), state.params,
                          jax.eval_shape(partial(hybrid.init_params, m=M), seed_key(7)))
    shape = jax.ShapeDtypeStruct((1, SEQ), jnp.int32)
    step = trainer.jitted_train_step(jax.eval_shape(lambda: state),
                                     Batch(tokens=shape, labels=shape))
    prog = {"losses": []}
    ref = {"losses": []}
    mom, vel = (jax.tree.map(jnp.zeros_like, params) for _ in range(2))
    p0 = jax.tree.map(jnp.copy, params)
    for t, (tok, lab) in enumerate(batches(3)):
        state, metrics = step(state, Batch(tokens=jnp.asarray(tok), labels=jnp.asarray(lab)))
        prog["losses"].append(float(metrics["loss"]))
        loss, grads = hybrid.clipped_grads(params, jnp.asarray(tok), jnp.asarray(lab),
                                           m=M, opt=OPT, variant="f32")
        ref["losses"].append(float(loss))
        if t == 0:
            prog["first_grad"] = dense.leaf_dict(jax.tree.map(
                lambda g: g / (1 - OPT.b1), dense.leaf_norms(state.opt_state["m"])))
            ref["first_grad"] = dense.leaf_dict(dense.leaf_norms(grads))
        params, mom, vel = dense.adamw(params, grads, mom, vel, jnp.asarray(t), opt=OPT)
    prog["change"] = dense.leaf_dict(dense.leaf_norms(jax.tree.map(
        lambda m, p: m.reshape(p.shape) - p, state.opt_state["master"], p0)))
    ref["change"] = dense.leaf_dict(dense.leaf_norms(jax.tree.map(jnp.subtract, params, p0)))
    gaps = checks.train_numbers(prog, ref)
    assert gaps["loss_gap"] < 1e-5
    assert gaps["grad_norm_gap"] < 1e-4
    assert gaps["update_norm_gap"] < 1e-3


def tiny_cell(monkeypatch) -> harness.Cell:
    """The cell at the tiny sizes above, 1 x 320 tokens, held to the tiny
    train limits; the program's registry gives the same sizes meanwhile."""
    import repro.configs as configs

    w = next(w for w in B["workloads"] if w["name"] == CELL)
    traffic = harness.load_json(harness.BENCH / "traffic" / f"{w['traffic']}.json")
    small = configs.get_arch(CONFIG["program_arch"]).replace(**PROGRAM)
    monkeypatch.setattr(configs, "get_arch", lambda _: small)
    return harness.Cell(name=CELL, chips=1, config_name=w["config"], config=dict(CONFIG, **TINY),
                        traffic_name=w["traffic"], traffic=dict(traffic, seq_len=SEQ, pool=4),
                        end_to_end=[{"name": n, "unit": "-"} for n in
                                    ("train_tokens_per_s", "peak_hbm_gib", "setup_s")],
                        per_layer=[], limits=dict(TINY_LIMITS["train"]))


def test_sound_run_is_correct(monkeypatch):
    cell = tiny_cell(monkeypatch)
    r = harness.execute(cell, 2**32 + 9, 0.5, False, jax.devices()[:1], time.perf_counter())[0]
    assert r["correct"] and r["failed"] == 0 and r["attempted"] >= 1
    assert set(r["metrics"]) == {"train_tokens_per_s", "peak_hbm_gib", "setup_s"}


@pytest.fixture(scope="module")
def checked():
    """The tiny cell's driver after its check, and the limits it is held to."""
    with pytest.MonkeyPatch.context() as mp:
        cell = tiny_cell(mp)
        drv = harness.driver_class(cell)(cell, 2**31 + 11)
    drv.release()
    return drv, drv.check(), cell.limits


def _fails(numbers: dict, limits: dict) -> bool:
    return any(limits[k] is not None and numbers[k] > limits[k] for k in numbers)


def test_checked_run_is_within_the_limits(checked):
    _, sound, limits = checked
    assert not _fails(sound, limits), sound


def test_control_fails_a_limit(checked):
    drv, _, limits = checked
    numbers = drv.control()
    assert _fails(numbers, limits), numbers


@pytest.mark.parametrize("fault", ["chunk_reset", "ungated_norm"])
def test_fault_fails_a_limit(checked, fault):
    drv, _, limits = checked
    numbers = drv.faults()[fault]
    assert _fails(numbers, limits), numbers


def test_program_config_refuses_a_changed_mixer(monkeypatch):
    from drivers.train_hybrid import program_config

    cell = tiny_cell(monkeypatch)
    program_config(cell.config)
    for key, value, field in (("mamba_n_groups", 2, "ssm_groups"),
                              ("attention_multiplier", 0.125, "attention_multiplier"),
                              ("position_embedding_type", "rope", "use_rope"),
                              ("layer_types", ["mamba"] * 10, "layers")):
        with pytest.raises(SystemExit, match=field):
            program_config(dict(cell.config, **{key: value}))


def test_matmul_params_hand_count():
    # nine Mamba2 layers: in_proj 2048 x (4096 + 4352 + 64), out_proj 4096 x 2048;
    # one attention layer: 2048 x 64 x 2 x (32 + 8); ten MLPs 3 x 2048 x 8192;
    # the tied head 12,544 x 2048
    mamba, attn, mlp = 2048 * 8512 + 4096 * 2048, 2048 * 64 * 2 * 40, 3 * 2048 * 8192
    assert counts_hybrid.matmul_params(CONFIG) == 9 * (mamba + mlp) + attn + mlp + 12544 * 2048
    assert counts_hybrid.matmul_params(CONFIG) == 771_883_008


def test_train_flops_hand_count():
    # per token and Mamba2 layer, forward: C B^T over 257/2 positions x 128,
    # M X 64 heads x 257/2 x 64, states and read-out 2 x 64 x 64 x 128
    ssd = 2 * (128.5 * 128 + 64 * 128.5 * 64 + 2 * 64 * 64 * 128)
    assert counts_hybrid.ssd_flops_per_token(CONFIG) == ssd
    attn = 2 * 2 * 32 * 64 * 8193 / 2
    flops = counts_hybrid.train_flops_per_token(CONFIG, 8192)
    assert flops == 6 * 771_883_008 + 3 * (attn + 9 * ssd)
    assert flops * 8192 == pytest.approx(3.95e13, rel=3e-3)
