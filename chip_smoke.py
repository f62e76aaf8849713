"""Bring-up check: the DFL trainer's main path on a TPU, at published widths.

  python chip_smoke.py             # one chip
  python chip_smoke.py --chips 4   # the four-chip path only (2x2 v5e host)

One chip: ``smollm-360m`` at its published widths (not the smoke variant)
trains a few steps on one repeated batch (seq 2048, batch 8) through
``get_arch -> build_model -> DFLTrainer -> jitted_train_step`` with data from
``FederatedData``. Every loss must be finite, the first within 1 nat of
ln(vocab), and the last below the first. Then the gossip codecs' Pallas
kernels (int8 and int4 quantize/dequantize, top-k select) run compiled at the
model's flattened parameter size through ``repro.compress`` and are compared
with their oracles in ``repro.kernels.codec.ref``; each compiled program must
hold a ``tpu_custom_call``, so an interpret-mode or oracle path fails the run.

Four chips (mesh 4x1, four DFL nodes): ``gossip_exchange`` on a full-size
parameter tree that differs per node, where every FedAvg mode must equal
``allreduce_ref`` and the host mean, and int8 ``tree_allreduce`` and
``dissemination`` must stay within the codec's error bound; a few trainer
steps under each FedAvg mode; one churn round through ``DFLSession``
(``mesh_smoke`` scenario: replan and recompile).

The script refuses to run anywhere but on a TPU. Lines before the last are
bring-up information, not metrics. The last line is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import re
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

ARCH = "smollm-360m"
SEQ_LEN, BATCH_PER_NODE = 2048, 8
STEPS = 6
LR, WARMUP = 1e-3, 0
FEDAVG_MODES = ("tree_allreduce", "dissemination", "segmented", "flooding")


def info(msg: str) -> None:
    print(f"info: {msg}", flush=True)


def check(ok, what: str) -> None:
    if not bool(ok):
        raise SystemExit(f"FAILED: {what}")
    print(f"pass: {what}", flush=True)


def tpu_devices(n: int):
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"no TPU found: JAX's default backend is "
                         f"{devices[0].platform!r}; this check runs on a TPU only")
    if len(devices) < n:
        raise SystemExit(f"need {n} TPU chips, found {len(devices)}")
    return devices


def peak_bytes(devices) -> list:
    return [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]


def has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


def count_ops(hlo: str, op: str) -> int:
    return len(re.findall(rf"\s{op}(?:-start)?\(", hlo))


def make_batch(data):
    import jax.numpy as jnp

    from repro.models import Batch

    tok, lab = data.global_batch()
    return Batch(tokens=jnp.asarray(tok), labels=jnp.asarray(lab))


def build_trainer(mesh, mode: str, seed: int):
    import jax

    from repro.configs import get_arch
    from repro.data import DataConfig, FederatedData
    from repro.dfl import DFLConfig, DFLTrainer
    from repro.models import build_model

    cfg = get_arch(ARCH)
    trainer = DFLTrainer(build_model(cfg), mesh, DFLConfig(
        gossip_mode=mode, lr=LR, warmup=WARMUP, total_steps=STEPS))
    state = trainer.init_state(jax.random.PRNGKey(seed))
    data = FederatedData(DataConfig(
        vocab=cfg.vocab, seq_len=SEQ_LEN, batch_per_node=BATCH_PER_NODE,
        n_nodes=trainer.plan.n_nodes, seed=seed))
    return trainer, state, data


def train_steps(trainer, state, batch, steps: int, label: str):
    """Compile the trainer's step, run ``steps`` steps on one repeated batch,
    and check the loss: finite, starting near ln(vocab), and falling."""
    import jax

    t0 = time.perf_counter()
    step = trainer.jitted_train_step(jax.eval_shape(lambda: state),
                                     jax.eval_shape(lambda: batch))
    compiled = step.lower(state, batch).compile()
    hlo = compiled.as_text()
    info(f"{label}: compile_s={time.perf_counter() - t0:.3f} "
         f"all-reduce={count_ops(hlo, 'all-reduce')} "
         f"collective-permute={count_ops(hlo, 'collective-permute')} "
         f"all-gather={count_ops(hlo, 'all-gather')}")
    losses, secs = [], []
    for _ in range(steps):
        t0 = time.perf_counter()
        state, metrics = compiled(state, batch)
        losses.append(float(metrics["loss"]))
        secs.append(time.perf_counter() - t0)
    info(f"{label}: losses={losses} step_s={[round(s, 4) for s in secs]}")
    ln_v = math.log(trainer.cfg.vocab)
    check(all(math.isfinite(x) for x in losses), f"{label}: every loss finite")
    check(abs(losses[0] - ln_v) < 1.0,
          f"{label}: first loss {losses[0]:.4f} within 1 nat of ln(vocab)={ln_v:.4f}")
    check(losses[-1] < losses[0],
          f"{label}: loss on the repeated batch fell {losses[0]:.4f} -> {losses[-1]:.4f}")
    return state


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------


def codec_kernels(flat) -> None:
    """Each codec kernel compiled for the chip at ``flat``'s size, against
    its oracle in kernels/codec/ref.py (all comparisons reduce on device)."""
    import jax
    import jax.numpy as jnp

    from repro.compress import make_codec
    from repro.kernels.codec.ops import _chunked
    from repro.kernels.codec.ref import dequantize_ref, quantize_ref, topk_select_ref

    n = flat.shape[0]
    for name in ("int8", "int4"):
        codec = make_codec(name)
        t0 = time.perf_counter()
        enc = jax.jit(codec.jax_encode).lower(flat).compile()
        codes, scales = enc(flat)
        dec = jax.jit(lambda e: codec.jax_decode(e, (n,), jnp.float32)
                      ).lower((codes, scales)).compile()
        out = dec((codes, scales))
        jax.block_until_ready(out)
        info(f"{name}: encode+decode compile+run_s={time.perf_counter() - t0:.3f}")
        check(has_kernel(enc) and has_kernel(dec),
              f"{name}: quantize and dequantize compiled as Pallas kernels")

        @jax.jit
        def compare(x, out, codes, scales):
            xc = _chunked(x, codec.chunk)
            ref_codes, ref_scales = quantize_ref(xc, float(codec.qmax))
            ref_out = dequantize_ref(ref_codes, ref_scales)
            oc = _chunked(out, codec.chunk)
            r = {
                "scale_rel": jnp.max(jnp.abs(scales - ref_scales) / ref_scales),
                # error against the input, in quantization steps (<= 1/2)
                "steps_vs_input": jnp.max(jnp.abs(oc - xc) / ref_scales[:, None]),
                "steps_vs_ref": jnp.max(jnp.abs(oc - ref_out) / ref_scales[:, None]),
                "mismatch_vs_ref": jnp.sum(oc != ref_out),
            }
            if codec.bits == 8:
                r["code_mismatch"] = jnp.sum(codes != ref_codes)
                r["dequant_exact"] = jnp.all(dequantize_ref(codes, scales) == oc)
            return r

        r = {k: v.item() for k, v in compare(flat, out, codes, scales).items()}
        info(f"{name}: {r}")
        check(r["scale_rel"] <= 1e-6, f"{name}: scales match the oracle")
        check(r["steps_vs_input"] <= 0.5 * 1.001,
              f"{name}: decoded values within half a quantization step of the input")
        # kernel and oracle each divide by the scale; where x/scale sits
        # within an ulp of a rounding boundary they may round apart by one
        check(r["steps_vs_ref"] <= 1.0 * 1.001 and r["mismatch_vs_ref"] <= 1e-4 * n,
              f"{name}: decoded values agree with the oracle "
              f"({r['mismatch_vs_ref']} of {n} one step apart)")
        if codec.bits == 8:
            check(r["dequant_exact"], f"{name}: dequantize kernel exact on the kernel's codes")
        del codes, scales, out

    codec = make_codec("topk")
    t0 = time.perf_counter()
    enc = jax.jit(codec.jax_encode).lower(flat).compile()
    vals, idx = enc(flat)
    jax.block_until_ready(vals)
    info(f"topk: select compile+run_s={time.perf_counter() - t0:.3f} "
         f"k={codec.k} block={codec.block}")
    check(has_kernel(enc), "topk: select compiled as a Pallas kernel")

    @jax.jit
    def compare_topk(x, vals, idx):
        ref_vals, ref_idx = topk_select_ref(_chunked(x, codec.block), codec.k)
        return jnp.sum(idx != ref_idx), jnp.sum(vals != ref_vals)

    bad_idx, bad_vals = (v.item() for v in compare_topk(flat, vals, idx))
    check(bad_idx == 0 and bad_vals == 0,
          f"topk: indices and values equal the oracle's "
          f"({bad_idx} indices, {bad_vals} values differ)")


def one_chip(seed: int) -> None:
    import jax
    import jax.numpy as jnp

    from repro.launch.mesh import make_local_mesh

    devices = jax.devices()[:1]
    mesh = make_local_mesh((1, 1), ("data", "model"))
    trainer, state, data = build_trainer(mesh, "tree_allreduce", seed)
    info(f"{ARCH}: params={trainer.cfg.param_count()} seq={SEQ_LEN} "
         f"batch={BATCH_PER_NODE} mesh={dict(mesh.shape)}")
    state = train_steps(trainer, state, make_batch(data), STEPS, "train 1x1")
    info(f"train 1x1: peak_bytes_in_use={peak_bytes(devices)}")

    master = state.opt_state.get("master", state.params)
    flat = jax.jit(lambda t: jnp.concatenate(
        [jnp.ravel(x).astype(jnp.float32) for x in jax.tree.leaves(t)]))(master)
    del state, master
    info(f"codec kernels on the flattened parameters: {flat.shape[0]} f32")
    codec_kernels(flat)
    info(f"after codecs: peak_bytes_in_use={peak_bytes(devices)}")


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------


def gossip_node_distinct(mesh, seed: int) -> None:
    """gossip_exchange on a full-size tree whose leading axis is sharded over
    the nodes (``P("data")``), so each node holds a parameter-shaped copy
    with its own values: a collective that moves nothing, or the wrong thing,
    cannot pass. Outputs are compared on the device; only the reference is
    compared with the mean taken on the host."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from repro.compress import make_codec
    from repro.configs import get_arch
    from repro.dfl.collectives import GossipPlan, gossip_exchange
    from repro.dfl.sharding import named
    from repro.models import build_model

    n = mesh.shape["data"]
    plan = GossipPlan.build(mesh, ("data",))
    shapes = jax.eval_shape(build_model(get_arch(ARCH)).init, jax.random.PRNGKey(0))
    leaves, treedef = jax.tree.flatten(shapes)
    specs = jax.tree.map(lambda _: P("data"), shapes)

    def make(key):
        return treedef.unflatten([
            jax.random.normal(jax.random.fold_in(key, i),
                              (n * leaf.shape[0], *leaf.shape[1:]), jnp.float32)
            for i, leaf in enumerate(leaves)])

    make_theta = jax.jit(lambda: make(jax.random.PRNGKey(seed)),
                         out_shardings=named(mesh, specs))
    info(f"gossip tree: {len(leaves)} leaves, {sum(x.size for x in leaves)} f32 per node")

    def exchange(mode, codec=None):
        # the same tree made afresh and donated: a dissemination buffer holds
        # N copies of it, and the input must not take HBM beside them
        t0 = time.perf_counter()
        out = jax.jit(lambda t: gossip_exchange(mode, plan, mesh, t, specs, codec=codec),
                      donate_argnums=0)(make_theta())
        jax.block_until_ready(out)
        info(f"gossip {mode}{'+' + codec.name if codec else ''}: "
             f"compile+run_s={time.perf_counter() - t0:.3f}")
        return out

    def per_node(x):  # (n * d0, ...) -> (n, d0, ...)
        return x.reshape(n, x.shape[0] // n, *x.shape[1:])

    @jax.jit
    def node_spread(t):  # largest difference between node 0 and any node
        return jnp.max(jnp.stack([jnp.max(jnp.abs(per_node(x) - per_node(x)[:1]))
                                  for x in jax.tree.leaves(t)]))

    @jax.jit
    def max_diff(a, b):
        return jnp.max(jnp.stack([jnp.max(jnp.abs(x - y)) for x, y in
                                  zip(jax.tree.leaves(a), jax.tree.leaves(b))]))

    theta = make_theta()
    check(node_spread(theta).item() > 1.0, "gossip input differs per node")
    absmax = max(jnp.max(jnp.abs(x)).item() for x in jax.tree.leaves(theta))
    ref = exchange("allreduce_ref")
    check(node_spread(ref).item() == 0.0, "allreduce_ref: every node holds the same mean")
    host_err = 0.0
    for r, x in zip(jax.tree.leaves(ref), jax.tree.leaves(theta)):
        mean = per_node(np.asarray(jax.device_get(x))).mean(axis=0)
        node0 = np.asarray(jax.device_get(r[: x.shape[0] // n]))
        host_err = max(host_err, float(np.abs(node0 - mean).max()))
    check(host_err <= 1e-5, f"allreduce_ref equals the host mean (max err {host_err:.3g})")
    del theta, ref

    int8 = make_codec("int8")
    cases = [(mode, None, 1e-5) for mode in FEDAVG_MODES] + [
        # the scenario jax executor's bound: dissemination pays one encode
        # per contribution, the tree re-encodes partial sums on every hop
        ("tree_allreduce", int8, int8.mean_atol(absmax) * n),
        ("dissemination", int8, int8.mean_atol(absmax))]
    for mode, codec, atol in cases:
        out = exchange(mode, codec)
        err = max_diff(out, exchange("allreduce_ref")).item()
        del out
        label = mode + ("+" + codec.name if codec else "")
        check(err <= atol, f"{label}: equals allreduce_ref on every node "
                           f"within {atol:.3g} (max err {err:.3g})")


def four_chips(seed: int) -> None:
    import jax

    from repro.dfl.session import DFLSession, run_scenario_rounds
    from repro.launch.mesh import make_local_mesh
    from repro.scenario import scenarios

    devices = jax.devices()[:4]
    mesh = make_local_mesh((4, 1), ("data", "model"))
    gossip_node_distinct(mesh, seed)
    info(f"gossip: peak_bytes_in_use={peak_bytes(devices)}")

    for mode in FEDAVG_MODES:
        trainer, state, data = build_trainer(mesh, mode, seed)
        train_steps(trainer, state, make_batch(data), 3, f"train 4x1 {mode}")
        info(f"train 4x1 {mode}: peak_bytes_in_use={peak_bytes(devices)}")
        del state

    scenario = scenarios.get("mesh_smoke")
    trainer, state, data = build_trainer(mesh, "tree_allreduce", seed)
    session = DFLSession(trainer, scenario=scenario)
    t0 = time.perf_counter()
    state, metrics = run_scenario_rounds(session, state, make_batch(data),
                                         lambda: make_batch(data), log=info)
    info(f"churn: {scenario.rounds} rounds with replan in "
         f"{time.perf_counter() - t0:.3f}s")
    check(math.isfinite(float(metrics["loss"])), "churn: loss finite after the replan")
    check(session.members == {0, 1, 2} and session.trainer.plan.n_nodes == 3,
          "churn: node 3 left and the step was replanned over 3 nodes")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: train + codec kernels; 4: the multi-node path only")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    devices = tpu_devices(args.chips)
    from repro.launch.compile_cache import enable_compile_cache

    info(f"device_kind={devices[0].device_kind} count={len(devices)} "
         f"compile_cache={enable_compile_cache()}")
    if args.chips == 4:
        four_chips(args.seed)
    else:
        one_chip(args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
