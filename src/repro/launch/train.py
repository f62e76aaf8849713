"""End-to-end DFL training driver.

Runs real steps on the devices of the default backend (CPU smoke: reduced
arch variant; TPU: full config), with MOSGU gossip every step,
checkpointing, and moderator rotation each communication round. A mesh
needs that many devices; to rehearse on the CPU, give it virtual ones:

  XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \
  PYTHONPATH=src python -m repro.launch.train --arch smollm-360m --smoke \
      --steps 50 --mesh 1x2x2 --gossip tree_allreduce

With ``--scenario NAME`` the run is driven by a declarative registry
scenario (:mod:`repro.scenario`): the scenario's protocol picks the gossip
mode, its round count the number of communication rounds, and its churn
schedule fires inside :class:`repro.dfl.session.DFLSession` (replan +
recompile on every membership change, moderator rotation every round):

  PYTHONPATH=src python -m repro.launch.train --arch smollm-360m --smoke \
      --mesh 1x4x2 --scenario churn_storm   # 8 devices

With ``--profile-dir DIR`` the JAX profiler runs over every step after the
first (so the compile stays outside it) and writes its trace under DIR: open
it in XProf or Perfetto to see each step's device ops under the step's named
scopes (``repro.obs.scopes``) beside the host spans of ``--trace``.

With ``--sweep NAME`` the run is one cell of a registered experiment grid
(:mod:`repro.scenario.sweep`) — the launcher-array pattern: ``--cell K``
trains the K-th expanded cell's scenario (one cell per process / SLURM
array index), while ``--sweep NAME`` alone prints the expanded grid with
its plan-executor accounting (a dry-run of the whole table) and exits:

  PYTHONPATH=src python -m repro.launch.train --sweep codec_x_protocol
  PYTHONPATH=src python -m repro.launch.train --smoke --mesh 1x4x2 \
      --sweep codec_x_protocol --cell 3     # 8 devices
"""
from __future__ import annotations

import argparse
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--smoke", action="store_true", help="use the reduced variant")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--batch-per-node", type=int, default=2)
    ap.add_argument("--mesh", default="", help="e.g. 2x2 (data x model) or 1x2x2")
    ap.add_argument("--gossip", default="tree_allreduce")
    ap.add_argument("--scenario", default="",
                    help="registry scenario driving protocol/rounds/churn "
                         "(see repro.scenario.scenarios.names())")
    ap.add_argument("--sweep", default="",
                    help="registered sweep grid; with --cell K trains that "
                         "cell's scenario, alone prints the expanded grid "
                         "(see repro.scenario.scenarios.sweep_names())")
    ap.add_argument("--cell", type=int, default=-1,
                    help="cell index into --sweep (the launcher-array slot)")
    ap.add_argument("--gossip-interval", type=int, default=1)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--checkpoint-dir", default="")
    ap.add_argument("--checkpoint-every", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--trace", default="",
                    help="record an observability trace of the run and write "
                         "Chrome/Perfetto JSON to this path")
    ap.add_argument("--profile-dir", default="",
                    help="run the JAX profiler over every step after the first "
                         "(the compile stays outside) and write its trace here, "
                         "for XProf or Perfetto: device ops under the step's "
                         "named scopes, beside the host spans")
    args = ap.parse_args()

    if args.trace:
        from .. import obs

        obs.set_recorder(obs.Recorder())

    if args.sweep and args.scenario:
        raise SystemExit("--sweep and --scenario are mutually exclusive: "
                         "a sweep cell *is* the scenario for the run")
    if args.profile_dir and (args.scenario or args.sweep):
        raise SystemExit("--profile-dir profiles the plain step loop, not "
                         "--scenario or --sweep rounds")
    if args.cell >= 0 and not args.sweep:
        raise SystemExit("--cell is an index into --sweep; pass a sweep name "
                         "(see repro.scenario.scenarios.sweep_names())")
    sweep_cell = None
    if args.sweep:
        # resolved before jax comes up: the dry-run path never needs devices
        from ..scenario import run_sweep, scenarios

        sweep = scenarios.get_sweep(args.sweep)
        cells = sweep.cells()
        if args.cell < 0:
            result = run_sweep(sweep, executor="plan")
            print(f"sweep {sweep.name!r}: {len(cells)} cells "
                  f"(pass --cell K to train one)")
            for row in result.table():
                coords = ",".join(f"{k}={v}" for k, v in row.items()
                                  if k in sweep.axes())
                print(f"  [{row['cell']:3d}] {coords:40s} "
                      f"tx={row['transmissions']:6d} "
                      f"wire={row['bytes_on_wire_mb']:10.1f}MB")
            return
        if not (0 <= args.cell < len(cells)):
            raise SystemExit(
                f"--cell {args.cell} outside [0, {len(cells)}) for sweep "
                f"{sweep.name!r}")
        sweep_cell = cells[args.cell]
        print(f"sweep {sweep.name!r} cell {args.cell}: "
              f"{sweep_cell.spec.name}")

    import jax
    import jax.numpy as jnp

    from ..checkpoint import save_pytree
    from ..configs import get_arch
    from ..data import DataConfig, FederatedData
    from ..dfl import DFLConfig, DFLTrainer
    from ..models import Batch, build_model
    from .compile_cache import enable_compile_cache
    from .mesh import make_local_mesh

    enable_compile_cache()

    scenario = None
    codec = ""
    if sweep_cell is not None:
        from ..scenario import resolve_gossip_mode

        scenario = sweep_cell.spec
        args.gossip = resolve_gossip_mode(scenario.protocol)
        args.steps = scenario.rounds
        print(f"cell scenario: protocol={scenario.protocol} "
              f"codec={scenario.codec} rounds={scenario.rounds}")
    elif args.scenario:
        from ..scenario import resolve_gossip_mode, scenarios

        scenario = scenarios.get(args.scenario)
        args.gossip = resolve_gossip_mode(scenario.protocol)
        args.steps = scenario.rounds
        print(f"scenario {scenario.name!r}: protocol={scenario.protocol} "
              f"rounds={scenario.rounds} churn={len(scenario.churn)} events")
    if scenario is not None:
        # the scenario's wire codec drives the trainer ("" = raw fp32, the
        # DFLConfig default — same resolution as examples/train_dfl.py)
        codec = scenario.codec if scenario.codec != "fp32" else ""

    cfg = get_arch(args.arch)
    if args.smoke:
        cfg = cfg.smoke_variant()
    dims = tuple(int(x) for x in args.mesh.split("x")) if args.mesh else (1, 1)
    mesh = make_local_mesh(dims, ("pod", "data", "model")[-len(dims):])

    model = build_model(cfg)
    dfl = DFLConfig(gossip_mode=args.gossip, gossip_interval=args.gossip_interval,
                    lr=args.lr, total_steps=args.steps, codec=codec)
    trainer = DFLTrainer(model, mesh, dfl)
    print(f"arch={cfg.name} params={cfg.param_count()/1e6:.1f}M nodes={trainer.plan.n_nodes} "
          f"mst_slots={trainer.plan.dissemination.n_slots} gossip={args.gossip}")

    state = trainer.init_state(jax.random.PRNGKey(0))
    n_nodes = max(trainer.plan.n_nodes, 1)
    data = FederatedData(DataConfig(
        vocab=cfg.vocab, seq_len=args.seq_len,
        batch_per_node=args.batch_per_node, n_nodes=n_nodes,
    ))

    def make_batch():
        tok, lab = data.global_batch()
        kw = {}
        b = tok.shape[0]
        if cfg.family == "audio":
            kw["encoder_frames"] = jnp.zeros((b, cfg.n_frames, cfg.d_model), jnp.float32)
        if cfg.family == "vlm":
            kw["patch_embeddings"] = jnp.zeros((b, cfg.n_patches, cfg.d_model), jnp.float32)
        return Batch(tokens=jnp.asarray(tok), labels=jnp.asarray(lab), **kw)

    batch = make_batch()
    if scenario is not None:
        from ..dfl.session import DFLSession, run_scenario_rounds

        session = DFLSession(trainer, scenario=scenario)
        t0 = time.time()
        state, _ = run_scenario_rounds(session, state, batch, make_batch)
        print(f"done: {scenario.rounds} scenario rounds in {time.time()-t0:.1f}s")
        _flush_trace(args.trace)
        return

    step_fn = trainer.jitted_train_step(jax.eval_shape(lambda: state),
                                        jax.eval_shape(lambda: batch))
    from .. import obs

    rec = obs.get()
    t0 = time.time()
    for i in range(args.steps):
        if i == 1 and args.profile_dir:
            jax.profiler.start_trace(args.profile_dir)
        with jax.profiler.StepTraceAnnotation("train", step_num=i):
            if rec.enabled:
                with rec.span("train:dispatch", cat="train", track="train", step=i,
                              gossip=(i + 1) % max(args.gossip_interval, 1) == 0):
                    state, metrics = step_fn(state, batch)
            else:
                state, metrics = step_fn(state, batch)
        batch = make_batch()
        if (i + 1) % args.log_every == 0 or i == 0:
            print(f"step {i+1:5d} loss={float(metrics['loss']):.4f} "
                  f"gnorm={float(metrics['grad_norm']):.3f} "
                  f"({(time.time()-t0)/(i+1):.2f}s/step)")
        if i == 0:
            t1 = time.time()  # the first step, compile included, is done: its loss was read
        if args.checkpoint_dir and args.checkpoint_every and (i + 1) % args.checkpoint_every == 0:
            save_pytree(f"{args.checkpoint_dir}/step{i+1:08d}",
                        jax.device_get(state.params),
                        {"step": i + 1, "arch": cfg.name})
    jax.block_until_ready(state)
    t_end = time.time()
    if args.profile_dir and args.steps > 1:
        jax.profiler.stop_trace()
        print(f"wrote a profile of steps 2-{args.steps} under {args.profile_dir}")
    rate = (f", {(args.steps - 1) / (t_end - t1):.4f} steps/s after the first"
            if args.steps > 1 else "")
    print(f"done: {args.steps} steps in {t_end - t0:.1f}s{rate}")
    _flush_trace(args.trace)


def _flush_trace(path: str) -> None:
    """Uninstall the run's recorder and export it as a Perfetto trace."""
    if not path:
        return
    from .. import obs
    from ..obs import write_trace

    rec = obs.set_recorder(obs.NULL_RECORDER)
    write_trace(rec, path)
    print(f"wrote {path} ({len(rec.spans)} spans) — open in ui.perfetto.dev")


if __name__ == "__main__":
    main()
