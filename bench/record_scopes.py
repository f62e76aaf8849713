"""Traces the DFL step on a TPU and splits its device time by layer scope.

  python3 bench/record_scopes.py --out bench/testdata
  python3 bench/record_scopes.py --workload <cell> --seed <n> --seconds <s> [--save DIR]

Run from the root of a checkout on a TPU. With ``--out`` it records the small
trace that ``bench/tests/test_scopes.py`` reduces: a few steps of the DFL step
at the tiny widths of ``bench/tests/tiny.py`` (two layers of width 64, 512
tokens so that attention's query-block loop runs as in the cells), in a
window shaped like the harness's, written as ``scopes.xplane.pb`` beside the
compiled step's ``as_text()`` as ``scopes.hlo.txt``. With ``--workload`` it
builds the cell's step as ``bench/run.py`` does, traces a window as a ``--trace 1``
run does, and prints one JSON line: each layer's own device time per step
(``bench/scopes.py``), the step's busy time per step as ``step_device_ms``
reads it, attention split into forward, remat and backward, and each layer's
longest ops; ``--save`` keeps the two tables it joined, the compiled step's
text and each op's own time, for a later reading.
"""
import argparse
import json
import shutil
import sys
import tempfile
from collections import defaultdict
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import jax  # noqa: E402

import harness  # noqa: E402
import scopes  # noqa: E402
import xplane  # noqa: E402

TINY_STEPS, TINY_SEQ, TINY_BATCH = 3, 512, 2
TOP = 5


def _traced(run, trace_dir: Path) -> None:
    """``run()`` under the profiler, inside a ``bench.window`` annotation."""
    shutil.rmtree(trace_dir, ignore_errors=True)
    jax.profiler.start_trace(str(trace_dir))
    with jax.profiler.TraceAnnotation(xplane.WINDOW):
        run()
    jax.profiler.stop_trace()


def record_tiny(out: Path) -> None:
    import jax.numpy as jnp

    from repro.configs import get_arch
    from repro.dfl import DFLConfig, DFLTrainer
    from repro.launch.mesh import make_local_mesh
    from repro.models import Batch, build_model
    from tests.tiny import PROGRAM_SIZES

    cfg = get_arch("smollm-360m").replace(**PROGRAM_SIZES)
    trainer = DFLTrainer(build_model(cfg), make_local_mesh((1, 1), ("data", "model")),
                         DFLConfig(gossip_mode="dissemination"))
    state = trainer.init_state(jax.random.PRNGKey(0))
    tok = jax.random.randint(jax.random.PRNGKey(1), (TINY_BATCH, TINY_SEQ), 0, cfg.vocab)
    batch = Batch(tokens=tok, labels=tok)
    step = trainer.jitted_train_step(jax.eval_shape(lambda: state),
                                     jax.eval_shape(lambda: batch)).lower(state, batch).compile()
    state, _ = jax.block_until_ready(step(state, batch))
    box = [state]

    def run():
        for _ in range(TINY_STEPS):
            with jax.profiler.TraceAnnotation("bench.dispatch"):
                box[0], metrics = step(box[0], batch)
            with jax.profiler.TraceAnnotation("bench.wait"):
                jax.block_until_ready(metrics)

    tmp = Path(tempfile.mkdtemp())
    _traced(run, tmp)
    out.mkdir(parents=True, exist_ok=True)
    shutil.copyfile(xplane.find_xplane(tmp), out / "scopes.xplane.pb")
    (out / "scopes.hlo.txt").write_text(step.as_text())
    shutil.rmtree(tmp)
    print(f"wrote {out}/scopes.xplane.pb and scopes.hlo.txt")


def split_cell(workload: str, seed: int, seconds: float, save: Path = None) -> dict:
    cell = harness.resolve(workload)
    devices = harness.require_tpu(cell.chips)
    harness.enable_compile_cache()
    drv = harness.driver_class(cell)(cell, seed)
    hlo = drv.step.as_text()
    op_scopes = scopes.op_names(hlo)
    win = {}
    trace_dir = harness.TRACE_DIR / f"{workload}.scopes"
    _traced(lambda: win.update(drv.window(seconds)), trace_dir)
    summary = xplane.summarize(trace_dir, len(devices))
    self_s = scopes.window_self_times(trace_dir, len(devices))
    shutil.rmtree(trace_dir, ignore_errors=True)
    drv.release()
    if save:
        save.mkdir(parents=True, exist_ok=True)
        (save / f"{workload}.hlo.txt").write_text(hlo)
        (save / f"{workload}.self_s.json").write_text(json.dumps(
            {"steps": win["units"], "busy_s": summary.busy_s, "op_self_s": self_s}))

    steps = win["units"]
    per_step = {k: 1e3 * v / steps for k, v in scopes.split(self_s, op_scopes).items()}
    attn_phases, top = defaultdict(float), defaultdict(list)
    for name, secs in self_s.items():
        op = op_scopes.get(name, "")
        layer = scopes.layer_of(op)
        if layer == scopes.ATTENTION:
            attn_phases[scopes.phase_of(op)] += 1e3 * secs / steps
        top[layer].append((secs, name, op))
    return {
        "workload": workload, "seed": seed, "steps": steps, "window_s": summary.window_s,
        "step_device_ms": 1e3 * summary.busy_s / steps,
        "layer_ms": per_step, "layer_sum_ms": sum(per_step.values()),
        "attention_phase_ms": dict(attn_phases),
        "unscoped_ms": 1e3 * sum(s for n, s in self_s.items() if not op_scopes.get(n)) / steps,
        "top_ops": {layer: [[name, 1e3 * secs / steps, op[-120:]]
                            for secs, name, op in sorted(ops, reverse=True)[:TOP]]
                    for layer, ops in top.items()},
    }


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", type=Path, help="record the tiny step's trace into this directory")
    ap.add_argument("--workload", help="split a benchmark cell's traced window by layer")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--save", type=Path, help="keep the cell's step text and op times here")
    args = ap.parse_args()
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("record_scopes runs on a TPU")
    if args.out:
        record_tiny(args.out)
    if args.workload:
        print(json.dumps(split_cell(args.workload, args.seed, args.seconds, args.save)),
              flush=True)


if __name__ == "__main__":
    main()
