"""End-to-end system behaviour.

Multi-device cases run in subprocesses because
``--xla_force_host_platform_device_count`` must be set before jax imports —
and the rest of the suite must keep seeing 1 device.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_devices(code: str, n_devices: int = 8, timeout: int = 520) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, env=env, timeout=timeout,
    )
    assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"
    return out.stdout


class TestGossipCollectives:
    def test_all_modes_produce_exact_fedavg(self):
        out = run_devices("""
            import jax, jax.numpy as jnp, numpy as np, json
            from jax.sharding import PartitionSpec as P, NamedSharding
            from repro.launch.mesh import make_local_mesh
            mesh = make_local_mesh((2, 2, 2), ("pod", "data", "model"))
            from repro.dfl.collectives import GossipPlan, gossip_exchange
            plan = GossipPlan.build(mesh, ("pod", "data"))
            w_host = np.arange(4*8, dtype=np.float32).reshape(4, 8)
            # per node (8, 3): segmented splits this leaf along its leading
            # axis (4 segments of 2 rows); it flattens and pads the (1, 4)
            # blocks of "w"
            v_host = np.arange(4*8*3, dtype=np.float32).reshape(4, 8, 3) ** 1.5
            theta = {
              "w": jax.device_put(jnp.asarray(w_host),
                                  NamedSharding(mesh, P(("pod","data"), "model"))),
              "v": jax.device_put(jnp.asarray(v_host.reshape(32, 3)),
                                  NamedSharding(mesh, P(("pod","data")))),
              "b": jax.device_put(jnp.arange(4.0), NamedSharding(mesh, P())),
            }
            specs = {"w": P(("pod","data"), "model"), "v": P(("pod","data")), "b": P()}
            want = {"w": np.broadcast_to(w_host.mean(axis=0), (4, 8)),
                    "v": np.tile(v_host.mean(axis=0), (4, 1)),
                    "b": np.arange(4.0)}
            res = {}
            for mode in ("tree_allreduce","dissemination","segmented","flooding",
                         "allreduce_ref"):
                out = jax.jit(lambda t: gossip_exchange(mode, plan, mesh, t, specs))(theta)
                res[mode] = all(np.allclose(np.asarray(out[k]), want[k], rtol=1e-6, atol=1e-5)
                                for k in want)
            print(json.dumps(res))
        """)
        res = json.loads(out.strip().splitlines()[-1])
        assert all(res.values()), res

    def test_mixing_converges_to_mean(self):
        out = run_devices("""
            import jax, jax.numpy as jnp, numpy as np
            from jax.sharding import PartitionSpec as P, NamedSharding
            from repro.launch.mesh import make_local_mesh
            mesh = make_local_mesh((4, 2), ("data", "model"))
            from repro.dfl.collectives import GossipPlan, gossip_exchange
            plan = GossipPlan.build(mesh, ("data",))
            w = np.arange(4*2, dtype=np.float32).reshape(4, 2)
            theta = {"w": jax.device_put(jnp.asarray(w),
                                         NamedSharding(mesh, P("data", "model")))}
            specs = {"w": P("data", "model")}
            f = jax.jit(lambda t: gossip_exchange("mixing", plan, mesh, t, specs))
            for _ in range(30):
                theta = f(theta)
            spread = float(np.ptp(np.asarray(theta["w"]), axis=0).max())
            print("SPREAD", spread)
        """)
        spread = float(out.strip().split()[-1])
        assert spread < 1e-2  # doubly-stochastic mixing contracts to the mean


class TestDFLTraining:
    def test_loss_decreases_with_gossip(self):
        out = run_devices("""
            import jax, jax.numpy as jnp, numpy as np
            from repro.launch.mesh import make_local_mesh
            mesh = make_local_mesh((2, 2, 2), ("pod", "data", "model"))
            from repro.configs import get_arch
            from repro.models import Batch, build_model
            from repro.dfl import DFLConfig, DFLTrainer
            from repro.data import DataConfig, FederatedData
            cfg = get_arch("smollm-360m").smoke_variant()
            model = build_model(cfg)
            tr = DFLTrainer(model, mesh, DFLConfig(gossip_mode="tree_allreduce", lr=2e-3))
            state = tr.init_state(jax.random.PRNGKey(0))
            data = FederatedData(DataConfig(vocab=cfg.vocab, seq_len=64,
                                            batch_per_node=2, n_nodes=4))
            tok, lab = data.global_batch()
            batch = Batch(tokens=jnp.asarray(tok), labels=jnp.asarray(lab))
            step = tr.jitted_train_step(jax.eval_shape(lambda: state),
                                        jax.eval_shape(lambda: batch))
            losses = []
            for i in range(14):
                state, m = step(state, batch)
                losses.append(float(m["loss"]))
                tok, lab = data.global_batch()
                batch = Batch(tokens=jnp.asarray(tok), labels=jnp.asarray(lab))
            print("LOSSES", losses[0], min(losses[-3:]))
        """)
        first, last = (float(x) for x in out.strip().split()[-2:])
        assert last < first

    def test_gossip_modes_agree_after_one_round(self):
        """dissemination+FedAvg == tree all-reduce == flooding mean: the
        beyond-paper schedule is numerically equivalent to the paper's."""
        out = run_devices("""
            import jax, jax.numpy as jnp, numpy as np, json
            from repro.launch.mesh import make_local_mesh
            mesh = make_local_mesh((4, 2), ("data", "model"))
            from repro.configs import get_arch
            from repro.models import Batch, build_model
            from repro.dfl import DFLConfig, DFLTrainer
            cfg = get_arch("granite-3-2b").smoke_variant()
            model = build_model(cfg)
            tok = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, cfg.vocab)
            batch = Batch(tokens=tok, labels=tok)
            outs = {}
            for mode in ("dissemination", "tree_allreduce", "flooding"):
                tr = DFLTrainer(model, mesh, DFLConfig(gossip_mode=mode, lr=1e-3))
                state = tr.init_state(jax.random.PRNGKey(0))
                step = tr.jitted_train_step(jax.eval_shape(lambda: state),
                                            jax.eval_shape(lambda: batch))
                state, _ = step(state, batch)
                flat = jnp.concatenate([jnp.ravel(x).astype(jnp.float32)
                                        for x in jax.tree.leaves(state.params)])
                outs[mode] = np.asarray(flat)
            d1 = float(np.abs(outs["dissemination"] - outs["tree_allreduce"]).max())
            d2 = float(np.abs(outs["dissemination"] - outs["flooding"]).max())
            print("DIFFS", d1, d2)
        """)
        d1, d2 = (float(x) for x in out.strip().split()[-2:])
        assert d1 < 1e-5 and d2 < 1e-5


class TestDryRunSmoke:
    def test_one_pair_lowers_and_compiles(self):
        out = run_devices("""
            from repro.launch.dryrun import dryrun_pair
            r = dryrun_pair("whisper-tiny", "train_4k", multi_pod=False, verbose=False)
            print("STATUS", r["status"], r["bottleneck"], round(r["peak_memory_gb"], 2))
        """, n_devices=512)
        assert "STATUS ok" in out

    def test_skip_marked(self):
        out = run_devices("""
            from repro.launch.dryrun import dryrun_pair
            r = dryrun_pair("whisper-tiny", "long_500k", multi_pod=False, verbose=False)
            print("STATUS", r["status"])
        """, n_devices=512)
        assert "STATUS skipped" in out
