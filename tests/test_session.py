"""DFL session: moderator rotation + churn-triggered replanning on devices."""
import os
import subprocess
import sys
import textwrap

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_devices(code: str, n_devices: int = 8, timeout: int = 520) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env, timeout=timeout)
    assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"
    return out.stdout


def test_session_rounds_with_churn():
    out = run_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_local_mesh
        mesh = make_local_mesh((4, 2), ("data", "model"))
        from repro.configs import get_arch
        from repro.models import Batch, build_model
        from repro.dfl import DFLConfig, DFLTrainer
        from repro.dfl.session import DFLSession
        cfg = get_arch("smollm-360m").smoke_variant()
        model = build_model(cfg)
        trainer = DFLTrainer(model, mesh, DFLConfig(gossip_mode="tree_allreduce", lr=1e-3))
        session = DFLSession(trainer)
        state = trainer.init_state(jax.random.PRNGKey(0))
        tok = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, cfg.vocab)
        batch = Batch(tokens=tok, labels=tok)

        mods = [session.moderator.moderator_id]
        state, m = session.train_round(state, batch)
        mods.append(session.moderator.moderator_id)
        l0 = float(m["loss"])

        # churn: node 3 fails -> replan over 3 nodes -> recompile -> train on
        session.node_leaves(3)
        assert session.trainer.plan.n_nodes == 4  # stale until next round plans
        state, m = session.train_round(state, batch)
        assert session.trainer.plan.n_nodes == 3
        assert int((np.asarray(session.trainer.plan.colors) < 0).sum()) == 1
        l1 = float(m["loss"])

        # rejoin -> replan back to 4 healthy nodes
        session.node_rejoins(3)
        state, m = session.train_round(state, batch)
        assert session.trainer.plan.n_nodes == 4
        l2 = float(m["loss"])
        print("MODS", mods[0] != mods[1], "LOSSES", l0, l1, l2)
    """)
    flag = out.strip().split()[1]
    assert flag == "True"  # moderator actually rotated
    losses = [float(x) for x in out.strip().split()[-3:]]
    assert losses[-1] < losses[0]  # still learning through churn


def test_noncontiguous_membership_all_buffer_modes():
    """Churn that leaves a hole in the id space (node 1 of {0,1,2,3} fails):
    payload ids are subgraph-indexed while ppermute addresses physical nodes,
    so the buffer bodies must remap through GossipPlan.node_slot."""
    out = run_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.launch.mesh import make_local_mesh
        mesh = make_local_mesh((4, 2), ("data", "model"))
        from repro.dfl.collectives import gossip_exchange
        from repro.dfl.session import _plan_for_members
        plan = _plan_for_members(mesh, ("data",), {0, 2, 3})  # node 1 masked
        w = np.arange(8, dtype=np.float32).reshape(4, 2)
        theta = {"w": jax.device_put(jnp.asarray(w),
                                     NamedSharding(mesh, P("data", "model")))}
        specs = {"w": P("data", "model")}
        healthy = w[[0, 2, 3]].mean(axis=0)
        ok = True
        for mode in ("dissemination", "segmented", "tree_allreduce"):
            res = np.asarray(jax.jit(lambda t: gossip_exchange(
                mode, plan, mesh, t, specs))(theta)["w"])
            ok &= np.allclose(res[[0, 2, 3]], healthy, atol=1e-5)
            ok &= np.allclose(res[1], w[1], atol=1e-6)
        print("OK", ok)
    """)
    assert out.strip().endswith("True")


def test_masked_nodes_keep_local_params():
    out = run_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.launch.mesh import make_local_mesh
        mesh = make_local_mesh((4, 2), ("data", "model"))
        from repro.dfl.collectives import gossip_exchange
        from repro.dfl.session import _plan_for_members
        plan = _plan_for_members(mesh, ("data",), {0, 1, 2})  # node 3 masked
        w = np.arange(8, dtype=np.float32).reshape(4, 2)
        theta = {"w": jax.device_put(jnp.asarray(w),
                                     NamedSharding(mesh, P("data", "model")))}
        specs = {"w": P("data", "model")}
        out = jax.jit(lambda t: gossip_exchange(
            "tree_allreduce", plan, mesh, t, specs))(theta)
        res = np.asarray(out["w"])
        healthy_mean = w[:3].mean(axis=0)
        ok_members = np.allclose(res[:3], healthy_mean, atol=1e-5)
        ok_masked = np.allclose(res[3], w[3], atol=1e-6)
        print("OK", ok_members and ok_masked)
    """)
    assert out.strip().endswith("True")
