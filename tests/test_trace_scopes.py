"""Named scopes at each layer of the jitted DFL step, and host spans on the
profiler's clock (``repro.obs.scopes``, ``Recorder.span``)."""
import contextlib
import os
import re
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest

from repro import obs
from repro.configs import get_arch
from repro.dfl import DFLConfig, DFLTrainer
from repro.launch.mesh import make_local_mesh
from repro.models import Batch, build_model
from repro.obs import scopes

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = {"d_model": 64, "d_ff": 128, "n_layers": 2, "n_heads": 4, "n_kv_heads": 2,
        "head_dim": 16, "vocab": 200}
# 512 tokens: two blocks of the attention's query-block loop, as in the cells
SEQ, BATCH = 512, 2
_DEBUG_BLOCKS = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")


def _compiled_step_text() -> str:
    cfg = get_arch("smollm-360m").replace(**TINY)
    trainer = DFLTrainer(build_model(cfg), make_local_mesh((1, 1), ("data", "model")),
                         DFLConfig(gossip_mode="dissemination"))
    state = jax.eval_shape(trainer.init_state, jax.random.PRNGKey(0))
    tok = jax.ShapeDtypeStruct((BATCH, SEQ), jnp.int32)
    batch = Batch(tokens=tok, labels=tok)
    step = trainer.jitted_train_step(state, batch)
    return step.lower(state, batch).compile().as_text()


def _op_names(hlo: str) -> set:
    return set(re.findall(r'op_name="([^"]*)"', hlo))


def _segments(op_name: str) -> list:
    """The path's names, a transform's wrapper counting as its content."""
    out = []
    for seg in op_name.split("/"):
        while seg.endswith(")") and "(" in seg:
            seg = seg[seg.index("(") + 1:-1]
        out.append(seg)
    return out


def _without_metadata(hlo: str) -> str:
    blocks = [b for b in hlo.split("\n\n") if not b.lstrip().startswith(_DEBUG_BLOCKS)]
    return re.sub(r",? metadata=\{[^}]*\}", "", "\n\n".join(blocks))


@pytest.fixture(scope="module")
def step_text() -> str:
    return _compiled_step_text()


def test_step_carries_layer_scopes_forward_and_backward(step_text):
    names = _op_names(step_text)
    under = {s: [n for n in names if s in _segments(n)]
             for s in (scopes.ATTENTION, scopes.MLP, scopes.LM_HEAD_LOSS, scopes.EMBED,
                       scopes.GRADS, scopes.CLIP, scopes.UPDATE, scopes.GOSSIP)}
    assert all(under.values()), [s for s, ns in under.items() if not ns]
    attn = under[scopes.ATTENTION]
    assert any("jvp(" in n and "transpose(" not in n for n in attn)  # forward
    assert any("transpose(" in n for n in attn)  # backward
    # the model's layers run inside the gradient, the optimizer outside it
    # (a reduction's own computation carries a path without the jit's root)
    assert all(scopes.GRADS in _segments(n) for n in attn + under[scopes.MLP]
               if n.startswith("jit("))
    assert not any(scopes.GRADS in _segments(n) for n in under[scopes.UPDATE])


def test_mamba2_step_carries_ssm_and_ssd_scopes():
    """In a Mamba2 layer (granite-4.0-h's stack at a tiny width), the chunked
    scan sits in ``ssm.ssd`` inside ``ssm``, forward and backward, apart from
    the projections, conv and gated norm, which ``ssm`` holds alone."""
    cfg = get_arch("granite-4.0-h-micro").smoke_variant().replace(**dict(TINY, n_layers=10))
    trainer = DFLTrainer(build_model(cfg), make_local_mesh((1, 1), ("data", "model")))
    state = jax.eval_shape(trainer.init_state, jax.random.PRNGKey(0))
    tok = jax.ShapeDtypeStruct((1, SEQ), jnp.int32)
    batch = Batch(tokens=tok, labels=tok)
    names = _op_names(trainer.jitted_train_step(state, batch).lower(state, batch)
                      .compile().as_text())
    ssd = [n for n in names if scopes.SSM_SSD in _segments(n)]
    assert ssd and all(scopes.SSM in _segments(n) for n in ssd if n.startswith("jit("))
    assert any("transpose(" in n for n in ssd)
    assert any(scopes.SSM in _segments(n) and scopes.SSM_SSD not in _segments(n)
               for n in names)


def test_scopes_change_only_metadata(step_text, monkeypatch):
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    plain = _compiled_step_text()
    assert not any(scopes.ATTENTION in _segments(n) for n in _op_names(plain))
    assert _without_metadata(plain) == _without_metadata(step_text)


def test_recorder_span_reaches_the_profiler_host_plane(tmp_path):
    from jax.profiler import ProfileData

    f = jax.jit(lambda x: x * 2.0)
    f(jnp.ones(8)).block_until_ready()
    rec = obs.Recorder()
    jax.profiler.start_trace(str(tmp_path))
    with rec.span("test:traced", cat="t", track="t"):
        f(jnp.ones(8)).block_until_ready()
    with obs.NULL_RECORDER.span("test:off"):  # observability off stays a no-op
        f(jnp.ones(8)).block_until_ready()
    jax.profiler.stop_trace()
    (path,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    host = [ev.name for plane in ProfileData.from_file(str(path)).planes
            if plane.name == "/host:CPU" for line in plane.lines for ev in line.events]
    assert "test:traced" in host and "test:off" not in host
    assert [s.name for s in rec.spans] == ["test:traced"]  # the recorder's own span stays


def test_gossip_steps_scoped_and_session_dispatch_spans():
    code = textwrap.dedent("""
        import jax, re
        from repro import obs
        from repro.configs import get_arch
        from repro.dfl import DFLConfig, DFLTrainer
        from repro.dfl.session import DFLSession
        from repro.launch.mesh import make_local_mesh
        from repro.models import Batch, build_model
        cfg = get_arch("smollm-360m").smoke_variant()
        mesh = make_local_mesh((4, 1), ("data", "model"))
        tr = DFLTrainer(build_model(cfg), mesh,
                        DFLConfig(gossip_mode="dissemination", gossip_interval=2))
        state = tr.init_state(jax.random.PRNGKey(0))
        tok = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, cfg.vocab)
        batch = Batch(tokens=tok, labels=tok)
        step = tr.jitted_train_step(jax.eval_shape(lambda: state),
                                    jax.eval_shape(lambda: batch))
        text = step.lower(state, batch).compile().as_text()
        print("STEPS", len(tr.plan.diss_steps))
        print("SCOPES", " ".join(sorted(set(re.findall(r"(dfl\\.gossip|gossip\\.step\\d+)/", text)))))
        with obs.recording(obs.Recorder()) as rec:
            session = DFLSession(tr)
            state, _ = session.train_round(state, batch, local_steps=3)
        print("SPANS", " ".join(f"{s.name}:{s.args.get('gossip', '-')}" for s in rec.spans))
    """)
    env = dict(os.environ, XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(ROOT, "src"))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True,
                         env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    lines = dict(line.split(" ", 1) for line in out.stdout.splitlines() if " " in line)
    n_steps = int(lines["STEPS"])
    assert n_steps > 1
    assert set(lines["SCOPES"].split()) == (
        {scopes.GOSSIP} | {scopes.gossip_step(i) for i in range(n_steps)})
    # interval 2 from step 0: the second of three steps gossips
    assert lines["SPANS"].split() == ["plan:recompile:-", "train:dispatch:False",
                                      "train:dispatch:True", "train:dispatch:False"]


def test_launch_profile_dir_writes_a_trace_of_the_steps(tmp_path):
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"), JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--smoke", "--steps", "3",
         "--seq-len", "32", "--log-every", "100", "--profile-dir", str(tmp_path / "prof")],
        capture_output=True, text=True, env=env, timeout=600, cwd=str(tmp_path))
    assert out.returncode == 0, out.stderr[-3000:]
    assert "steps/s after the first" in out.stdout
    from jax.profiler import ProfileData

    (path,) = (tmp_path / "prof").glob("plugins/profile/*/*.xplane.pb")
    host = [ev.name for plane in ProfileData.from_file(str(path)).planes
            if plane.name == "/host:CPU" for line in plane.lines for ev in line.events]
    assert host.count("train") == 2  # steps 2 and 3; the first, with its compile, is not traced
