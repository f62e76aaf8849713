"""The fused causal flash-attention path of ``models.attention.attention``.

Plain causal self-attention goes through JAX's Pallas flash kernel on a TPU;
every other case keeps the query-block scan. Here the kernel runs in TPU
interpret mode on the CPU, and the dispatch is read from the traced program
(a ``pallas_call`` is there or not), with the backend check steered in the
test.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from repro.models import attention as attn_lib
from repro.models.layers import get_mesh_ctx, set_mesh_ctx
from repro.obs import scopes

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
B, S, D, H, KV, HD = 1, 256, 128, 4, 2, 64


@pytest.fixture
def on_tpu(monkeypatch):
    """No mesh, and the backend check answering TPU; caches cleared so no
    trace taken for the CPU is reused."""
    was = get_mesh_ctx()
    set_mesh_ctx(None)
    jax.clear_caches()
    monkeypatch.setattr(attn_lib, "_on_tpu", lambda: True)
    yield
    jax.clear_caches()
    set_mesh_ctx(*was)


def _inputs(s: int = S, b: int = B):
    kp, kx, kc = jax.random.split(jax.random.PRNGKey(0), 3)
    params = attn_lib.init_attention(kp, D, H, KV, HD, jnp.bfloat16)
    x = jax.random.normal(kx, (b, s, D), jnp.float32).astype(jnp.bfloat16)
    cot = jax.random.normal(kc, (b, s, D), jnp.float32)
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    return params, x, cot, positions


def _loss_and_grads(params, x, cot, positions, **kw):
    def loss(p, x_):
        out = attn_lib.attention(p, x_, positions, causal=True, **kw)
        return jnp.sum(out.astype(jnp.float32) * cot), out

    (_, out), grads = jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)(params, x)
    return out, grads


def _f32(t):
    return np.asarray(t.astype(jnp.float32))


def test_fused_matches_scan_forward_and_backward(on_tpu):
    params, x, cot, positions = _inputs()
    ref_out, (ref_dp, ref_dx) = jax.jit(
        lambda p, x_: _loss_and_grads(p, x_, cot, positions))(params, x)
    with pltpu.force_tpu_interpret_mode():
        out, (dp, dx) = jax.jit(lambda p, x_: _loss_and_grads(
            p, x_, cot, positions, positions_are_rows=True))(params, x)
    np.testing.assert_allclose(_f32(out), _f32(ref_out), rtol=0.05, atol=0.02)
    for name in ("wq", "wk", "wv", "wo"):
        g, ref = _f32(dp[name]), _f32(ref_dp[name])
        assert np.abs(g - ref).max() <= 0.03 * np.abs(ref).max(), name
    g, ref = _f32(dx), _f32(ref_dx)
    assert np.abs(g - ref).max() <= 0.03 * np.abs(ref).max()


def test_nope_at_a_set_scale_takes_the_kernel_and_matches_the_scan(on_tpu):
    """granite-4.0-h's attention: no positional embedding, scores scaled by
    1/64 rather than head_dim ** -0.5. It takes the kernel, at its cell's
    8192 too, and the kernel's scale is the one asked for."""
    params, x, cot, positions = _inputs()
    x = x * 16  # scores large enough that their scale shows in the output
    kw = dict(use_rope=False, scale=1 / 64)
    assert _takes_kernel(**kw) and _takes_kernel(s=8192, grad=True, **kw)
    ref_out, (ref_dp, ref_dx) = jax.jit(
        lambda p, x_: _loss_and_grads(p, x_, cot, positions, **kw))(params, x)
    with pltpu.force_tpu_interpret_mode():
        out, (dp, dx) = jax.jit(lambda p, x_: _loss_and_grads(
            p, x_, cot, positions, positions_are_rows=True, **kw))(params, x)
        default_scale, _ = jax.jit(lambda p, x_: _loss_and_grads(
            p, x_, cot, positions, positions_are_rows=True, use_rope=False))(params, x)
    np.testing.assert_allclose(_f32(out), _f32(ref_out), rtol=0.05, atol=0.02)
    for name in ("wq", "wk", "wv", "wo"):
        g, ref = _f32(dp[name]), _f32(ref_dp[name])
        assert np.abs(g - ref).max() <= 0.03 * np.abs(ref).max(), name
    g, ref = _f32(dx), _f32(ref_dx)
    assert np.abs(g - ref).max() <= 0.03 * np.abs(ref).max()
    assert np.abs(_f32(default_scale) - _f32(out)).max() > 0.1 * np.abs(_f32(out)).max()


def _takes_kernel(s: int = S, positions_are_rows: bool = True, hd: int = HD,
                  grad: bool = False, **kw) -> bool:
    """Whether the traced call, or with ``grad`` its gradient, holds the
    kernel (shapes only, nothing runs)."""
    params = jax.eval_shape(lambda: attn_lib.init_attention(
        jax.random.PRNGKey(0), D, H, KV, hd, jnp.bfloat16))
    x = jax.ShapeDtypeStruct((B, s, D), jnp.bfloat16)
    positions = jnp.broadcast_to(jnp.arange(s), (B, s))

    def call(p, x_):
        return attn_lib.attention(p, x_, positions, positions_are_rows=positions_are_rows, **kw)

    traced = jax.grad(lambda p, x_: jnp.sum(call(p, x_).astype(jnp.float32))) if grad else call
    return "pallas_call" in str(jax.make_jaxpr(traced)(params, x))


def test_plain_causal_self_attention_takes_the_kernel(on_tpu):
    params, x, _, positions = _inputs()
    assert _takes_kernel()
    with pltpu.force_tpu_interpret_mode():
        text = jax.jit(lambda p, x_: attn_lib.attention(
            p, x_, positions, positions_are_rows=True)).lower(params, x).as_text(
                debug_info=True)
    assert f"{scopes.ATTENTION}/{scopes.ATTENTION_FLASH}/" in text


@pytest.mark.parametrize("case", [
    dict(sliding_window=64),
    dict(softcap=30.0),
    dict(prefix_len=16),
    dict(kv_override="cross"),
    dict(causal=False),
    dict(s=S + 64),  # no multiple of the kernel's smallest block
    dict(positions_are_rows=False),
    dict(hd=160, s=2048, grad=True),  # stablelm-12b: above 128 and no multiple of it
], ids=["window", "softcap", "prefix", "kv_override", "bidirectional", "odd_length",
        "positions_not_rows", "head_dim_160"])
def test_other_attention_keeps_the_scan(on_tpu, case):
    case = dict(case)
    if case.pop("kv_override", None):
        k = jnp.zeros((B, 32, KV, HD), jnp.bfloat16)
        case.update(kv_override=(k, k), kv_positions=None, use_rope=False)
    assert not _takes_kernel(**case)


@pytest.mark.parametrize("hd", [64, 112, 128, 256])
def test_head_dims_the_kernel_takes(on_tpu, hd):
    """At 2048 the kernel's key tile is shorter than the sequence, the case
    in which it refuses other head sizes; tracing the gradient builds the
    forward and both backward kernels at this head size."""
    assert _takes_kernel(s=2048, hd=hd, grad=True)


def test_off_the_tpu_plain_causal_keeps_the_scan():
    assert not attn_lib._on_tpu()
    assert not _takes_kernel()


def test_fused_matches_scan_under_a_2x2_mesh():
    """Under a mesh the kernel runs per shard inside ``shard_map``: batch over
    ``data``, heads over ``model``. Four CPU devices need a fresh process."""
    code = textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.experimental.pallas import tpu as pltpu
        from repro.launch.mesh import make_local_mesh
        from repro.models import attention as attn_lib
        from repro.models.layers import set_mesh_ctx
        import test_attention_fused as t

        set_mesh_ctx(make_local_mesh((2, 2), ("data", "model")), ("data",))
        attn_lib._on_tpu = lambda: True
        params, x, cot, positions = t._inputs(s=128, b=2)
        ref = jax.jit(lambda p, x_: t._loss_and_grads(p, x_, cot, positions))(params, x)
        with pltpu.force_tpu_interpret_mode():
            got = jax.jit(lambda p, x_: t._loss_and_grads(
                p, x_, cot, positions, positions_are_rows=True))(params, x)
        for g, r in zip(jax.tree.leaves(got), jax.tree.leaves(ref)):
            g, r = t._f32(g), t._f32(r)
            assert np.abs(g - r).max() <= 0.03 * np.abs(r).max(), (np.abs(g - r).max(), np.abs(r).max())
        print("OK")
    """)
    env = dict(os.environ, XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([os.path.join(ROOT, "src"), os.path.join(ROOT, "tests")]))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert out.stdout.strip().endswith("OK")
