"""The Mamba2 mixer (``models/mamba.py``) and the interleaved stack it runs in
(granite-4.0-h), at tiny sizes on the CPU.

The chunked SSD scan is checked against the recurrence as published, run one
time step at a time in float32 here, outputs and gradients alike; the decode
step against the full-sequence forward across a chunk boundary; the stack
through ``DFLTrainer``'s step.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.models import Batch, build_model
from repro.models import mamba as mamba_lib

D_MODEL, HEADS, HEAD_DIM, N, CONV = 32, 4, 16, 32, 4
EPS = 1e-6


def _params(groups: int, seed: int = 0):
    p = mamba_lib.init_mamba2(jax.random.PRNGKey(seed), D_MODEL, HEADS * HEAD_DIM, N, groups,
                              CONV, jnp.float32, head_dim=HEAD_DIM)
    # move the zero-initialised leaves off zero, so that each one matters
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed + 1), 3)
    p["conv_b"] = 0.1 * jax.random.normal(k1, p["conv_b"].shape)
    p["norm"] = 0.1 * jax.random.normal(k2, p["norm"].shape)
    p["D"] = p["D"] + 0.1 * jax.random.normal(k3, p["D"].shape)
    return p


def _sequential_mixer(p, u, groups: int):
    """The published Mamba2 mixer, its scan one step at a time, in f32."""
    b, s, _ = u.shape
    di = HEADS * HEAD_DIM
    z = u @ p["wz"]
    xbc = u @ p["wxBC"]
    dt = jax.nn.softplus(u @ p["wdt"] + p["dt_bias"])  # (b, s, H)
    padded = jnp.pad(xbc, ((0, 0), (CONV - 1, 0), (0, 0)))
    xbc = sum(padded[:, i:i + s] * p["conv_w"][i] for i in range(CONV)) + p["conv_b"]
    xbc = jax.nn.silu(xbc)
    x = xbc[..., :di].reshape(b, s, HEADS, HEAD_DIM)
    gn = groups * N
    Bm = jnp.repeat(xbc[..., di:di + gn].reshape(b, s, groups, N), HEADS // groups, axis=2)
    Cm = jnp.repeat(xbc[..., di + gn:].reshape(b, s, groups, N), HEADS // groups, axis=2)
    A = -jnp.exp(p["A_log"])

    def step(h, t):  # h (b, H, P, N)
        x_t, dt_t, B_t, C_t = t
        h = (jnp.exp(dt_t * A)[..., None, None] * h
             + (dt_t[..., None] * x_t)[..., None] * B_t[:, :, None, :])
        return h, jnp.einsum("bhpn,bhn->bhp", h, C_t)

    h0 = jnp.zeros((b, HEADS, HEAD_DIM, N))
    _, y = jax.lax.scan(step, h0, tuple(t.swapaxes(0, 1) for t in (x, dt, Bm, Cm)))
    y = y.swapaxes(0, 1) + p["D"][:, None] * x
    g = (y.reshape(b, s, di) * jax.nn.silu(z)).reshape(b, s, groups, -1)
    g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + EPS)
    return (g.reshape(b, s, di) * (1 + p["norm"])) @ p["out_proj"]


def _close(got, ref, rel):
    got, ref = np.asarray(got), np.asarray(ref)
    assert np.abs(got - ref).max() <= rel * np.abs(ref).max(), np.abs(got - ref).max()


@pytest.mark.parametrize("groups", [1, 2])
@pytest.mark.parametrize("s", [256, 512, 320], ids=["one_chunk", "two_chunks", "short_last"])
def test_chunked_ssd_matches_the_sequential_recurrence(groups, s):
    """Outputs and gradients of the mixer in float32, SSD chunks of 256
    against the step-by-step scan: they compute one function in another
    order, so they agree to float32 rounding (the within-chunk decays are
    exponentials of cumulative sums up to about 100 in size, which carry
    relative errors near 1e-5)."""
    p = _params(groups)
    u = jax.random.normal(jax.random.PRNGKey(5), (2, s, D_MODEL))
    cot = jax.random.normal(jax.random.PRNGKey(6), (2, s, D_MODEL))

    def loss(fn):
        return jax.jit(jax.value_and_grad(
            lambda p_, u_: jnp.sum(fn(p_, u_) * cot), argnums=(0, 1)))

    out, (dp, du) = loss(lambda p_, u_: mamba_lib.mamba2_forward(p_, u_, groups))(p, u)
    ref, (rdp, rdu) = loss(lambda p_, u_: _sequential_mixer(p_, u_, groups))(p, u)
    _close(out, ref, 1e-4)
    _close(mamba_lib.mamba2_forward(p, u, groups), _sequential_mixer(p, u, groups), 1e-4)
    _close(du, rdu, 1e-4)
    for name in rdp:
        _close(dp[name], rdp[name], 1e-4)


@pytest.mark.parametrize("groups", [1, 2])
def test_decode_steps_match_the_forward_across_a_chunk(groups):
    """The recurrence of the decode step, fed one token at a time from an empty
    cache, gives the full-sequence forward's output at every position, past
    the first chunk's end."""
    p = _params(groups)
    b, s = 2, 272
    u = jax.random.normal(jax.random.PRNGKey(7), (b, s, D_MODEL))
    full = mamba_lib.mamba2_forward(p, u, groups)
    cache = mamba_lib.init_mamba2_cache(b, HEADS * HEAD_DIM, N, groups, CONV, jnp.float32,
                                        head_dim=HEAD_DIM)
    step = jax.jit(lambda c, u_t: mamba_lib.mamba2_decode(p, u_t, c, groups)[::-1])
    _, outs = jax.lax.scan(step, cache, u.swapaxes(0, 1)[:, :, None])
    _close(outs[:, :, 0].swapaxes(0, 1), full, 1e-4)


def _tiny_granite(**kw):
    cfg = get_arch("granite-4.0-h-micro").smoke_variant()
    return cfg.replace(d_model=128, n_heads=4, n_kv_heads=2, head_dim=32, d_ff=256, **kw)


def test_tiny_stack_has_the_published_layer_order():
    cfg = _tiny_granite()
    model = build_model(cfg)
    assert cfg.n_layers == 10 and model.runs == [("M", 5), ("A", 1), ("M", 4)]
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    kinds = [("attn" if "attn" in run else "mamba", run["mlp"]["wg"].shape[:2])
             for run in params["runs"]]
    assert kinds == [("mamba", (1, 5)), ("attn", (1, 1)), ("mamba", (1, 4))]
    # the analytic count, and the final norm it leaves out
    assert sum(p.size for p in jax.tree.leaves(params)) == cfg.param_count() + cfg.d_model


def test_decode_matches_forward_across_a_chunk():
    """Teacher-forced decode of the tiny stack reproduces the full forward's
    logits at every position, past the first SSD chunk."""
    cfg = _tiny_granite()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(1))
    b, s = 1, 264
    tokens = jax.random.randint(jax.random.PRNGKey(2), (b, s), 0, cfg.vocab)
    full, _ = jax.jit(model.forward)(params, Batch(tokens=tokens))

    def step(cache, t):
        logits, cache = model.decode_step(params, tokens[:, t][:, None],
                                          jnp.full((b,), t, jnp.int32), cache)
        return cache, logits[:, 0]

    _, logits = jax.jit(lambda c: jax.lax.scan(step, c, jnp.arange(s)))(
        model.init_cache(b, s))
    _close(logits.swapaxes(0, 1)[..., :cfg.vocab], full[..., :cfg.vocab], 1e-4)


def test_tiny_stack_trains_through_the_dfl_step():
    """Ten layers in the published order, muP multipliers applied, through
    ``DFLTrainer.jitted_train_step``: finite losses that fall on a repeated
    batch."""
    from repro.dfl import DFLConfig, DFLTrainer
    from repro.launch.mesh import make_local_mesh

    cfg = _tiny_granite(dtype="bfloat16", remat=True)
    trainer = DFLTrainer(build_model(cfg), make_local_mesh((1, 1), ("data", "model")),
                         DFLConfig(lr=3e-3, warmup=0, total_steps=100))
    state = trainer.init_state(jax.random.PRNGKey(3))
    tok = jax.random.randint(jax.random.PRNGKey(4), (2, 256), 0, cfg.vocab)
    batch = Batch(tokens=tok, labels=jnp.roll(tok, -1, axis=1))
    step = trainer.jitted_train_step(jax.eval_shape(lambda: state),
                                     jax.eval_shape(lambda: batch))
    losses = []
    for _ in range(4):
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    assert np.all(np.isfinite(losses)) and losses[-1] < losses[0], losses
    assert abs(losses[0] - np.log(cfg.vocab)) < 1.0


@pytest.mark.parametrize("field, value", [("residual_multiplier", 0.5),
                                          ("attention_multiplier", 0.1),
                                          ("use_rope", False)])
def test_other_families_refuse_the_interleaved_fields(field, value):
    with pytest.raises(ValueError, match="interleaved family only"):
        build_model(get_arch("smollm-360m").replace(**{field: value}))
