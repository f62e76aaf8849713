"""Plain float32 reference of one DFL silo's local step on a hybrid stack of
Mamba2 and attention layers (granite-4.0-h).

Forward, loss and gradients in plain ``jax.numpy`` under
``default_matmul_precision("highest")``, then clipping by the global norm and
AdamW with float32 moments (``dense.adamw``), from a configuration file's
sizes and a traffic file's optimizer settings. It imports nothing of the
program. Each layer is ``x + r * mixer(RMSNorm(x))`` and then ``x + r *
MLP(RMSNorm(x))``, the mixer a Mamba2 layer or attention as the file's
``layer_types`` say. The Mamba2 scan runs one time step at a time,

    h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T,    y_t = h_t C_t + D x_t,

with no decomposition into chunks, in ``jax.checkpoint``ed blocks of
:data:`TIME_BLOCK` steps, so that the states its gradient needs fit one chip
at 8192 tokens. Rows of the batch run one at a time, and attention in blocks
of queries, as in ``dense.py``.

The weights are drawn from the seed by the same scheme as the program under
test, which the reference implements itself: matrices normal with standard
deviation 0.02 (the conv's taps 0.5), rounded to bfloat16; norm weights and
the conv's bias 0; per Mamba2 head ``h``, ``A_log = log(h + 1)``, ``D = 1``
and ``dt_bias`` the inverse softplus of a log-even spread over [1e-3, 1e-1].

Departures from the published granite-4.0-h description, which the program
shares, so that both compute one function:

- RMSNorm, the gated one included, multiplies by ``1 + w`` with ``w``
  starting at 0, and its epsilon is 1e-6 (published 1e-5; the configuration
  file lists it as changed).
- Mamba2's in_proj is three matrices (z, xBC, dt), the published one matrix
  split by its output columns in the published order.
- The embedding table's rows are padded to a multiple of 128. The padded
  rows are never read; only weight decay moves them.

``variant`` selects what is computed in the program's place:

- ``"f32"``: the reference.
- ``"fp8"``: the control: every matrix product takes its operands in float8
  (e4m3, scaled per tensor) and its cotangents in float8 (e5m2), the
  precision below the bfloat16 the configuration states (``dense.py``).
- ``"chunk_reset"``: a fault: the Mamba2 state is zeroed at every 256-token
  boundary, as a scan whose pass between chunks is broken would give.
- ``"ungated_norm"``: a fault: the ``silu(z)`` gate before the gated norm is
  left out.
"""
from __future__ import annotations

import itertools
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from reference.dense import VOCAB_PAD, AdamW, _mm, _norm, _rms, adamw, leaf_dict, leaf_norms

Q_BLOCK = 512
TIME_BLOCK = 256  # the fault's chunk, and the steps between saved states
VARIANTS = ("f32", "fp8", "chunk_reset", "ungated_norm")


@dataclass(frozen=True)
class Hybrid:
    """The sizes of a granite-4.0-h stack, by the published config's names."""

    layer_types: tuple  # "mamba" or "attention" per layer, in order
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    vocab: int
    eps: float
    ssm_heads: int
    ssm_head_dim: int
    ssm_state: int
    ssm_groups: int
    conv: int
    embedding_multiplier: float
    residual_multiplier: float
    attention_multiplier: float
    logits_scaling: float

    @property
    def padded_vocab(self) -> int:
        return -(-self.vocab // VOCAB_PAD) * VOCAB_PAD

    @property
    def d_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.ssm_groups * self.ssm_state

    @property
    def runs(self) -> list:
        """The layers as runs of one kind: ``[("mamba", 5), ("attention", 1), ...]``."""
        return [(k, len(list(g))) for k, g in itertools.groupby(self.layer_types)]

    @classmethod
    def from_config(cls, c: dict) -> "Hybrid":
        return cls(layer_types=tuple(c["layer_types"][: c["num_hidden_layers"]]),
                   d=c["hidden_size"], heads=c["num_attention_heads"],
                   kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
                   ff=c["shared_intermediate_size"], vocab=c["vocab_size"],
                   eps=float(c["rms_norm_eps"]), ssm_heads=c["mamba_n_heads"],
                   ssm_head_dim=c["mamba_d_head"], ssm_state=c["mamba_d_state"],
                   ssm_groups=c["mamba_n_groups"], conv=c["mamba_d_conv"],
                   embedding_multiplier=float(c["embedding_multiplier"]),
                   residual_multiplier=float(c["residual_multiplier"]),
                   attention_multiplier=float(c["attention_multiplier"]),
                   logits_scaling=float(c["logits_scaling"]))


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------


def _normal(key, shape, std=0.02):
    return (jax.random.normal(key, shape, jnp.float32) * std
            ).astype(jnp.bfloat16).astype(jnp.float32)


def _mamba_weights(key, m: Hybrid):
    kz, kx, kdt, kc, ko = jax.random.split(key, 5)
    h = m.ssm_heads
    dt = np.exp(np.linspace(np.log(1e-3), np.log(1e-1), h))
    return {"wz": _normal(kz, (m.d, m.d_inner)), "wxBC": _normal(kx, (m.d, m.conv_dim)),
            "wdt": _normal(kdt, (m.d, h)), "conv_w": _normal(kc, (m.conv, m.conv_dim), 0.5),
            "conv_b": jnp.zeros((m.conv_dim,), jnp.float32),
            "A_log": jnp.asarray(np.log(np.arange(1, h + 1)), jnp.float32),
            "dt_bias": jnp.asarray(dt + np.log(-np.expm1(-dt)), jnp.float32),
            "D": jnp.ones((h,), jnp.float32),
            "norm": jnp.zeros((m.d_inner,), jnp.float32),
            "out_proj": _normal(ko, (m.d_inner, m.d))}


def _layer_weights(key, kind: str, m: Hybrid):
    k_mix, k_mlp = jax.random.split(key)
    if kind == "attention":
        kq, kk, kv, ko = jax.random.split(k_mix, 4)
        mixer = {"attn": {"wq": _normal(kq, (m.d, m.heads, m.head_dim)),
                          "wk": _normal(kk, (m.d, m.kv_heads, m.head_dim)),
                          "wv": _normal(kv, (m.d, m.kv_heads, m.head_dim)),
                          "wo": _normal(ko, (m.heads, m.head_dim, m.d))}}
    else:
        mixer = {"mamba": _mamba_weights(k_mix, m)}
    kg, ki, kw = jax.random.split(k_mlp, 3)
    zeros = jnp.zeros((m.d,), jnp.float32)
    return {"ln1": zeros, **mixer, "ln2": zeros,
            "mlp": {"wg": _normal(kg, (m.d, m.ff)), "wi": _normal(ki, (m.d, m.ff)),
                    "wo": _normal(kw, (m.ff, m.d))}}


def init_params(key, m: Hybrid):
    """Float32 weights, drawn from ``key``; each run's layers stacked."""
    keys = jax.random.split(key, 8)
    return {
        "embed": {"table": _normal(keys[0], (m.padded_vocab, m.d))},
        "final_norm": jnp.zeros((m.d,), jnp.float32),
        "runs": [jax.vmap(lambda k, kind=kind: _layer_weights(k, kind, m))(
                     jax.random.split(jax.random.fold_in(keys[1], i), n))
                 for i, (kind, n) in enumerate(m.runs)],
    }


# ---------------------------------------------------------------------------
# forward and loss of one row
# ---------------------------------------------------------------------------


def _attention(p, x, m: Hybrid, fp8: bool):
    """Causal softmax attention, no positional embedding, scores times
    ``attention_multiplier``."""
    s = x.shape[0]
    q = _mm("sd,dhk->shk", x, p["wq"], fp8)
    k = jnp.repeat(_mm("sd,dhk->shk", x, p["wk"], fp8), m.heads // m.kv_heads, axis=1)
    v = jnp.repeat(_mm("sd,dhk->shk", x, p["wv"], fp8), m.heads // m.kv_heads, axis=1)
    qb = min(Q_BLOCK, s)
    keys = jnp.arange(s)

    @jax.checkpoint
    def block(args):
        qi, pos = args
        scores = _mm("qhk,shk->hqs", qi, k, fp8) * m.attention_multiplier
        scores = jnp.where(keys[None, None, :] <= pos[None, :, None], scores, -jnp.inf)
        return _mm("hqs,shk->qhk", jax.nn.softmax(scores, axis=-1), v, fp8)

    out = jax.lax.map(block, (q.reshape(s // qb, qb, *q.shape[1:]), keys.reshape(s // qb, qb)))
    return _mm("shk,hkd->sd", out.reshape(q.shape), p["wo"], fp8)


def _scan(x, dt, A, B, C, reset: bool):
    """y_t = h_t C_t with h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T from a zero
    state, one step at a time. x (s, H, P), dt (s, H), B and C (s, H, N) per
    head. With ``reset`` the state is zeroed at each block's start."""
    s, H, P = x.shape
    n_blocks = -(-s // TIME_BLOCK)
    pad = n_blocks * TIME_BLOCK - s

    def blocks(t):
        t = jnp.pad(t, [(0, pad)] + [(0, 0)] * (t.ndim - 1))
        return t.reshape(n_blocks, TIME_BLOCK, *t.shape[1:])

    def step(h, inputs):
        x_t, dt_t, B_t, C_t = inputs
        h = (jnp.exp(dt_t * A)[:, None, None] * h
             + (dt_t[:, None] * x_t)[:, :, None] * B_t[:, None, :])
        return h, jnp.einsum("hpn,hn->hp", h, C_t)

    @jax.checkpoint
    def block(h, inputs):
        if reset:
            h = jnp.zeros_like(h)
        return jax.lax.scan(step, h, inputs, unroll=8)

    h0 = jnp.zeros((H, P, B.shape[-1]), jnp.float32)
    _, y = jax.lax.scan(block, h0, tuple(blocks(t) for t in (x, dt, B, C)))
    return y.reshape(n_blocks * TIME_BLOCK, H, P)[:s]


def _mamba(p, u, m: Hybrid, variant: str):
    fp8 = variant == "fp8"
    s, di, gn = u.shape[0], m.d_inner, m.ssm_groups * m.ssm_state
    z = _mm("sd,dk->sk", u, p["wz"], fp8)
    xbc = _mm("sd,dk->sk", u, p["wxBC"], fp8)
    dt = jax.nn.softplus(_mm("sd,dh->sh", u, p["wdt"], fp8) + p["dt_bias"])
    padded = jnp.pad(xbc, ((m.conv - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(padded[i:i + s] * p["conv_w"][i] for i in range(m.conv))
                      + p["conv_b"])
    x = xbc[:, :di].reshape(s, m.ssm_heads, m.ssm_head_dim)
    per_head = m.ssm_heads // m.ssm_groups
    B = jnp.repeat(xbc[:, di:di + gn].reshape(s, m.ssm_groups, -1), per_head, axis=1)
    C = jnp.repeat(xbc[:, di + gn:].reshape(s, m.ssm_groups, -1), per_head, axis=1)
    y = _scan(x, dt, -jnp.exp(p["A_log"]), B, C, reset=variant == "chunk_reset")
    y = (y + p["D"][:, None] * x).reshape(s, di)
    if variant != "ungated_norm":
        y = y * jax.nn.silu(z)
    g = y.reshape(s, m.ssm_groups, -1)
    g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + m.eps)
    return _mm("sk,kd->sd", g.reshape(s, di) * (1.0 + p["norm"]), p["out_proj"], fp8)


def _mlp(p, x, fp8: bool):
    g = jax.nn.silu(_mm("sd,df->sf", x, p["wg"], fp8))
    return _mm("sf,fd->sd", g * _mm("sd,df->sf", x, p["wi"], fp8), p["wo"], fp8)


def row_loss_sum(params, tokens, labels, m: Hybrid, variant: str = "f32"):
    """Summed cross-entropy of one row (tokens and labels of shape (s,))."""
    fp8, r = variant == "fp8", m.residual_multiplier
    x = params["embed"]["table"][tokens] * m.embedding_multiplier

    for (kind, _), run in zip(m.runs, params["runs"]):
        @jax.checkpoint
        def layer(x, blk, kind=kind):
            u = _rms(x, blk["ln1"], m.eps)
            if kind == "attention":
                x = x + r * _attention(blk["attn"], u, m, fp8)
            else:
                x = x + r * _mamba(blk["mamba"], u, m, variant)
            return x + r * _mlp(blk["mlp"], _rms(x, blk["ln2"], m.eps), fp8), None

        x, _ = jax.lax.scan(layer, x, run)
    x = _rms(x, params["final_norm"], m.eps)
    logits = _mm("sd,vd->sv", x, params["embed"]["table"][: m.vocab], fp8) / m.logits_scaling
    picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.sum(jax.nn.logsumexp(logits, axis=-1) - picked)


# ---------------------------------------------------------------------------
# the step
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("m", "opt", "variant"))
def clipped_grads(params, tokens, labels, *, m: Hybrid, opt: AdamW, variant: str):
    """Mean loss over a batch (rows, s) and its gradient, clipped by the
    global norm; one row at a time, the rows' gradients summed in float32."""
    with jax.default_matmul_precision("highest"):
        grad_row = jax.value_and_grad(partial(row_loss_sum, m=m, variant=variant))

        def acc(carry, row):
            loss, g = carry
            lr, gr = grad_row(params, *row)
            return (loss + lr, jax.tree.map(jnp.add, g, gr)), None

        zero = jax.tree.map(jnp.zeros_like, params)
        (loss, grads), _ = jax.lax.scan(acc, (jnp.zeros((), jnp.float32), zero),
                                        (tokens, labels))
        grads = jax.tree.map(lambda g: g / tokens.size, grads)
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads)))
        scale = jnp.minimum(1.0, opt.max_grad_norm / jnp.maximum(gnorm, 1e-9))
        return loss / tokens.size, jax.tree.map(lambda g: g * scale, grads)


@partial(jax.jit, static_argnames=("m",))
def change_norms(key, params, *, m: Hybrid):
    """Each leaf's norm of its change since the weights were drawn."""
    return jax.tree.map(lambda p, p0: _norm(p - p0), params, init_params(key, m))


def run(key, batches, m: Hybrid, opt: AdamW, variant: str = "f32"):
    """``len(batches)`` steps from the weights drawn from ``key``: the loss of
    each step, each leaf's norm of the first clipped gradient, and each leaf's
    norm of the change after the last step, keyed by the leaf's path."""
    if variant not in VARIANTS:
        raise ValueError(f"variant {variant!r}, not one of {VARIANTS}")
    params = jax.jit(partial(init_params, m=m))(key)
    mom = vel = jax.tree.map(lambda p: np.zeros(p.shape, np.float32), params)
    losses, first_grad = [], None
    for t, (tokens, labels) in enumerate(batches):
        loss, grads = clipped_grads(params, jnp.asarray(tokens), jnp.asarray(labels),
                                    m=m, opt=opt, variant=variant)
        losses.append(float(loss))
        if first_grad is None:
            first_grad = leaf_dict(leaf_norms(grads))
        params, mom, vel = adamw(params, grads, jax.device_put(mom), jax.device_put(vel),
                                 jnp.asarray(t, jnp.int32), opt=opt)
        mom, vel = jax.device_get((mom, vel))
    del mom, vel
    return {"losses": losses, "first_grad": first_grad,
            "change": leaf_dict(change_norms(key, params, m=m))}
