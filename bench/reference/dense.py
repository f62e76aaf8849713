"""Plain float32 reference of one DFL silo's local step on a dense GQA decoder.

Forward, loss and gradients in plain ``jax.numpy`` under
``default_matmul_precision("highest")``, then clipping by the global norm and
AdamW with float32 moments, from a configuration file's sizes and a traffic
file's optimizer settings. It imports nothing of the program. It runs one row
of the batch at a time, each layer and each block of queries under
``jax.checkpoint``, so that its memory stays within one chip at the cells'
sizes; the arithmetic is that of the whole batch.

The weights are drawn from the seed by the same scheme as the decoder under
test (normal, standard deviation 0.02, rounded to bfloat16; norm weights 0),
which the reference implements itself.

Departures from the published Llama/Granite description, which the program
shares, so that both compute one function:

- RoPE rotates interleaved pairs ``(x[2i], x[2i+1])``; the published models
  rotate the two halves of each head. Both are rotations by the same angles
  of a different pairing of the coordinates.
- RMSNorm multiplies by ``1 + w`` with ``w`` starting at 0.
- Granite's muP multipliers (embedding, attention, residual, logits) are not
  applied; attention scales by ``head_dim ** -0.5``. The configuration file
  lists them as changed.
- The embedding table has its rows padded to a multiple of 128. The padded
  rows are never read; only weight decay moves them.

``variant`` selects what is computed in the program's place:

- ``"f32"``: the reference.
- ``"fp8"``: the control: every matrix product takes its operands in float8
  (e4m3, scaled per tensor) and its cotangents in float8 (e5m2, scaled per
  tensor), the precision below the bfloat16 the configuration states.
- ``"half_batch"``: a fault: half of the rows are left out and the mean is
  taken over the rest.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

VOCAB_PAD = 128
Q_BLOCK = 512


@dataclass(frozen=True)
class Dense:
    """The sizes of a dense GQA decoder, by the published config's names."""

    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    vocab: int
    rope_theta: float
    eps: float

    @property
    def padded_vocab(self) -> int:
        return -(-self.vocab // VOCAB_PAD) * VOCAB_PAD

    @classmethod
    def from_config(cls, c: dict) -> "Dense":
        return cls(layers=c["num_hidden_layers"], d=c["hidden_size"],
                   heads=c["num_attention_heads"], kv_heads=c["num_key_value_heads"],
                   head_dim=c["head_dim"], ff=c["intermediate_size"],
                   vocab=c["vocab_size"], rope_theta=float(c["rope_theta"]),
                   eps=float(c["rms_norm_eps"]))


@dataclass(frozen=True)
class AdamW:
    lr: float
    warmup: int
    total_steps: int
    final_frac: float
    b1: float
    b2: float
    eps: float
    weight_decay: float
    max_grad_norm: float

    @classmethod
    def from_traffic(cls, t: dict) -> "AdamW":
        o = t["optimizer"]
        return cls(**{k: o[k] for k in cls.__dataclass_fields__})

    def lr_at(self, step):
        step = jnp.asarray(step, jnp.float32)
        warm = self.lr * jnp.minimum(step / max(self.warmup, 1), 1.0)
        prog = jnp.clip((step - self.warmup) / max(self.total_steps - self.warmup, 1), 0.0, 1.0)
        cos = self.final_frac + (1 - self.final_frac) * 0.5 * (1 + jnp.cos(jnp.pi * prog))
        return jnp.where(step < self.warmup, warm, self.lr * cos)


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------


def _normal(key, shape):
    return (jax.random.normal(key, shape, jnp.float32) * 0.02
            ).astype(jnp.bfloat16).astype(jnp.float32)


def _block(key, m: Dense):
    k_attn, k_mlp = jax.random.split(key)
    kq, kk, kv, ko = jax.random.split(k_attn, 4)
    kg, ki, kw = jax.random.split(k_mlp, 3)
    zeros = jnp.zeros((m.d,), jnp.float32)
    return {
        "ln1": zeros,
        "attn": {"wq": _normal(kq, (m.d, m.heads, m.head_dim)),
                 "wk": _normal(kk, (m.d, m.kv_heads, m.head_dim)),
                 "wv": _normal(kv, (m.d, m.kv_heads, m.head_dim)),
                 "wo": _normal(ko, (m.heads, m.head_dim, m.d))},
        "ln2": zeros,
        "mlp": {"wg": _normal(kg, (m.d, m.ff)), "wi": _normal(ki, (m.d, m.ff)),
                "wo": _normal(kw, (m.ff, m.d))},
    }


def init_params(key, m: Dense):
    """Float32 weights, drawn from ``key``; blocks stacked over layers."""
    keys = jax.random.split(key, 8)
    return {
        "embed": {"table": _normal(keys[0], (m.padded_vocab, m.d))},
        "final_norm": jnp.zeros((m.d,), jnp.float32),
        "blocks": jax.vmap(lambda k: _block(k, m))(jax.random.split(keys[1], m.layers)),
    }


# ---------------------------------------------------------------------------
# precision of the matrix products
# ---------------------------------------------------------------------------


def _fp8(x, dtype):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / float(jnp.finfo(dtype).max)
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


@jax.custom_vjp
def _q8(x):
    return _fp8(x, jnp.float8_e4m3fn)


def _q8_fwd(x):
    return _q8(x), None


def _q8_bwd(_, ct):
    return (_fp8(ct, jnp.float8_e5m2),)


_q8.defvjp(_q8_fwd, _q8_bwd)


def _mm(spec, a, b, fp8: bool):
    if fp8:
        a, b = _q8(a), _q8(b)
    return jnp.einsum(spec, a, b)


# ---------------------------------------------------------------------------
# forward and loss of one row
# ---------------------------------------------------------------------------


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (1.0 + w)


def _rope(x, theta):  # x (s, h, hd)
    s, _, hd = x.shape
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None, None] * freqs  # (s, 1, hd/2)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).reshape(x.shape)


def _attention(p, x, m: Dense, fp8: bool):
    s = x.shape[0]
    q = _rope(_mm("sd,dhk->shk", x, p["wq"], fp8), m.rope_theta)
    k = _rope(_mm("sd,dhk->shk", x, p["wk"], fp8), m.rope_theta)
    v = _mm("sd,dhk->shk", x, p["wv"], fp8)
    k = jnp.repeat(k, m.heads // m.kv_heads, axis=1)
    v = jnp.repeat(v, m.heads // m.kv_heads, axis=1)
    qb = min(Q_BLOCK, s)
    keys = jnp.arange(s)

    @jax.checkpoint
    def block(args):
        qi, pos = args  # (qb, h, hd), (qb,)
        scores = _mm("qhk,shk->hqs", qi, k, fp8) * m.head_dim ** -0.5
        scores = jnp.where(keys[None, None, :] <= pos[None, :, None], scores, -jnp.inf)
        return _mm("hqs,shk->qhk", jax.nn.softmax(scores, axis=-1), v, fp8)

    out = jax.lax.map(block, (q.reshape(s // qb, qb, *q.shape[1:]),
                              keys.reshape(s // qb, qb)))
    return _mm("shk,hkd->sd", out.reshape(q.shape), p["wo"], fp8)


def _mlp(p, x, fp8: bool):
    g = jax.nn.silu(_mm("sd,df->sf", x, p["wg"], fp8))
    return _mm("sf,fd->sd", g * _mm("sd,df->sf", x, p["wi"], fp8), p["wo"], fp8)


def row_loss_sum(params, tokens, labels, m: Dense, fp8: bool = False):
    """Summed cross-entropy of one row (tokens and labels of shape (s,))."""
    x = params["embed"]["table"][tokens]

    @jax.checkpoint
    def layer(x, blk):
        x = x + _attention(blk["attn"], _rms(x, blk["ln1"], m.eps), m, fp8)
        x = x + _mlp(blk["mlp"], _rms(x, blk["ln2"], m.eps), fp8)
        return x, None

    x, _ = jax.lax.scan(layer, x, params["blocks"])
    x = _rms(x, params["final_norm"], m.eps)
    logits = _mm("sd,vd->sv", x, params["embed"]["table"][: m.vocab], fp8)
    picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.sum(jax.nn.logsumexp(logits, axis=-1) - picked)


# ---------------------------------------------------------------------------
# the step
# ---------------------------------------------------------------------------


def _norm(t):
    return jnp.sqrt(jnp.sum(jnp.square(t)))


@partial(jax.jit, static_argnames=("m", "opt", "variant"))
def clipped_grads(params, tokens, labels, *, m: Dense, opt: AdamW, variant: str):
    """Mean loss over a batch (rows, s) and its gradient, clipped by the
    global norm; one row at a time, the rows' gradients summed in float32."""
    with jax.default_matmul_precision("highest"):
        if variant == "half_batch":
            tokens, labels = tokens[: tokens.shape[0] // 2], labels[: labels.shape[0] // 2]
        grad_row = jax.value_and_grad(partial(row_loss_sum, m=m, fp8=variant == "fp8"))

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


@partial(jax.jit, static_argnames=("opt",), donate_argnums=(0, 2, 3))
def adamw(params, grads, mom, vel, t, *, opt: AdamW):
    lr = opt.lr_at(t)
    n = t.astype(jnp.float32) + 1.0
    mom = jax.tree.map(lambda a, g: opt.b1 * a + (1 - opt.b1) * g, mom, grads)
    vel = jax.tree.map(lambda a, g: opt.b2 * a + (1 - opt.b2) * g * g, vel, grads)
    params = jax.tree.map(
        lambda p, a, b: p - lr * ((a / (1 - opt.b1 ** n))
                                  / (jnp.sqrt(b / (1 - opt.b2 ** n)) + opt.eps)
                                  + opt.weight_decay * p),
        params, mom, vel)
    return params, mom, vel


@jax.jit
def leaf_norms(tree):
    return jax.tree.map(_norm, tree)


@partial(jax.jit, static_argnames=("m",))
def change_norms(key, params, *, m: Dense):
    """Each leaf's norm of its change since the weights were drawn."""
    return jax.tree.map(lambda p, p0: _norm(p - p0), params, init_params(key, m))


def run(key, batches, m: Dense, opt: AdamW, variant: str = "f32"):
    """``len(batches)`` steps from the weights drawn from ``key``.

    Returns the loss of each step, each leaf's norm of the first clipped
    gradient, and each leaf's norm of the change after the last step, as
    host numbers keyed by the leaf's path. Between steps the moments wait in
    host memory, so that the gradients of a row fit beside the weights.
    """
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


def leaf_dict(tree) -> dict:
    """``{"blocks/attn/wq": float, ...}`` from a tree of scalars."""
    out = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        out["/".join(str(getattr(k, "key", k)) for k in path)] = float(np.asarray(leaf))
    return out
