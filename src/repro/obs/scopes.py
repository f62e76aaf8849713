"""Names of the layers of the jitted DFL step, each a ``jax.named_scope``.

A named scope changes only the ``op_name`` metadata of the HLO the step
compiles to: no instruction, fusion or number moves. The compiled
executable's ``as_text()`` then carries each op's path of scopes, e.g.
``jit(fn)/dfl.grads/transpose(jvp())/while/body/closed_call/checkpoint/attention/...``
for the backward pass of a scanned layer, and a device trace can be split
by layer through it (``bench/scopes.py`` reads these names as literals, so
a rename here fails a test there instead of moving a metric silently).

Model layers (inside the shared functions, so every family carries them):

* :data:`EMBED` — the token embedding lookup;
* :data:`ATTENTION` — q/k/v/o projections, RoPE, KV expansion and the
  query-block loop (train, prefill and decode);
* :data:`MLP` — the SwiGLU MLP;
* :data:`MOE` — the expert block (router, dispatch, experts, combine);
* :data:`SSM` — the Mamba block;
* :data:`LM_HEAD_LOSS` — final norm, tied readout and cross-entropy.

Trainer step (``dfl/trainer.py``): :data:`GRADS` (``value_and_grad`` and the
microbatch scan), :data:`CLIP`, :data:`UPDATE` (the optimizer) and
:data:`GOSSIP` (the exchange), with :func:`gossip_step` around each permute
step of a gossip body (``dfl/collectives.py``).

Pallas kernels are named through ``pallas_call(name=...)``:
:data:`CODEC_QUANTIZE`, :data:`CODEC_DEQUANTIZE`, :data:`CODEC_TOPK`.
"""
from __future__ import annotations

import functools
from typing import Callable, TypeVar

import jax

EMBED = "embed"
ATTENTION = "attention"
MLP = "mlp"
MOE = "moe"
SSM = "ssm"
LM_HEAD_LOSS = "lm_head_loss"

GRADS = "dfl.grads"
CLIP = "dfl.clip"
UPDATE = "dfl.update"
GOSSIP = "dfl.gossip"

CODEC_QUANTIZE = "codec_quantize"
CODEC_DEQUANTIZE = "codec_dequantize"
CODEC_TOPK = "codec_topk"

F = TypeVar("F", bound=Callable)


def gossip_step(i: int) -> str:
    """The scope of the ``i``-th permute step of a gossip round."""
    return f"gossip.step{i}"


def scoped(name: str) -> Callable[[F], F]:
    """Decorator: trace every call of the function under ``named_scope(name)``."""

    def wrap(fn: F) -> F:
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)

        return inner  # type: ignore[return-value]

    return wrap
