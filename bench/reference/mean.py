"""Plain reference of one gossip round: every node ends with the mean.

FedAvg over node-distinct inputs: the node mean of each leaf, taken in
float32; the control takes it in bfloat16, the precision below the float32
the round states. It regenerates the inputs from the seed with the
benchmark's generator, leaf by leaf, and takes nothing the program made.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from trees import node_leaf


@partial(jax.jit, static_argnames=("i", "shape", "n"))
def leaf_gap(key, out, *, i: int, shape: tuple, n: int):
    """Largest absolute difference, over every node and element, between the
    program's output leaf ``out`` (shape ``(n * d0, ...)``) and the node mean."""
    x = node_leaf(key, i, shape, n).reshape(n, *shape)
    return jnp.max(jnp.abs(out.reshape(n, *shape) - jnp.mean(x, axis=0)[None]))


@partial(jax.jit, static_argnames=("i", "shape", "n"))
def control_leaf_gap(key, *, i: int, shape: tuple, n: int):
    """The same gap for the control: the mean taken in bfloat16 put in the
    program's place (it is the same on every node)."""
    x = node_leaf(key, i, shape, n).reshape(n, *shape)
    ctl = jnp.mean(x.astype(jnp.bfloat16), axis=0, dtype=jnp.bfloat16)
    return jnp.max(jnp.abs(ctl.astype(jnp.float32) - jnp.mean(x, axis=0)))


def mean_gap(key, outs: list, shapes: list, n: int) -> float:
    """The widest gap over every leaf of the tree."""
    return float(max(leaf_gap(key, out, i=i, shape=s, n=n)
                     for i, (out, s) in enumerate(zip(outs, shapes))))


def control_gap(key, shapes: list, n: int) -> float:
    return float(max(control_leaf_gap(key, i=i, shape=s, n=n) for i, s in enumerate(shapes)))
