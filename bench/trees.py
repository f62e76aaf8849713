"""Node-distinct parameter trees made on the device from a seed.

The gossip cells' input: for a tree of leaf shapes, each DFL node holds its
own float32 copy with its own values. Leaf ``i`` of shape ``(d0, ...)`` is one
array of shape ``(n * d0, ...)`` whose rows ``[u * d0, (u + 1) * d0)`` are
node ``u``'s, so that sharding the leading axis over the nodes gives each node
its copy. The same key gives the same values on any device layout.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def seed_key(seed: int) -> jax.Array:
    """A key from a seed of any size (``PRNGKey`` keeps only the low 32 bits)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF), seed >> 32)


def node_leaf(key, i: int, shape: tuple, n: int) -> jax.Array:
    """Leaf ``i`` of every node, stacked along the leading axis."""
    return jax.random.normal(jax.random.fold_in(key, i), (n * shape[0], *shape[1:]),
                             jnp.float32)


def node_tree(key, shapes: list, n: int) -> list:
    return [node_leaf(key, i, tuple(s), n) for i, s in enumerate(shapes)]
