"""The one generator: normal float32 arrays from ``--seed``, on the device.

A cell asks for a tree of ``(shape, scale)`` leaves; every leaf is drawn
from its own key, folded from the seed and a stream number, so weights
(stream 0) and each batch of the pool (streams 1, 2, ...) never share
draws, and the same seed gives the same arrays.  The whole tree comes from
one jitted call.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

WEIGHTS = 0


def seed_key(seed: int, stream: int) -> jax.Array:
    """A key for ``stream`` of ``seed``; seeds wider than 32 bits keep
    their high bits."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    key = jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)
    return jax.random.fold_in(key, stream)


@partial(jax.jit, static_argnums=(1, 2))
def _draw(key, treedef, leaves):
    keys = jax.random.split(key, len(leaves))
    return jax.tree.unflatten(treedef, [
        scale * jax.random.normal(k, shape, jnp.float32)
        for k, (shape, scale) in zip(keys, leaves)])


def is_spec(t) -> bool:
    """A ``(shape, scale)`` leaf."""
    return (isinstance(t, tuple) and len(t) == 2 and isinstance(t[0], tuple)
            and isinstance(t[1], (int, float)))


def draw(seed: int, stream: int, tree):
    """Arrays shaped like ``tree``, whose leaves are ``(shape, scale)``
    tuples: N(0, scale**2) float32 entries."""
    leaves, treedef = jax.tree.flatten(tree, is_leaf=is_spec)
    leaves = tuple((tuple(s), float(c)) for s, c in leaves)
    return _draw(seed_key(seed, stream), treedef, leaves)


def pool(seed: int, size: int, tree) -> list:
    """``size`` batches shaped like ``tree``: streams 1 .. size."""
    return [draw(seed, 1 + i, tree) for i in range(size)]
