"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (module import never touches jax
device state).  Single pod: (data=16, model=16) = 256 chips (one v5e pod).
Multi-pod: (pod=2, data=16, model=16) = 512 chips; the ``pod`` axis carries
pure data parallelism so only gradient all-reduces cross the slow inter-pod
links.
"""

from __future__ import annotations

import jax


def auto_mesh(shape, axes):
    """A mesh whose axes are ``Auto``: shardings propagate through the
    compiler as before, instead of jax's default ``Explicit`` axes, under
    which gathers from a sharded table (``jnp.take`` on an embedding)
    demand an explicit ``out_sharding``."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_host_mesh():
    """Whatever this host has (CPU smoke tests: 1 device)."""
    n = len(jax.devices())
    return auto_mesh((n, 1), ("data", "model"))
