"""Production mesh construction.

A FUNCTION, not a module-level constant: importing this module must never
touch jax device state (smoke tests see 1 device; only dryrun.py forces 512).
"""
from __future__ import annotations

import numpy as np


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips).

    Axes: ("data","model") single-pod, ("pod","data","model") multi-pod.
    Uses a prefix of jax.devices() so a 512-placeholder process can build
    both meshes.
    """
    import jax

    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for the production mesh, have {len(devices)} — "
            "set XLA_FLAGS=--xla_force_host_platform_device_count=512 before "
            "any jax import (dryrun.py does this)."
        )
    return jax.sharding.Mesh(
        np.asarray(devices[:n]).reshape(shape), axes
    )


def make_local_mesh(shape=(1, 1), axes=("data", "model")):
    """Mesh over the first ``prod(shape)`` devices of the default backend.

    Raises when fewer devices exist than the mesh asks for, so a run never
    silently shrinks its mesh. Axis types are ``Auto`` explicitly:
    ``jax.make_mesh`` defaults to ``Explicit`` axes, under which the
    vocab-sharded embedding gather (``models/layers.py``) is rejected.
    """
    import jax
    from jax.sharding import AxisType

    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {tuple(shape)} needs {n} devices, found {len(devices)} "
            f"({devices[0].platform})")
    return jax.sharding.Mesh(np.asarray(devices[:n]).reshape(shape), tuple(axes),
                             axis_types=(AxisType.Auto,) * len(axes))
