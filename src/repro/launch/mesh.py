"""Production meshes.

Single pod: 16 x 16 = 256 chips, axes (data, model).
Multi-pod:  2 x 16 x 16 = 512 chips, axes (pod, data, model) — the pod
axis crosses DCN; data/model stay on intra-pod ICI.

Defined as functions so importing this module never touches jax device
state (the dry-run sets XLA_FLAGS before any jax import).
"""
from __future__ import annotations

import numpy as np


def make_production_mesh(*, multi_pod: bool = False):
    import jax
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devs = jax.devices()
    if len(devs) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, found {len(devs)} — run under "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n} "
            f"(see launch/dryrun.py)")
    return jax.make_mesh(shape, axes, devices=devs[:n],
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_mesh(shape, axes):
    """Arbitrary mesh for tests/examples (e.g. (1, 1) on one CPU)."""
    import jax
    n = int(np.prod(shape))
    return jax.make_mesh(tuple(shape), tuple(axes),
                         devices=jax.devices()[:n],
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))
