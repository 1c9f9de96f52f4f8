"""Production mesh definitions (brief: 16x16 single pod, 2x16x16 multi-pod).

A function, not a module-level constant, so importing never touches jax
device state.
"""
from __future__ import annotations

import jax


def make_auto_mesh(axis_shapes, axis_names):
    """jax.make_mesh with every axis of Auto type (sharding left to XLA)."""
    axis_types = (jax.sharding.AxisType.Auto,) * len(axis_names)
    return jax.make_mesh(axis_shapes, axis_names, axis_types=axis_types)



def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_auto_mesh(shape, axes)


def make_smoke_mesh(n_devices: int = 1):
    """Single-host mesh for tests: (1, n) data x model."""
    return make_auto_mesh((1, n_devices), ("data", "model"))


def make_data_parallel_mesh(n_devices: int | None = None):
    """(n, 1) data x model mesh over all local devices: batches split over
    'data', params (``clax_param_rule``) land on the size-1 'model' axis —
    i.e. replicated across the data ranks. The shape every single-host
    ``--data-parallel`` training run uses."""
    n = n_devices or jax.local_device_count()
    return make_auto_mesh((n, 1), ("data", "model"))
