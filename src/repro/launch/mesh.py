"""Production mesh construction.

A FUNCTION, not a module-level constant, so importing this module never
touches jax device state (device count is locked at first jax init — the
dry-run must set XLA_FLAGS before any jax device query).

Both meshes use `Auto` axis types: the model code leaves layout to the
SPMD partitioner (parameters and batches carry `NamedSharding`s, ops do
not name mesh axes), which `Explicit` axes would reject at the first
gather of a sharded embedding.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes, devices=None):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(model_parallel: int = 1, devices=None):
    """Mesh over `devices` (default: every device of the process)."""
    devices = list(jax.devices() if devices is None else devices)
    n = len(devices)
    if n % model_parallel:
        raise ValueError(f"{n} devices do not split into model_parallel="
                         f"{model_parallel}")
    return _auto_mesh((n // model_parallel, model_parallel),
                      ("data", "model"), devices=devices)
