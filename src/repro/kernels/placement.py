"""Where a Pallas kernel runs: the one rule every kernel path shares.

A kernel entry point runs its Mosaic kernel compiled in a process whose
default backend is a TPU and in interpret mode anywhere else (`on_tpu`).
The model takes a kernel in place of its XLA path only where `on_one_tpu`
holds for the kernel's operand: on a TPU, for a step on at most one
device, since XLA cannot partition a Mosaic kernel across a mesh.  Each
path adds only its own test of the shapes (`models/attention.py`,
`models/ssm.py`).
"""
from __future__ import annotations

import jax


def on_tpu() -> bool:
    """Whether this process's default backend is a TPU."""
    return jax.default_backend() == "tpu"


def step_mesh(x):
    """The mesh the step tracing `x` runs over, from `x`'s type: the mesh
    of its sharding, which jit's shardings give it; empty off a mesh."""
    return jax.typeof(x).sharding.mesh


def on_one_tpu(x) -> bool:
    """Whether a kernel may take `x`: on a TPU, for a step on at most one
    device, by the larger of `step_mesh(x)` and the abstract mesh in
    context."""
    devices = max(step_mesh(x).size, jax.sharding.get_abstract_mesh().size)
    return on_tpu() and devices <= 1
