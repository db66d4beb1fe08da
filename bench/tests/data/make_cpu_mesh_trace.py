"""Record `cpu_mesh_window.xplane.pb`, the trace `test_collectives.py`
reduces: three steps of a jitted program sharded over four host devices of
the CPU backend, inside the benchmark's window span.  The SPMD partitioner
puts its collectives in and names them after their opcodes, as in a
sharded train step: on the CPU 1 all-reduce, 2 all-gathers, 1 all-to-all
and 4 collective-permutes.

  XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \
      python3 bench/tests/data/make_cpu_mesh_trace.py
"""
import os
import shutil
import sys
import tempfile

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(HERE))))
AXIS = "devices"


def program(mesh):
    rows, cols, whole = (NamedSharding(mesh, P(AXIS, None)),
                         NamedSharding(mesh, P(None, AXIS)),
                         NamedSharding(mesh, P()))

    def exchange(x):
        y = jax.lax.with_sharding_constraint(x.T @ x, rows)
        y = jnp.roll(jax.lax.with_sharding_constraint(y, cols), 1, axis=1)
        y = y + jax.lax.with_sharding_constraint(x[:64] @ x[:64].T, cols)
        return jax.lax.with_sharding_constraint(y, whole) * jnp.sum(y)
    return jax.jit(exchange, in_shardings=rows)


def main():
    devices = jax.devices()
    assert len(devices) == 4, "set XLA_FLAGS as the docstring says"
    f = program(Mesh(devices, (AXIS,)))
    x = jnp.ones((4 * 64, 64))
    f(x).block_until_ready()
    log_dir = tempfile.mkdtemp()
    jax.profiler.start_trace(log_dir)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.step"):
                y = f(x)
            with jax.profiler.TraceAnnotation("bench.wait"):
                y.block_until_ready()
    jax.profiler.stop_trace()
    from bench import trace
    shutil.copy(trace.find_trace(log_dir),
                os.path.join(HERE, "cpu_mesh_window.xplane.pb"))
    shutil.rmtree(log_dir)


if __name__ == "__main__":
    main()
