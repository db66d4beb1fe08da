"""Record `cpu_window.xplane.pb`, the trace `test_trace.py` reduces: three
jitted matrix products on the CPU backend inside the benchmark's window
span, with a host-only pause between the second and the third.

  JAX_PLATFORMS=cpu python3 bench/tests/data/make_cpu_trace.py
"""
import os
import shutil
import tempfile
import time

import jax
import jax.numpy as jnp

HERE = os.path.dirname(os.path.abspath(__file__))


def main():
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    log_dir = tempfile.mkdtemp()
    jax.profiler.start_trace(log_dir)
    with jax.profiler.TraceAnnotation("bench.window"):
        for i in range(3):
            with jax.profiler.TraceAnnotation("bench.step"):
                y = f(x)
            with jax.profiler.TraceAnnotation("bench.wait"):
                y.block_until_ready()
            if i == 1:
                with jax.profiler.TraceAnnotation("bench.pause"):
                    time.sleep(0.02)
    jax.profiler.stop_trace()
    from bench import trace
    shutil.copy(trace.find_trace(log_dir),
                os.path.join(HERE, "cpu_window.xplane.pb"))
    shutil.rmtree(log_dir)


if __name__ == "__main__":
    main()
