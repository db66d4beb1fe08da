"""A tiny dense cell over a 2x2 mesh of host devices, through `run.execute`.

  XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \
      python -m bench.tests.mesh_run

`bench/tests/test_mesh.py` starts this in a process of its own, since the
test run's own processes keep one host device.  It prints one JSON line:
the sound run's result, how the program's state was laid out, the gaps
between the mesh's and one device's readings at one seed, and the result
of a run with the half-batch fault and the float8 control's gaps.
"""
from __future__ import annotations

import json
import time

import jax

from bench import calibrate, compare, run
from bench.drivers import train
from bench.tests import tiny

SEED = 2**31 + 23
MESH = {"data": 2, "model": 2}


def mesh_spec() -> dict:
    spec = tiny.spec(tiny.DENSE)
    spec["traffic"] = dict(spec["traffic"], mesh=MESH)
    spec["cell"] = dict(spec["cell"], chips=4)
    return spec


def execute(spec, devices) -> dict:
    return run.execute(spec, seed=SEED, seconds=0.2, trace=False,
                       devices=devices, start=time.perf_counter())


def main() -> dict:
    devices = jax.devices()
    spec = mesh_spec()
    seen = {}
    first_steps, reference_readings = train.first_steps, \
        train.reference_readings

    def record_state(step, state, *args, **kwargs):
        leaves = jax.tree.leaves(state)
        seen["state_devices"] = len(set().union(
            *(x.sharding.device_set for x in leaves)))
        seen["leaves_split"] = sum(len(x.sharding.device_set) > 1
                                   and not x.sharding.is_fully_replicated
                                   for x in leaves)
        state, readings = first_steps(step, state, *args, **kwargs)
        seen["readings"] = readings
        return state, readings

    def record_reference(cfg, traffic, param_key, batches, *args, **kwargs):
        seen["reference_args"] = (param_key, batches)
        ref = reference_readings(cfg, traffic, param_key, batches, *args,
                                 **kwargs)
        seen["reference"] = ref
        return ref

    train.first_steps = record_state
    train.reference_readings = record_reference
    try:
        sound = execute(spec, devices)
    finally:
        train.first_steps, train.reference_readings = first_steps, \
            reference_readings
    one_device = calibrate.program_readings(tiny.spec(tiny.DENSE), SEED,
                                            devices=devices[:1])[0]
    param_key, batches = seen["reference_args"]
    control = calibrate.reference_readings(spec, param_key, batches, "fp8",
                                           devices)

    build_step = train.build_step

    def half_batch_step(arch, traffic, lay):
        step = build_step(arch, traffic, lay)
        return jax.jit(lambda state, batch: step(
            state, calibrate.half(batch, MESH["data"])))

    train.build_step = half_batch_step
    try:
        half_batch = execute(spec, devices)
    finally:
        train.build_step = build_step
    return {"sound": sound,
            "state_devices": seen["state_devices"],
            "leaves_split": seen["leaves_split"],
            "mesh_vs_one_device": compare.gaps(seen["readings"], one_device),
            "half_batch": half_batch,
            "control": compare.gaps(control, seen["reference"])}


if __name__ == "__main__":
    print(json.dumps(main()), flush=True)
