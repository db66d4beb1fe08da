"""A tiny GLM-4 cell for the harness's own tests, and its 2x2 mesh run.

The equations of `bench/configs/glm4-9b.json` (rotary on half of each head
in adjacent pairs, QKV bias, an untied head, the published eps) at widths
of 64, checked against `bench/reference/glm4.py`.  Run as a module over
four host devices it drives the cell over a 2x2 mesh through `run.execute`,
as `bench/tests/mesh_run.py` drives the tiny dense cell:

  XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \
      python -m bench.tests.glm4_tiny

and prints one JSON line: the sound run's result, the gaps between the
mesh's and one device's readings, the result of a run with the half-batch
fault and the float8 control's gaps.
"""
from __future__ import annotations

import json
import time

import jax

from bench import calibrate, compare, run
from bench.drivers import train
from bench.tests import tiny

CONFIG = {"name": "tiny-glm4", "reference": "glm4", "family": "dense",
          "n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
          "head_dim": 16, "d_ff": 128, "vocab_size": 256, "attention": "full",
          "window": 4096, "qkv_bias": True, "tie_embeddings": False,
          "rope_theta": 1e4, "partial_rotary_factor": 0.5,
          "rope_interleave": True, "norm_eps": 1.5625e-7,
          "mlp_kind": "swiglu", "dtype": "bfloat16"}
TRAFFIC = {"kind": "train", "batch": 4, "seq_len": 64}
MESH = {"data": 2, "model": 2}
# About three times the largest gap of sound runs on the CPU over four
# seeds, one device and the 2x2 mesh alike (1.05e-5, 1.17e-4, 3.6e-3,
# 1.33e-2); the float8 control reads 6.9e-5 or more in loss, 8.0e-4 or
# more in gradient norm.
LIMITS = {"loss_gap": 3.2e-5, "grad_norm_gap": 3.5e-4,
          "grad_leaf_gap": 1.1e-2, "update_leaf_gap": 4e-2}
SEED = 2**31 + 29


def spec(mesh=None) -> dict:
    """What `bench.run.cell_spec` returns for the tiny cell, laid over
    `mesh` where one is given."""
    traffic = dict(TRAFFIC, mesh=mesh) if mesh else TRAFFIC
    return {"cell": {"name": "tiny-glm4.train", "config": "tiny-glm4",
                     "traffic": "tiny", "chips": 4 if mesh else 1},
            "config": CONFIG, "traffic": traffic, "limits": LIMITS,
            "end_to_end": tiny.END_TO_END, "per_layer": tiny.PER_LAYER}


def execute(cell: dict, devices, seed: int = SEED) -> dict:
    return run.execute(cell, seed=seed, seconds=0.2, trace=False,
                       devices=devices, start=time.perf_counter())


def main() -> dict:
    devices = jax.devices()
    cell = spec(MESH)
    seen = {}
    first_steps, reference_readings = train.first_steps, \
        train.reference_readings

    def record_state(*args, **kwargs):
        state, readings = first_steps(*args, **kwargs)
        seen["readings"] = readings
        return state, readings

    def record_reference(cfg, traffic, param_key, batches, *args, **kwargs):
        seen["reference_args"] = (param_key, batches)
        seen["reference"] = reference_readings(cfg, traffic, param_key,
                                               batches, *args, **kwargs)
        return seen["reference"]

    train.first_steps = record_state
    train.reference_readings = record_reference
    try:
        sound = execute(cell, devices)
    finally:
        train.first_steps, train.reference_readings = first_steps, \
            reference_readings
    one_device = calibrate.program_readings(spec(), SEED,
                                            devices=devices[:1])[0]
    param_key, batches = seen["reference_args"]
    control = calibrate.reference_readings(cell, param_key, batches, "fp8",
                                           devices)

    build_step = train.build_step

    def half_batch_step(arch, traffic, lay):
        step = build_step(arch, traffic, lay)
        return jax.jit(lambda state, batch: step(
            state, calibrate.half(batch, MESH["data"])))

    train.build_step = half_batch_step
    try:
        half_batch = execute(cell, devices)
    finally:
        train.build_step = build_step
    return {"sound": sound,
            "mesh_vs_one_device": compare.gaps(seen["readings"], one_device),
            "half_batch": half_batch,
            "control": compare.gaps(control, seen["reference"])}


if __name__ == "__main__":
    print(json.dumps(main()), flush=True)
