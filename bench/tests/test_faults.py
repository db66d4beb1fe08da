"""The comparison has to fail what it is there to catch.

Each fault drives a whole run through `bench.run.execute` (the look for a
chip skipped) with the program's timed step broken underneath, and `correct`
has to come out false.  The control, the plain reference computed with
float8 matrix products, has to fail the cell's limits as well.  All at the
tiny sizes of `tiny.py`, on the CPU; `bench/calibrate.py` reads the same at
the cells' own sizes on the chip.
"""
import time

import jax
import pytest

from bench import calibrate, compare, run
from bench.drivers import train
from bench.tests import tiny


def _unchanged(step):
    """A step that returns its state unchanged."""
    def broken(state, batch):
        _, metrics = step(state, batch)
        return state, metrics
    return broken


def _half_batch(step):
    """A step that leaves out half of each batch and takes the mean over the
    rest."""
    return lambda state, batch: step(state, calibrate.half(batch))


@pytest.mark.parametrize("fault", [_unchanged, _half_batch])
@pytest.mark.parametrize("config", [tiny.DENSE, tiny.HYBRID],
                         ids=["dense", "hybrid"])
def test_a_broken_step_makes_the_run_incorrect(monkeypatch, fault, config):
    build_step = train.build_step

    def broken_build(*args):
        step = build_step(*args)
        return jax.jit(fault(step.__wrapped__ if hasattr(step, "__wrapped__")
                             else step))

    monkeypatch.setattr(train, "build_step", broken_build)
    result = run.execute(tiny.spec(config), seed=5, seconds=0.2, trace=False,
                         devices=jax.devices()[:1], start=time.perf_counter())
    assert result["correct"] is False
    failing = [k for k, c in result["checks"].items()
               if not c["value"] <= c["limit"]]
    assert failing, result["checks"]


@pytest.mark.parametrize("config", [tiny.DENSE, tiny.HYBRID],
                         ids=["dense", "hybrid"])
def test_the_float8_control_fails_the_limits(config):
    spec = tiny.spec(config)
    prog, key, batches = calibrate.program_readings(spec, 11)
    ref = calibrate.reference_readings(spec, key, batches)
    control = calibrate.reference_readings(spec, key, batches, "fp8")
    sound = compare.gaps(prog, ref)
    assert run.judge(sound, spec["limits"]), sound
    assert not run.judge(compare.gaps(control, ref), spec["limits"])
