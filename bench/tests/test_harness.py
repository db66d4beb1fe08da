"""A CPU rehearsal of `bench/run.py` at tiny sizes, with the look for a chip
skipped: what a run prints and how it decides `correct`."""
import time

import jax
import pytest

from bench import run
from bench.tests import tiny


def execute(config, seconds=0.3, trace=False):
    return run.execute(tiny.spec(config), seed=2**31 + 17, seconds=seconds,
                       trace=trace, devices=jax.devices()[:1],
                       start=time.perf_counter())


def test_the_device_gate_refuses_the_cpu():
    with pytest.raises(SystemExit) as exc:
        run.device_gate(1)
    assert exc.value.code == 2


def test_a_run_prints_the_end_to_end_line_and_its_checks_last():
    result = execute(tiny.DENSE)
    assert list(result) == ["correct", "attempted", "failed", "metrics",
                            "device", "checks"]
    assert result["correct"] is True, result["checks"]
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert set(result["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert result["metrics"]["train_tokens_per_s"]["unit"] == "tokens/s"
    assert result["metrics"]["setup_s"]["value"] > 0
    assert set(result["device"]) == {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    assert result["device"]["count"] == 1
    for name, check in result["checks"].items():
        assert check["limit"] == tiny.LIMITS["tiny-dense"][name]
        assert 0 <= check["value"] <= check["limit"]


def test_the_window_counts_every_step_over_the_whole_window():
    from bench.drivers import train
    calls = []
    loss = jax.numpy.float32(1.0)

    def step(state, batch):
        calls.append(batch)
        time.sleep(0.01)
        return state, {"loss": loss}

    batches = [{"i": i} for i in range(3)]
    _, n, t0, t1, losses = train.window(step, {}, batches, 0.1)
    assert n == len(calls) == len(losses)
    assert 0.1 <= t1 - t0 < 0.1 + 0.05
    assert [b["i"] for b in calls] == [i % 3 for i in range(n)]


def test_a_traced_run_reports_per_layer_metrics_and_a_breakdown(monkeypatch):
    # The CPU has no peaks in the table: this run reads the v5e's.
    v5e = run.device_peaks("TPU v5 lite")
    monkeypatch.setattr(run, "device_peaks", lambda kind: v5e)
    result = execute(tiny.HYBRID, trace=True)
    assert result["correct"] is True, result["checks"]
    assert set(result["metrics"]) == {"idle_share.train", "mfu.train"}
    assert 0 <= result["metrics"]["idle_share.train"]["value"] <= 100
    assert 0 < result["metrics"]["mfu.train"]["value"] < 100
    device = result["device"]
    assert 0 < device["busy_s"] <= device["window_s"]
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
    assert result["breakdown"]["device_ops"]
    assert list(result)[-1] == "checks"
