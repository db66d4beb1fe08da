"""Trace reduction (`bench/trace.py`): on a trace recorded on the CPU, and on
a hand-made trace shaped like a TPU's, whose answers are known exactly."""
import os
from types import SimpleNamespace as NS

import pytest

from bench import trace

FIXTURE = os.path.join(os.path.dirname(__file__), "data",
                       "cpu_window.xplane.pb")


def _event(name, start, end, stats=()):
    return NS(name=name, start_ns=start, end_ns=end, stats=list(stats))


def _profile(device_ops, spans):
    return NS(planes=[
        NS(name="/device:TPU:0", lines=[
            NS(name="XLA Modules", lines=[], events=[_event("jit_step",
                                                            0, 10_000)]),
            NS(name="XLA Ops", events=[_event(*e) for e in device_ops])]),
        NS(name="/host:CPU", lines=[
            NS(name="python", events=[_event(*s) for s in spans])])])


def test_union_merges_overlaps_and_touching_intervals():
    assert trace.union([(5, 7), (0, 2), (1, 3), (3, 4), (9, 10)]) == \
        [(0, 4), (5, 7), (9, 10)]


def test_reduce_counts_busy_union_ops_and_labelled_gaps_in_the_window():
    ops = [("fusion.1", 1_000, 3_000), ("fusion.1", 2_000, 4_000),
           ("convolution.2", 6_000, 7_000), ("fusion.1", 11_000, 12_000)]
    spans = [("bench.window", 1_000, 9_000), ("bench.step", 4_000, 5_500),
             ("bench.wait", 5_000, 5_500), ("bench.drain", 7_000, 9_000),
             ("unrelated", 0, 10_000)]
    r = trace.reduce(_profile(ops, spans))
    assert r["window_s"] == pytest.approx(8e-6)
    assert r["busy_s"] == pytest.approx(4e-6)     # [1,4) + [6,7) in us
    assert r["devices"] == 1
    assert r["ops"] == pytest.approx({"fusion.1": 4e-6,
                                      "convolution.2": 1e-6})
    # Gap [4,6) has its middle (5.0) in bench.wait, the innermost span;
    # gap [7,9) lies in bench.drain.
    assert dict(r["breakdown"]["idle_gaps"]) == pytest.approx(
        {"bench.wait": 2e-6, "bench.drain": 2e-6})
    assert r["breakdown"]["device_ops"][0][0] == "fusion.1"


def test_reduce_needs_exactly_one_window():
    with pytest.raises(RuntimeError, match="bench.window"):
        trace.reduce(_profile([("fusion.1", 0, 1)], []))


def test_reduce_of_a_trace_recorded_on_the_cpu():
    r = trace.reduce_file(FIXTURE)
    assert r["devices"] == 1
    assert 0 < r["busy_s"] < r["window_s"]
    assert sum(r["ops"].values()) >= r["busy_s"] * (1 - 1e-9)
    assert any(name.startswith("dot") for name in r["ops"])
    gaps = dict(r["breakdown"]["idle_gaps"])
    # The host slept 20 ms inside its `bench.pause` span with the device
    # idle: the longest gap, and most of the window's idle time.
    assert r["breakdown"]["idle_gaps"][0][0] == "bench.pause"
    assert gaps["bench.pause"] >= 0.02 * 0.95
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - r["busy_s"])
    for key in ("device_ops", "idle_gaps"):
        assert len(r["breakdown"][key]) <= trace.TOP
