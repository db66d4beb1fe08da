"""The per-layer metrics of device time by model scope (`bench/scopes.py`,
`bench/metrics/scope_*.py`), on a traced tiny run on the CPU."""
import time

import jax
import pytest

from bench import run, scopes, trace
from bench.tests import tiny

SCOPE_METRICS = [m for m in run.load_json(run.ROOT, "BENCHMARK.json")
                 ["per_layer"] if m["name"].startswith("scope_")]


def _thread_busy_s(profile, window):
    """Busy seconds of the CPU's op events, summed over the threads that
    ran them.  The CPU runs independent ops at once on several threads, so
    the union over all of them (`busy_s`) can be shorter than the ops'
    leaf time; one thread runs one op at a time, as a TPU does."""
    busy = 0
    for plane in profile.planes:
        for line in plane.lines:
            spans = [trace._clip((int(e.start_ns), int(e.end_ns)), window)
                     for e in line.events
                     if any(k == "hlo_op" for k, _ in e.stats)]
            busy += sum(hi - lo for lo, hi in trace.union(
                [s for s in spans if s]))
    return busy * 1e-9


def test_a_traced_run_reports_device_time_by_scope(monkeypatch):
    v5e = run.device_peaks("TPU v5 lite")
    monkeypatch.setattr(run, "device_peaks", lambda kind: v5e)
    profiles, contexts = [], []
    reduce = trace.reduce
    monkeypatch.setattr(trace, "reduce",
                        lambda p: profiles.append(p) or reduce(p))
    reader = run.metric_reader

    def recording(name):
        read = reader(name)
        return lambda ctx: contexts.append(ctx) or read(ctx)
    monkeypatch.setattr(run, "metric_reader", recording)
    spec = tiny.spec(tiny.HYBRID)
    spec["per_layer"] = spec["per_layer"] + [
        {k: v for k, v in m.items() if k != "workloads"}
        for m in SCOPE_METRICS]
    result = run.execute(spec, seed=2**33 + 5, seconds=0.3, trace=True,
                         devices=jax.devices()[:1],
                         start=time.perf_counter())

    assert result["correct"] is True, result["checks"]
    metrics = result["metrics"]
    assert {m["name"] for m in SCOPE_METRICS} <= set(metrics)
    for m in SCOPE_METRICS:
        assert metrics[m["name"]]["value"] > 0
        assert metrics[m["name"]]["unit"] == m["unit"]

    ctx = contexts[0]
    found = ctx[scopes._KEY]
    # Every event named an instruction of the re-lowered step, and the
    # while loops' events, which enclose their bodies', were left out.
    assert found.unmatched == 0
    assert found.enclosing > 0
    assert sum(found.by_scope.values()) + found.unattributed == \
        pytest.approx(found.leaf)
    us = {m["name"].split(".")[1]: metrics[m["name"]]["value"]
          for m in SCOPE_METRICS if m["name"].startswith("scope_us.")}
    seconds = dict(found.by_scope, none=found.unattributed)
    for scope, value in us.items():
        assert value == pytest.approx(seconds[scope] * 1e6 / ctx["tokens"])
    window = [(int(e.start_ns), int(e.end_ns))
              for p in profiles[:1] for plane in p.planes
              for line in plane.lines for e in line.events
              if e.name == trace.WINDOW_SPAN][0]
    busy = _thread_busy_s(profiles[0], window)
    assert found.leaf <= busy * 1.02
    assert sum(us.values()) <= busy * 1e6 / ctx["tokens"] * 1.02


def test_a_program_that_records_no_train_step_reports_no_scope_metric(
        monkeypatch):
    from repro.runtime import steps
    monkeypatch.delattr(steps, "last_traced_train_step")
    ctx = {"trace": {"devices": 1, "busy_s": 1.0, "window_s": 1.0,
                     "ops": {"fusion.1": 1.0}}, "tokens": 4096}
    for m in SCOPE_METRICS:
        assert run.metric_reader(m["name"])(ctx) is None


def test_a_cached_executable_built_without_the_scopes_is_compiled_again(
        tmp_path, monkeypatch):
    import contextlib
    import re

    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache

    from bench.drivers import train
    from repro.core.cct import scope_of
    from repro.models.scopes import MODEL_SCOPES
    from repro.runtime import steps
    arch = train.arch_config(tiny.DENSE)
    traffic = tiny.TRAFFIC["tiny-dense"]
    state = jax.eval_shape(train.build_init(arch), jax.random.PRNGKey(0))
    rows = jax.ShapeDtypeStruct((traffic["batch"], traffic["seq_len"]),
                                jnp.int32)
    batch = {"tokens": rows, "labels": rows}
    saved = {k: getattr(jax.config, k) for k in (
        "jax_enable_compilation_cache", "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs")}
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compilation_cache.reset_cache()
    try:
        with monkeypatch.context() as m:
            m.setattr(jax, "named_scope",
                      lambda name: contextlib.nullcontext())
            train.build_step(arch, traffic).lower(state, batch).compile()
        jax.clear_caches()
        # The persistent cache's key leaves the scopes out: the scoped
        # build is served the executable of the build without them.
        ran = train.build_step(arch, traffic).lower(state, batch).compile()
        assert "optimizer/" not in ran.as_text()
        text = scopes.compiled_text(*steps.last_traced_train_step())
        assert {"attn", "mlp", "optimizer"} <= {
            scope_of(path, MODEL_SCOPES)
            for path in re.findall(r'op_name="([^"]*)"', text)}
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
