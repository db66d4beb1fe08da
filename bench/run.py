#!/usr/bin/env python3
"""The benchmark: one run of one cell on the chips of this machine.

  python3 bench/run.py --workload qwen2-0.5b.train --seed 7 --seconds 10 \
      --trace 0

Everything is found by name.  The cell is an entry of `workloads` in
`BENCHMARK.json`; it names a configuration, `bench/configs/<config>.json`,
and a traffic mix, `bench/traffic/<traffic>.json`, whose `kind` names the
driver `bench/drivers/<kind>.py`; a traffic with a `mesh` (`{"data": d,
"model": m}`) lays the program over the cell's d x m chips.  The limits of
its comparison are in `bench/limits/<cell>.json`, each per-layer metric is
read by `bench/metrics/<metric>.py`, and peaks are looked up in
`bench/peaks.json` by the device's kind.

With `--trace 0` the run reports the cell's end-to-end metrics; with
`--trace 1` it records a profiler trace of the window and reports its
per-layer metrics.  The last line of standard output is the result as one
JSON object; the numbers compared, each beside its limit, are also the
last lines of standard error.  Without a TPU, or with fewer chips than the
cell asks for, the run exits with code 2 and prints no result.
"""
from __future__ import annotations

import time

PROCESS_START = time.perf_counter()

import argparse
import contextlib
import importlib
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def cell_spec(workload: str) -> dict:
    """The cell, its configuration, traffic, limits and metric entries."""
    spec = load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; known: "
                         f"{sorted(cells)}")
    cell = cells[workload]

    def applies(metric):
        return workload in metric.get("workloads", [workload])

    traffic = load_json(BENCH, "traffic", cell["traffic"] + ".json")
    mesh = traffic.get("mesh")
    if mesh and mesh["data"] * mesh["model"] != cell["chips"]:
        raise ValueError(f"{workload}: traffic {cell['traffic']!r} lays the "
                         f"program over a {mesh['data']} x {mesh['model']} "
                         f"mesh, the cell asks for {cell['chips']} chips")
    return {"cell": cell,
            "config": load_json(BENCH, "configs", cell["config"] + ".json"),
            "traffic": traffic,
            "limits": load_json(BENCH, "limits", workload + ".json"),
            "end_to_end": [m for m in spec["end_to_end"] if applies(m)],
            "per_layer": [m for m in spec["per_layer"] if applies(m)]}


def device_gate(chips: int):
    """The first `chips` TPU devices, or exit 2 with no result."""
    import jax
    devices = jax.devices()
    d = devices[0]
    print(f"device: platform={d.platform} kind={d.device_kind} "
          f"count={len(devices)}", file=sys.stderr, flush=True)
    if d.platform != "tpu":
        print(f"bench: needs a TPU; JAX found {d.platform!r}",
              file=sys.stderr)
        sys.exit(2)
    if len(devices) < chips:
        print(f"bench: the cell needs {chips} chips, found {len(devices)}",
              file=sys.stderr)
        sys.exit(2)
    return devices[:chips]


def device_peaks(kind: str) -> dict:
    table = load_json(BENCH, "peaks.json")["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in bench/peaks.json")
    return table[kind]


def metric_reader(name: str):
    path = os.path.join(BENCH, "metrics", name + ".py")
    module_name = "bench_metric_" + name.replace(".", "_").replace("-", "_")
    if module_name not in sys.modules:
        spec = importlib.util.spec_from_file_location(module_name, path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        sys.modules[module_name] = module
    return sys.modules[module_name].read


def enable_compile_cache():
    """The program's compile cache (a fixed directory in the checkout unless
    JAX_COMPILATION_CACHE_DIR is set), holding every program set-up
    compiles, small ones included, so that a second run compiles nothing."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache as enable
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return enable()


def judge(checks: dict, limits: dict) -> bool:
    """True when every number that has a limit is finite and within it."""
    missing = set(limits) - set(checks)
    if missing:
        raise ValueError(f"limits for {sorted(missing)}, which no run reads")
    return all(math.isfinite(checks[k]) and checks[k] <= v
               for k, v in limits.items())


def execute(spec: dict, seed: int, seconds: float, trace: bool,
            devices, start: float = None) -> dict:
    """Drive one run of the cell on `devices`; the result line as a dict."""
    import jax
    start = PROCESS_START if start is None else start
    driver = importlib.import_module(f"bench.drivers.{spec['traffic']['kind']}")
    log_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None

    @contextlib.contextmanager
    def traced():
        if not trace:
            yield
            return
        jax.profiler.start_trace(log_dir)
        try:
            yield
        finally:
            jax.profiler.stop_trace()

    def peak_memory():
        stats = [d.memory_stats() or {} for d in devices]
        return max(s.get("peak_bytes_in_use", 0) for s in stats)

    try:
        out = driver.run(spec["config"], spec["traffic"], seed, seconds,
                         traced, peak_memory, devices)
        reduced = None
        if trace:
            from bench import trace as trace_mod
            reduced = trace_mod.reduce_file(trace_mod.find_trace(log_dir))
    finally:
        if log_dir:
            shutil.rmtree(log_dir, ignore_errors=True)

    d = devices[0]
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": len(devices),
              "memory_peak_bytes": out["memory_peak_bytes"]}
    setup_s = out["window_start"] - start
    if trace:
        from bench import flops
        context = {"trace": reduced, "window_s": out["window_s"],
                   "tokens": out["tokens"], "config": spec["config"],
                   "peaks": device_peaks(d.device_kind), "chips": len(devices),
                   "flops": flops}
        metrics = {}
        for m in spec["per_layer"]:
            value = metric_reader(m["name"])(context)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
    else:
        values = dict(out["end_to_end"], setup_s=setup_s)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in spec["end_to_end"]}
    checks = out["checks"]
    correct = judge(checks, spec["limits"]) and out["failed"] == 0
    result = {"correct": correct, "attempted": out["attempted"],
              "failed": out["failed"], "metrics": metrics, "device": device}
    if trace:
        result["breakdown"] = reduced["breakdown"]
    result["checks"] = {k: {"value": checks[k], "limit": v}
                        for k, v in spec["limits"].items()}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    # The checkout's root (for `bench.*`) and the program's sources, in place
    # of this directory, whose module names would shadow the library's.
    sys.path[:1] = [ROOT, os.path.join(ROOT, "src")]
    spec = cell_spec(args.workload)
    devices = device_gate(spec["cell"]["chips"])
    enable_compile_cache()
    result = execute(spec, args.seed, args.seconds, bool(args.trace), devices)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
