#!/usr/bin/env python3
"""Readings that the limits of a training cell are set from.

  python3 bench/calibrate.py --workload qwen2-0.5b.train \
      --seeds 11 12 13 --write

For each seed, in one process and at the cell's own sizes, it takes the
comparison's numbers (`bench/compare.py`) of

  program    the program's first steps against the plain reference: the
             lower readings;
  control    the reference computed with float8 matrix products, put in the
             program's place (on the first CONTROL_SEEDS seeds);
  half_batch the program trained on half of each batch, the mean taken over
             the rest: a fault the comparison has to catch (on the first
             CONTROL_SEEDS seeds).  Half of the rows, or of the positions
             where the rows left would not divide over the mesh's `data`.

A step that returns its state unchanged reads 1 on both leaf numbers by
their definition and needs no run.  One JSON line per reading goes to
standard output, and a summary of each kind as the last line.  With
`--write` the summary goes to `bench/calibration/<cell>.json` and the
limits that `limits_from` sets from it to `bench/limits/<cell>.json`.  The
benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CONTROL_SEEDS = 3  # seeds that the control and the fault are read on


def half(batch: dict, data: int = 1) -> dict:
    """Half of the batch's rows, while the rows left still divide over a
    mesh's `data` axis of that size; otherwise half of its positions."""
    b, s = batch["tokens"].shape
    if b // 2 and (b // 2) % data == 0:
        return {k: v[: b // 2] for k, v in batch.items()}
    return {k: v[:, : s // 2] for k, v in batch.items()}


def half_placed(spec):
    """`half` for the cell's batches, each half laid out as its batch was."""
    import jax
    data = spec["traffic"].get("mesh", {}).get("data", 1)
    return lambda batch: {k: jax.device_put(v, batch[k].sharding)
                          for k, v in half(batch, data).items()}


def program_readings(spec, seed: int, fault=None, devices=None):
    """The program's readings, on `devices` where the traffic has a mesh."""
    from bench.drivers import train
    _, state, batches, readings, param_key, _ = train.checked_start(
        spec["config"], spec["traffic"], seed, fault, devices)
    del state
    gc.collect()
    return readings, param_key, batches


def reference_readings(spec, param_key, batches, precision="f32",
                       devices=None):
    from bench.drivers import train
    out = train.reference_readings(spec["config"], spec["traffic"], param_key,
                                   batches, precision, devices)
    gc.collect()
    return out


def calibrate(spec, seeds, emit=print, devices=None):
    from bench import compare
    kinds = {"program": [], "control": [], "half_batch": []}
    for i, seed in enumerate(seeds):
        prog, param_key, batches = program_readings(spec, seed,
                                                    devices=devices)
        ref = reference_readings(spec, param_key, batches, devices=devices)
        found = {"program": prog}
        if i < CONTROL_SEEDS:
            found["control"] = reference_readings(spec, param_key, batches,
                                                  "fp8", devices)
            found["half_batch"] = program_readings(
                spec, seed, half_placed(spec), devices)[0]
        for kind, readings in found.items():
            gaps = compare.gaps(readings, ref)
            kinds[kind].append(gaps)
            emit(json.dumps({"seed": seed, "kind": kind, **gaps,
                             "losses": readings["losses"],
                             "ref_losses": ref["losses"],
                             "grad_norms": readings["grad_norms"],
                             "ref_grad_norms": ref["grad_norms"]}))
    summary = {}
    for kind, rows in kinds.items():
        if rows:
            pick = max if kind == "program" else min
            summary[kind] = {k: pick(r[k] for r in rows) for k in rows[0]}
    return summary


LEAF_NUMBERS = ("grad_leaf_gap", "update_leaf_gap")
UNCHANGED_STATE = 1.0  # both leaf numbers of a step that returns its state


def upper_reading(summary: dict, number: str):
    """The least reading that fails: the control's where it is 3x the lower
    reading or more, the half-batch fault's where 10x or more, a state left
    unchanged (leaf numbers) where 3x or more.  None where none is."""
    lower = summary["program"][number]
    readings = [(summary.get("control", {}).get(number), 3),
                (summary.get("half_batch", {}).get(number), 10)]
    if number in LEAF_NUMBERS:
        readings.append((UNCHANGED_STATE, 3))
    found = [r for r, factor in readings
             if r is not None and r >= factor * lower]
    return min(found) if found else None


def limits_from(summary: dict) -> dict:
    """Each number's limit, lower * (upper / lower) ** 0.6 to two digits:
    above the lower reading with more room than below the upper.  A number
    with no upper reading is not compared."""
    limits = {}
    for number, lower in summary["program"].items():
        upper = upper_reading(summary, number)
        if upper is None:
            continue
        raw = lower * (upper / lower) ** 0.6
        limits[number] = round(raw, 1 - int(math.floor(math.log10(raw))))
    return limits


def write(workload: str, summary: dict):
    for folder, data in (("calibration", summary),
                         ("limits", limits_from(summary))):
        os.makedirs(os.path.join(BENCH, folder), exist_ok=True)
        with open(os.path.join(BENCH, folder, workload + ".json"), "w") as f:
            json.dump(data, f, indent=2)
            f.write("\n")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--write", action="store_true")
    args = ap.parse_args(argv)
    sys.path[:1] = [ROOT, os.path.join(ROOT, "src")]
    from bench import run
    spec = run.cell_spec(args.workload)
    devices = run.device_gate(spec["cell"]["chips"])
    run.enable_compile_cache()
    summary = calibrate(spec, args.seeds,
                        emit=lambda line: print(line, flush=True),
                        devices=devices)
    summary["seeds"] = {"program": args.seeds,
                        "control": args.seeds[:CONTROL_SEEDS]}
    if args.write:
        write(args.workload, summary)
    print(json.dumps({"summary": summary}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
