#!/usr/bin/env python3
"""Bring-up smoke run: LEO's main path on the chip, through the entry points.

  python chip_smoke.py              # one TPU chip
  python chip_smoke.py --chips 4    # the sharded path on a 2x2 v5e host
  python chip_smoke.py --chips 4 --arch glm4-9b   # 6 of its 40 layers

With no option it drives, in one process:

  1. the device gate: a TPU, and the LEO backend that models its kind;
  2. the persistent compilation cache (`repro.launch.compile_cache`);
  3. `repro.launch.train` on qwen2-0.5b at published widths (10 steps,
     batch 4 x 1024 tokens), plus a forward loss on one fixed batch that
     must match the same parameters on the CPU backend of this process;
  4. LEO on the train step's own compiled program (`LeoService`): the
     estimate beside the measured step, the model-FLOP share of LEO's
     counted FLOPs (`useful_ratio`), and every registered backend;
  5. each Pallas kernel compiled by Mosaic at a real model width and
     checked against its oracle in `repro.kernels.ref`; the flash
     attention pair also by its q, k and v gradients, at the head layouts
     of both one-chip cells.

`--chips 4` runs only the trainer on a 2x2 data x model mesh and the same
steps on a witness mesh in this process: one chip, or for a model one chip
cannot hold (`--arch glm4-9b`, at the 6 layers of its benchmark cell)
tensor parallelism alone over the four chips, with no weights or optimizer
state sharded over data.  It compares the loss and gradient norm of every
step and, leaf by leaf and element by element, the master weights'
change over the run; it checks that the parameter shards cover the four
chips, and reads the step's collective bytes through LEO.

Any failed check raises and ends the run with a non-zero code.  The last
line of standard output is the result as one JSON object.  Step times
printed here are smoke readings, not benchmark numbers.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time
from contextlib import contextmanager
from unittest import mock

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))
# The reference check needs the CPU backend beside the chip's.
if os.environ.get("JAX_PLATFORMS") and \
        "cpu" not in os.environ["JAX_PLATFORMS"].split(","):
    os.environ["JAX_PLATFORMS"] += ",cpu"

ARCH, BATCH, SEQ = "qwen2-0.5b", 4, 1024
TRAIN_ARGS = ["--arch", ARCH, "--steps", "10", "--batch", str(BATCH),
              "--seq", str(SEQ)]
# The step-0 learning rate is 0 (one warmup step), so the last of 4 steps
# comes after two updates.
SHARDED_STEPS = 4
# The sharded phase's architectures: the layers trained (0: all) and the
# chips of the witness mesh, all on its model axis.
SHARDED_ARCHS = {"qwen2-0.5b": (0, 1), "glm4-9b": (6, 4)}
REF_TOKENS = (1, 256)           # the fixed batch of the chip/CPU check
# Bounds on relative differences, each about 5x above the gap measured on
# a v5e (in brackets; PERF.md).  Both sides of a comparison run the same
# programs on the same inputs, so the gaps repeat from run to run.
REF_LOSS_RTOL = 1e-3            # chip vs CPU forward loss (1.9e-4)
# 2x2 mesh vs its witness (gaps measured against one chip, qwen2-0.5b).
# Before any update the two differ only in the order of reductions; each
# update widens the gap.  A run that trains on half of each batch lands
# 0.41 away in step-0 gradient norm.
SHARDED_STEP0_RTOL = 6e-4       # step-0 loss, grad norm (5.6e-5, 1.2e-4)
SHARDED_STEP_RTOL = 2e-2        # later steps (3.0e-3, 3.7e-3)
# The master weights' change, leaf by leaf, element by element: 3x above
# GLM-4-9B's embedding on a v5e (0.116; qwen2-0.5b 0.061 and a narrowed GLM
# 0.043 on four CPU devices), as Adam moves an element whose gradient is
# round-off by the learning rate either way.  A half-batch run lands 0.88
# away over the whole tree; a leaf moved 6.4x as far reads 5.4 (PERF.md).
SHARDED_UPDATE_LEAF_RTOL = 0.35
USEFUL_RATIO_RANGE = (0.5, 1.05)
# Flash attention's q, k and v gradients from bf16 inputs against the f32
# oracle's: max |g - g_ref| / max |g_ref|, per gradient (6.7e-3).
FLASH_GRAD_RTOL = 3e-2
# (batch, seq, q heads, kv heads, head_dim, window) of the one-chip cells.
FLASH_LAYOUTS = {"qwen2-0.5b": (4, 1024, 14, 2, 64, None),
                 "hymba-1.5b": (1, 4096, 25, 5, 64, 1024)}


class SmokeFailure(RuntimeError):
    """A phase's check did not hold."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


@contextmanager
def phase(name: str):
    print(f"== {name}", flush=True)
    t0 = time.perf_counter()
    yield
    print(f"== {name}: ok ({time.perf_counter() - t0:.1f} s)", flush=True)


def device_gate(chips: int):
    """The first `chips` TPU devices and the LEO backend of their kind."""
    import jax
    from repro.core import backend_for_device_kind

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU; JAX found platform "
                         f"{devices[0].platform!r}")
    check(len(devices) >= chips,
          f"{chips} chips asked for, {len(devices)} found")
    backend = backend_for_device_kind(devices[0].device_kind)
    print(f"device: platform={devices[0].platform} "
          f"kind={devices[0].device_kind} count={len(devices)} "
          f"-> LEO backend {backend.name}")
    return devices[:chips], backend


def train_phase() -> dict:
    from repro.launch import train

    result = train.main(TRAIN_ARGS + ["--analyze"])
    losses = result["losses"]
    check(all(math.isfinite(x) for x in losses), f"non-finite loss: {losses}")
    check(losses[-1] < losses[0],
          f"loss did not fall: {losses[0]} -> {losses[-1]}")
    warm = sorted(result["step_seconds"][1:])
    result["warm_step_seconds"] = warm[len(warm) // 2]
    print(f"smoke reading, not a benchmark: warm step median "
          f"{result['warm_step_seconds']:.4f} s over {len(warm)} steps; "
          f"first step incl. compile {result['step_seconds'][0]:.1f} s")
    return result


def reference_phase() -> float:
    """Forward loss of one fixed batch on the default device and on the
    CPU backend of this process, from the same parameters."""
    import jax
    from repro.configs import get_config
    from repro.models import init_params, loss_fn

    cfg = get_config(ARCH)
    tokens = jax.random.randint(jax.random.PRNGKey(1), REF_TOKENS, 0,
                                cfg.vocab_size)
    batch = {"tokens": tokens, "labels": jax.numpy.roll(tokens, -1, axis=1)}
    params = init_params(jax.random.PRNGKey(0), cfg)
    fwd = jax.jit(lambda p, b: loss_fn(p, cfg, b))
    chip_loss = float(fwd(params, batch))
    cpu = jax.devices("cpu")[0]
    # The model picks its Pallas kernels by the process's default backend,
    # the chip's here: trace the CPU program as a CPU process would.
    with mock.patch.object(jax, "default_backend", lambda: "cpu"):
        cpu_loss = float(jax.jit(lambda p, b: loss_fn(p, cfg, b))(
            jax.device_put(params, cpu), jax.device_put(batch, cpu)))
    rel = _rel(chip_loss, cpu_loss)
    print(f"forward loss on {REF_TOKENS}: device {chip_loss:.6f} "
          f"cpu {cpu_loss:.6f} rel diff {rel:.3e} (bound {REF_LOSS_RTOL})")
    check(rel <= REF_LOSS_RTOL, "device loss disagrees with the CPU")
    return rel


def leo_phase(result: dict, backend) -> dict:
    from repro.core import LeoService

    text = result["compiled_text"]
    hints = {"total_devices": 1}
    svc = LeoService()
    diag = svc.diagnose(text, backend=backend.name, hints=hints)
    print(f"LEO {backend.name}: estimated step {diag.estimated_step_seconds:.4g}"
          f" s; measured warm step {result['warm_step_seconds']:.4g} s")
    lo, hi = USEFUL_RATIO_RANGE
    useful = result["useful_ratio"]
    print(f"useful_ratio (model FLOPs / LEO's HLO FLOPs) {useful:.4f}")
    check(lo <= useful <= hi, f"useful_ratio {useful} outside [{lo}, {hi}]")
    per_backend = svc.compare_backends(text, hints=hints)
    for name, an in sorted(per_backend.items()):
        print(f"  {name:14s} estimated step {an.estimated_step_seconds:.4g} s")
        check(math.isfinite(an.estimated_step_seconds)
              and an.estimated_step_seconds > 0, f"{name}: no estimate")
    svc.close()
    return {"leo_step_seconds": diag.estimated_step_seconds,
            "useful_ratio": useful, "backends": len(per_backend)}


def kernel_cases():
    """(name, op, oracle, args builder, atol = rtol) at real model widths:
    flash at qwen2-0.5b heads, rmsnorm at its d_model, the selective scan
    at hymba-1.5b's d_inner and state, mLSTM/sLSTM at xlstm-125m's.  The
    bf16 kernels get about 2.5 bf16 ulps of 1.0, the f32 ones 1e-4."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops, ref

    s, din, d_sl = 1024, 3200, 768

    def rand(i, shape, dtype=jnp.float32, scale=0.5):
        x = jax.random.normal(jax.random.PRNGKey(i), shape) * scale
        return x.astype(dtype)

    def flash():
        return (rand(0, (1, s, 14, 64), jnp.bfloat16),
                rand(1, (1, s, 2, 64), jnp.bfloat16),
                rand(2, (1, s, 2, 64), jnp.bfloat16))

    def flash_hymba():
        return flash_inputs(*FLASH_LAYOUTS["hymba-1.5b"][:5])[:3]

    def rms():
        return rand(3, (s, 896), jnp.bfloat16), 1.0 + 0.1 * rand(4, (896,))

    def ssm():
        return (jax.nn.softplus(rand(5, (1, s // 4, din))),
                rand(6, (1, s // 4, din)), rand(7, (1, s // 4, 16)),
                rand(15, (1, s // 4, 16)),
                -jnp.arange(1.0, 17.0)[None].repeat(din, 0))  # A at init

    def mlstm():
        return (rand(8, (2, s // 2, 4, 192)),
                rand(9, (2, s // 2, 4, 192)) / math.sqrt(192),
                rand(10, (2, s // 2, 4, 192)), rand(11, (2, s // 2, 4)),
                jax.nn.log_sigmoid(rand(12, (2, s // 2, 4)) + 2.0))

    def slstm():
        return (rand(13, (4, s // 4, 4 * d_sl)),
                rand(14, (d_sl, 4 * d_sl)) * 0.1 / math.sqrt(d_sl / 64))

    bf16_tol, f32_tol = 2e-2, 1e-4
    return [
        ("flash_attention", ops.flash_attention_op,
         ref.flash_attention_ref, flash, bf16_tol),
        ("flash_attention_swa",
         lambda *a, **k: ops.flash_attention_op(*a, window=1024, **k),
         lambda *a: ref.flash_attention_ref(*a, window=1024), flash_hymba,
         bf16_tol),
        ("rmsnorm_baseline", ops.rmsnorm_baseline_op, ref.rmsnorm_ref, rms,
         bf16_tol),
        ("rmsnorm_pipelined", ops.rmsnorm_op, ref.rmsnorm_ref, rms, bf16_tol),
        ("selective_scan", ops.selective_scan_op, ref.selective_scan_ref,
         ssm, f32_tol),
        ("mlstm_chunkwise",
         lambda *a, **k: ops.mlstm_chunkwise_op(*a, chunk=64, **k),
         ref.mlstm_ref, mlstm, f32_tol),
        ("slstm_scan", lambda *a, **k: ops.slstm_scan_op(*a, chunk=64, **k),
         ref.slstm_scan_ref, slstm, f32_tol),
    ]


def kernels_phase() -> dict:
    """Each kernel compiled by Mosaic against its oracle at `highest`
    matmul precision, within |out - ref| <= tol + tol * |ref|."""
    import jax
    import numpy as np

    errors = {}
    for name, op, oracle, make_args, tol in kernel_cases():
        args = make_args()
        kernel = jax.jit(lambda *a, op=op: op(*a, interpret=False))
        compiled = kernel.lower(*args).compile()
        check("tpu_custom_call" in compiled.as_text(),
              f"{name}: no Mosaic kernel in the compiled program")
        out = np.asarray(compiled(*args), np.float32)
        with jax.default_matmul_precision("highest"):
            expect = np.asarray(jax.jit(oracle)(*args), np.float32)
        err = np.abs(out - expect)
        used = float(np.max(err / (tol + tol * np.abs(expect))))
        errors[name] = float(np.max(err))
        print(f"  {name:18s} {tuple(args[0].shape)} max abs err "
              f"{errors[name]:.3e}; worst err / ({tol} + {tol}|ref|) "
              f"{used:.3e}")
        check(used <= 1.0, f"{name}: output outside the bound")
    return errors


def flash_inputs(b, s, h, kv, hd):
    """bf16 q, k, v and an f32 weight on the output, from fixed keys."""
    import jax
    import jax.numpy as jnp
    ks = jax.random.split(jax.random.PRNGKey(20), 4)
    return ((jax.random.normal(ks[0], (b, s, h, hd)) * 0.5).astype(
        jnp.bfloat16),
        (jax.random.normal(ks[1], (b, s, kv, hd)) * 0.5).astype(
            jnp.bfloat16),
        (jax.random.normal(ks[2], (b, s, kv, hd)) * 0.5).astype(
            jnp.bfloat16),
        jax.random.normal(ks[3], (b, s, h, hd)))


def flash_grads_phase() -> dict:
    """The flash kernel pair's q, k and v gradients of sum(out * w) against
    the f32 oracle's at `highest` precision, at each one-chip cell's head
    layout and rows: each gradient within FLASH_GRAD_RTOL of the oracle's
    largest element."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ops, ref

    worst = {}
    for cell, (b, s, h, kv, hd, window) in FLASH_LAYOUTS.items():
        q, k, v, w = flash_inputs(b, s, h, kv, hd)

        def grads(attend):
            return jax.jit(jax.grad(lambda q, k, v: jnp.sum(
                attend(q, k, v).astype(jnp.float32) * w), (0, 1, 2)))
        kernel = grads(lambda q, k, v: ops.flash_attention_op(
            q, k, v, window=window, interpret=False)).lower(q, k, v).compile()
        check("flash_attention_bwd" in kernel.as_text(),
              f"{cell}: no flash backward kernel in the gradient")
        got = kernel(q, k, v)
        with jax.default_matmul_precision("highest"):
            want = grads(lambda q, k, v: ref.flash_attention_ref(
                *(x.astype(jnp.float32) for x in (q, k, v)),
                window=window))(q, k, v)
        gaps = [float(np.max(np.abs(np.asarray(g, np.float32) - e))
                      / np.max(np.abs(e)))
                for g, e in zip(got, (np.asarray(x) for x in want))]
        worst[cell] = max(gaps)
        print(f"  flash gradients {cell} {tuple(q.shape)}: max |g - ref| / "
              f"max |ref| dq {gaps[0]:.3e} dk {gaps[1]:.3e} dv {gaps[2]:.3e}"
              f" (bound {FLASH_GRAD_RTOL})")
        check(worst[cell] <= FLASH_GRAD_RTOL,
              f"{cell}: flash gradients outside the bound")
    return worst


def _rel(a, b) -> float:
    return abs(a - b) / abs(b)


def _change_fn(cfg, master):
    """A jittable map from the master weights to their change since the
    trainer's initial parameters (`PRNGKey(0)`), laid out as `master`."""
    import jax
    import jax.numpy as jnp
    from repro.models import init_params

    layout = jax.tree.map(lambda m: m.sharding, master)

    def change(master):
        params = jax.lax.with_sharding_constraint(
            init_params(jax.random.PRNGKey(0), cfg), layout)
        return jax.tree.map(lambda m, p: m - p.astype(jnp.float32),
                            master, params)
    return change, layout


def _change(cfg, master):
    """The master weights' change, on the host."""
    import jax
    change, _ = _change_fn(cfg, master)
    return jax.device_get(jax.jit(change)(master))


def _leaf_gaps(cfg, master, witness_change) -> dict:
    """Each leaf's |change - witness's change| / max(|witness's change|,
    the median leaf's), one per layer of a stacked group.  Element by
    element: a shard in the wrong place or a wrong sign moves it as a
    wrong magnitude does."""
    import jax
    import numpy as np
    from bench import compare

    change, layout = _change_fn(cfg, master)
    witness = jax.device_put(witness_change, layout)

    def norms(master, witness):
        return (compare.norms(jax.tree.map(lambda c, w: c - w,
                                           change(master), witness)),
                compare.norms(witness))
    diff, ref = (compare.as_dict(t) for t in jax.jit(norms)(master, witness))
    floor = float(np.median(list(ref.values())))
    return {k: diff[k] / max(ref[k], floor) for k in ref}


def sharded_phase(devices, arch: str) -> dict:
    """The trainer on a 2x2 data x model mesh against the witness mesh: the
    loss and gradient norm of every step from the same initial state and
    batches, and the master weights' change over the run, leaf by leaf."""
    from repro.core import collective_summary, parse_hlo
    from repro.launch import train
    from repro.launch.mesh import make_host_mesh

    layers, witness_chips = SHARDED_ARCHS[arch]
    run_args = ["--arch", arch, "--steps", str(SHARDED_STEPS), "--batch",
                str(BATCH), "--seq", str(SEQ), "--layers", str(layers)]
    mesh = make_host_mesh(witness_chips, devices=devices[:witness_chips])
    with mesh:
        cfg, state, _, pipeline, step_fn = train.build(
            arch, False, BATCH, SEQ, mesh, steps=SHARDED_STEPS, layers=layers)
        witness = {"losses": [], "grad_norms": []}
        for i in range(SHARDED_STEPS):
            state, metrics = step_fn(state, pipeline.device_batch(i))
            witness["losses"].append(float(metrics["loss"]))
            witness["grad_norms"].append(float(metrics["grad_norm"]))
        witness_change = _change(cfg, state["opt"]["master"])
    del state, metrics

    result = train.main(run_args + ["--model-parallel", "2", "--analyze"])
    worst = 0.0
    for i in range(SHARDED_STEPS):
        bound = SHARDED_STEP0_RTOL if i == 0 else SHARDED_STEP_RTOL
        loss_rel = _rel(result["losses"][i], witness["losses"][i])
        gnorm_rel = _rel(result["grad_norms"][i], witness["grad_norms"][i])
        print(f"step {i}: loss 2x2 {result['losses'][i]:.6f} witness "
              f"{witness['losses'][i]:.6f} rel {loss_rel:.3e}; grad norm "
              f"2x2 {result['grad_norms'][i]:.6f} witness "
              f"{witness['grad_norms'][i]:.6f} rel {gnorm_rel:.3e} "
              f"(bound {bound})")
        check(max(loss_rel, gnorm_rel) <= bound,
              f"step {i} of the 2x2 mesh disagrees with the witness")
        worst = max(worst, loss_rel, gnorm_rel)
    leaf_gaps = _leaf_gaps(cfg, result.pop("master"), witness_change)
    del witness_change
    update_rel = max(leaf_gaps.values())
    far = sorted(leaf_gaps, key=lambda k: -leaf_gaps[k])[:3]
    print(f"master weights' change, worst leaf gap {update_rel:.3e} (bound "
          f"{SHARDED_UPDATE_LEAF_RTOL}); farthest leaves: "
          + ", ".join(f"{k} {leaf_gaps[k]:.3e}" for k in far))
    check(update_rel <= SHARDED_UPDATE_LEAF_RTOL,
          "the 2x2 mesh's master weights' change disagrees with the "
          "witness's in some leaf")
    print(f"parameter shards on {result['param_devices']} devices, "
          f"{result['split_params']} leaves split")
    check(result["param_devices"] == len(devices),
          "parameter shards do not cover every chip")
    check(result["split_params"] > 0, "no parameter is split across chips")
    colls = collective_summary(parse_hlo(
        result["compiled_text"], hints={"total_devices": len(devices)}),
        trip_aware=True)
    wire = sum(s.wire_bytes for s in colls.values())
    print("LEO collectives: " + ", ".join(
        f"{k} {v.wire_bytes:.3e} B" for k, v in sorted(colls.items())))
    check(wire > 0, "LEO counts no collective bytes in the sharded step")
    return {"step_rel_diff": worst, "update_rel_diff": update_rel,
            "collective_bytes": wire}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--arch", choices=sorted(SHARDED_ARCHS), default=ARCH,
                    help="the sharded phase's architecture (--chips 4)")
    args = ap.parse_args(argv)

    with phase("device gate"):
        devices, backend = device_gate(args.chips)
    with phase("compile cache"):
        from repro.launch.compile_cache import enable_compile_cache
        print(f"compile cache: {enable_compile_cache()}")

    if args.chips == 4:
        with phase(f"sharded trainer on 2x2 vs its witness ({args.arch})"):
            sharded_phase(devices, args.arch)
    else:
        with phase("train at full width"):
            result = train_phase()
        with phase("device vs CPU forward loss"):
            reference_phase()
        with phase("LEO on the compiled step"):
            leo_phase(result, backend)
        with phase("Mosaic kernels vs oracles"):
            kernels_phase()
        with phase("flash attention gradients vs oracle"):
            flash_grads_phase()

    d = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
