"""Training traffic: the program's jitted train step, dispatched back to back.

Set-up makes one object, the compiled step with its state: the weights and
the optimizer state come from the seed in one jitted call of the program's
initialiser, on the device, in the dtypes the configuration states.  The
traffic's batches are drawn from the seed in one jitted call.  Set-up then
drives that step through its first CHECKED_STEPS steps, on distinct
batches, and reads what the comparison needs: each step's loss and
pre-clip gradient norm, the first gradient from the optimizer's first
moment, and the master weights' change.  The window goes on from there
with the same step and state.  Once it has closed and the program's state
is freed, the plain reference trains the same first steps from the same
seed and batches.

The step is `repro.runtime.steps.make_train_step` under `jax.jit` with the
state donated, with the options `repro.launch.train.build` gives a run of
unstated length: group remat, attention chunks of min(512, seq_len),
AdamW at 3e-4 with 100 warmup steps over 10,000.

A traffic with a `mesh` (`{"data": d, "model": m}`) runs the program's
sharded step over the cell's d x m devices, as `repro.launch.train` lays it
out: the mesh of `repro.launch.mesh.make_host_mesh` and the state's and
batch's shardings of `repro.launch.train.train_shardings`.  The initialiser,
the batches and the weights' change come out in those shardings, so no
device holds a whole copy, and the step is jitted with them in and out.
Without a mesh the jitted programs take no sharding and run on one device.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib
import math
import sys
import time
from typing import Callable, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp

from bench import compare

DISTINCT_BATCHES = 8  # drawn from the seed; the window cycles through them
IN_FLIGHT = 2         # steps dispatched ahead of the one the host waits on
CHECKED_STEPS = 3     # first steps compared with the plain reference


def seed_key(seed: int):
    """A key from any seed up to 64 bits (PRNGKey alone keeps 32 of them)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


def arch_config(cfg: dict):
    from repro.configs.base import ArchConfig
    names = {f.name for f in dataclasses.fields(ArchConfig)}
    return ArchConfig(**{k: v for k, v in cfg.items() if k in names})


class Layout(NamedTuple):
    """The program's mesh and shardings for a traffic with a `mesh`."""
    mesh: object
    state: object     # the train state's tree of shardings
    batch: Dict       # "tokens" and "labels"


def layout(arch, traffic: dict, devices) -> Optional[Layout]:
    """The traffic's mesh over `devices`, laid out by the program's own
    rules; None for a traffic without one."""
    if "mesh" not in traffic:
        return None
    from repro.launch.mesh import make_host_mesh
    from repro.launch.train import train_shardings
    from repro.runtime.steps import init_train_state
    mesh = make_host_mesh(model_parallel=traffic["mesh"]["model"],
                          devices=devices)
    abstract = jax.eval_shape(lambda: init_train_state(jax.random.PRNGKey(0),
                                                       arch))
    state, batch = train_shardings(mesh, arch, abstract)
    return Layout(mesh, state, {k: batch[k] for k in ("tokens", "labels")})


def build_step(arch, traffic: dict, lay: Optional[Layout] = None) -> Callable:
    from repro.optim import AdamWConfig
    from repro.runtime.steps import TrainOptions, make_train_step
    options = TrainOptions(remat="group", chunk=min(512, traffic["seq_len"]))
    step = make_train_step(arch, AdamWConfig(), options)
    if lay is None:
        return jax.jit(step, donate_argnums=(0,))
    return jax.jit(step, in_shardings=(lay.state, lay.batch),
                   out_shardings=(lay.state, None),
                   donate_argnums=(0,))


def build_init(arch, lay: Optional[Layout] = None) -> Callable:
    from repro.runtime.steps import init_train_state
    init = lambda key: init_train_state(key, arch)  # noqa: E731
    if lay is None:
        return jax.jit(init)
    return jax.jit(init, out_shardings=lay.state)


def build_change(arch, lay: Optional[Layout] = None) -> Callable:
    """Norms of the master weights' change since the seed's parameters."""
    from repro.models import init_params

    def params_of(key):
        params = init_params(key, arch)
        if lay is None:
            return params
        return jax.lax.with_sharding_constraint(params,
                                                lay.state["opt"]["master"])
    return jax.jit(lambda master, key: compare.norms(jax.tree.map(
        lambda m, p: m - p.astype(jnp.float32), master, params_of(key))))


def make_batches(key, traffic: dict, vocab: int,
                 lay: Optional[Layout] = None):
    n, b, s = DISTINCT_BATCHES, traffic["batch"], traffic["seq_len"]

    def draw(key):
        tokens = jax.random.randint(key, (n, b, s + 1), 0, vocab, jnp.int32)
        return tuple({"tokens": tokens[i, :, :-1], "labels": tokens[i, :, 1:]}
                     for i in range(n))
    if lay is None:
        return jax.jit(draw)(key)
    return jax.jit(draw, out_shardings=(lay.batch,) * n)(key)


def first_steps(step, state, batches, n: int, change_fn, param_key,
                b1: float):
    """The program's readings over its first `n` steps."""
    losses, gnorms, first_grad = [], [], None
    for i in range(n):
        state, metrics = step(state, batches[i])
        losses.append(metrics["loss"])
        gnorms.append(metrics["grad_norm"])
        if i == 0:
            # After one step the first moment is (1 - b1) g.
            first_grad = compare.as_dict(
                jax.jit(compare.norms)(state["opt"]["mu"]), 1.0 / (1.0 - b1))
    readings = {"losses": [float(x) for x in losses],
                "grad_norms": [float(x) for x in gnorms],
                "first_grad": first_grad,
                "change": compare.as_dict(change_fn(state["opt"]["master"],
                                                    param_key))}
    return state, readings


def window(step, state, batches, seconds: float):
    """Steps back to back for `seconds`, at most IN_FLIGHT unfinished; the
    window closes when the last step's state is ready."""
    losses = []
    n = 0
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.window"):
        while True:
            with jax.profiler.TraceAnnotation("bench.step"):
                state, metrics = step(state, batches[n % len(batches)])
            n += 1
            losses.append(metrics["loss"])
            if n > IN_FLIGHT:
                with jax.profiler.TraceAnnotation("bench.wait"):
                    losses[n - 1 - IN_FLIGHT].block_until_ready()
            if time.perf_counter() - t0 >= seconds:
                break
        with jax.profiler.TraceAnnotation("bench.drain"):
            jax.block_until_ready(state)
    t1 = time.perf_counter()
    return state, n, t0, t1, losses


def mesh_context(lay: Optional[Layout]):
    """The context the program runs in: its mesh, where it has one."""
    return lay.mesh if lay is not None else contextlib.nullcontext()


def checked_start(cfg: dict, traffic: dict, seed: int, fault=None,
                  devices=None):
    """Set-up up to the window, on `devices` where the traffic has a mesh:
    the step, its state after the checked steps, the batches, the
    program's readings, the parameters' key and the layout (None without a
    mesh; the window runs in `mesh_context` of it).  `fault`, where given,
    breaks each batch before the step sees it."""
    reference = importlib.import_module(f"bench.reference.{cfg['reference']}")
    arch = arch_config(cfg)
    key = seed_key(seed)
    param_key, data_key = jax.random.fold_in(key, 0), jax.random.fold_in(key, 1)
    t = time.perf_counter()
    lay = layout(arch, traffic, devices)
    with mesh_context(lay):
        step = build_step(arch, traffic, lay)
        state = build_init(arch, lay)(param_key)
        batches = make_batches(data_key, traffic, cfg["vocab_size"], lay)
        jax.block_until_ready((state, batches))
        t_init = time.perf_counter()
        fed = [fault(b) for b in batches] if fault else batches
        state, prog = first_steps(step, state, fed, CHECKED_STEPS,
                                  build_change(arch, lay), param_key,
                                  reference.ADAM["b1"])
    print(f"set-up: weights and batches {t_init - t:.3f} s, "
          f"{CHECKED_STEPS} checked steps "
          f"{time.perf_counter() - t_init:.3f} s", file=sys.stderr, flush=True)
    return step, state, batches, prog, param_key, lay


def reference_readings(cfg: dict, traffic: dict, param_key, batches,
                       precision: str = "f32", devices=None) -> Dict:
    """The plain reference over the same checked steps, laid out over
    `devices` (default: the first device)."""
    reference = importlib.import_module(f"bench.reference.{cfg['reference']}")
    t = time.perf_counter()
    ref = reference.train(cfg, param_key,
                          [(b["tokens"], b["labels"]) for b in
                           batches[:CHECKED_STEPS]],
                          compare.norms, precision, devices)
    ref["first_grad"] = compare.as_dict(ref["first_grad"])
    ref["change"] = compare.as_dict(ref["change"])
    print(f"reference ({precision}): {time.perf_counter() - t:.3f} s",
          file=sys.stderr, flush=True)
    return ref


def run(cfg: dict, traffic: dict, seed: int, seconds: float,
        traced: Callable, peak_memory: Callable, devices) -> Dict:
    """One run on `devices`, the cell's.  `traced()` is the context the
    window runs in; `peak_memory()` reads the devices' peak once the window
    has closed."""
    step, state, batches, prog, param_key, lay = checked_start(
        cfg, traffic, seed, devices=devices)
    with mesh_context(lay), traced():
        state, n, t0, t1, losses = window(step, state, batches, seconds)
    memory_peak = peak_memory()
    failed = sum(not math.isfinite(float(x)) for x in losses)
    del state, losses
    gc.collect()

    ref = reference_readings(cfg, traffic, param_key, batches,
                             devices=devices)
    tokens = n * traffic["batch"] * traffic["seq_len"]
    return {"window_start": t0, "window_s": t1 - t0, "attempted": n,
            "failed": failed, "tokens": tokens,
            "end_to_end": {"train_tokens_per_s": tokens / (t1 - t0)},
            "memory_peak_bytes": memory_peak,
            "checks": compare.gaps(prog, ref)}
