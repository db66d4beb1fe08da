"""End-to-end training driver: data pipeline -> jitted train step ->
checkpointing -> fault handling -> (optional) LEO analysis of the compiled
step.

On a CPU it drives reduced configs (`--smoke`) on a host mesh; on a TPU the
same driver runs published widths over the chips of the host.  `--analyze`
diagnoses the step's own compiled program on the LEO backend that models
the chip it ran on, so it needs a chip that backend table lists.  Example:

  PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b --smoke \
      --steps 50 --batch 8 --seq 64 --checkpoint-dir ckpt
  python -m repro.launch.train --arch qwen2-0.5b --steps 10 --batch 4 \
      --seq 1024 --analyze            # on a TPU, PYTHONPATH=src
  python -m repro.launch.train --arch glm4-9b --layers 6 --steps 10 \
      --batch 2 --seq 4096 --model-parallel 2   # a 2x2 v5e host
"""
from __future__ import annotations

import argparse
import time

import jax
from jax.sharding import NamedSharding, PartitionSpec as P


def train_shardings(mesh, cfg, state):
    """The train state's and the batch's shardings on `mesh`; `state` may
    be abstract (shapes only)."""
    from ..parallel.sharding import ShardingRules

    rules = ShardingRules(mesh, cfg)
    pspecs = rules.param_specs(state["params"])
    ospecs = rules.opt_specs(state["opt"], state["params"])
    state_specs = {"params": pspecs, "opt": ospecs, "step": P()}
    state_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), state_specs,
                            is_leaf=lambda x: isinstance(x, P))
    dp = rules.dp_spec
    batch_sharding = {
        "tokens": NamedSharding(mesh, P(dp, None)),
        "labels": NamedSharding(mesh, P(dp, None)),
        "embeds": NamedSharding(mesh, P(dp, None, None)),
    }
    return state_sh, batch_sharding


def build(arch: str, smoke: bool, batch: int, seq: int, mesh,
          microbatch: int = 1, grad_compression: bool = False,
          steps: int = 0, lr: float = 0.0, layers: int = 0):
    """`layers`, where given, trains that many of the architecture's
    layers: a pipeline stage's share of a model the mesh cannot hold."""
    from dataclasses import replace

    from ..configs import get_config, smoke_config
    from ..data.pipeline import DataPipeline
    from ..data.synthetic import SyntheticConfig, SyntheticTokenDataset
    from ..optim import AdamWConfig
    from ..runtime.steps import TrainOptions, init_train_state, \
        make_train_step

    cfg = get_config(arch)
    if smoke:
        cfg = smoke_config(cfg)
    if layers:
        cfg = replace(cfg, n_layers=layers)

    # Drawn on the mesh, each device making its own shards: a model that no
    # one device holds never passes through one.
    init = lambda key: init_train_state(key, cfg)  # noqa: E731
    state_sh, batch_sharding = train_shardings(
        mesh, cfg, jax.eval_shape(init, jax.random.PRNGKey(0)))
    state = jax.jit(init, out_shardings=state_sh)(jax.random.PRNGKey(0))
    ds = SyntheticTokenDataset(SyntheticConfig(
        vocab_size=cfg.vocab_size, seq_len=seq, d_model=cfg.d_model,
        frontend=cfg.frontend))
    pipeline = DataPipeline(ds, batch, shardings=batch_sharding)

    # The TrainOptions schedule defaults (100-step warmup over a 10k-step
    # horizon) are production-run constants; a short run that never leaves
    # warmup makes no measurable progress.  Scale the schedule to the run
    # that was actually requested.
    if steps > 0:
        warmup = max(1, min(100, steps // 10))
        total = steps
    else:
        warmup, total = 100, 10_000
    options = TrainOptions(remat="group", chunk=min(512, seq),
                           microbatch=microbatch,
                           grad_compression=grad_compression,
                           warmup_steps=warmup, total_steps=total)
    # Smoke configs are tiny (d_model 64); the production 3e-4 moves them
    # too slowly to beat per-batch loss noise inside a smoke-length run.
    if lr <= 0.0:
        lr = 3e-3 if smoke else AdamWConfig().lr
    opt_cfg = AdamWConfig(lr=lr)
    step_fn = jax.jit(make_train_step(cfg, opt_cfg, options=options),
                      donate_argnums=(0,))
    return cfg, state, state_sh, pipeline, step_fn


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--microbatch", type=int, default=1)
    ap.add_argument("--lr", type=float, default=0.0,
                    help="peak learning rate (0 = auto: 3e-3 smoke, "
                         "3e-4 production)")
    ap.add_argument("--grad-compression", action="store_true")
    ap.add_argument("--checkpoint-dir", default="")
    ap.add_argument("--checkpoint-every", type=int, default=25)
    ap.add_argument("--restore", action="store_true")
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--layers", type=int, default=0,
                    help="train this many of the architecture's layers "
                         "(0 = all)")
    ap.add_argument("--analyze", action="store_true",
                    help="run LEO on the compiled train step")
    args = ap.parse_args(argv)

    from .compile_cache import enable_compile_cache
    from .mesh import make_host_mesh
    enable_compile_cache()
    devices = jax.devices()
    print(f"devices: platform={devices[0].platform} "
          f"kind={devices[0].device_kind} count={len(devices)}")
    mesh = make_host_mesh(model_parallel=args.model_parallel)

    with mesh:
        cfg, state, state_sh, pipeline, step_fn = build(
            args.arch, args.smoke, args.batch, args.seq, mesh,
            microbatch=args.microbatch,
            grad_compression=args.grad_compression,
            steps=args.steps, lr=args.lr, layers=args.layers)

        manager = None
        start_step = 0
        if args.checkpoint_dir:
            from ..checkpoint.manager import CheckpointManager
            manager = CheckpointManager(args.checkpoint_dir, keep=3)
            if args.restore and manager.has_checkpoint():
                state, start_step = manager.restore_latest(
                    state, shardings=state_sh)
                print(f"restored from step {start_step}")

        losses, grad_norms, step_seconds = [], [], []
        t0 = time.time()
        it = pipeline(start_step)
        for step in range(start_step, args.steps):
            batch = next(it)
            t_step = time.perf_counter()
            state, metrics = jax.block_until_ready(step_fn(state, batch))
            step_seconds.append(time.perf_counter() - t_step)
            loss = float(metrics["loss"])
            losses.append(loss)
            grad_norms.append(float(metrics["grad_norm"]))
            if step % 10 == 0 or step == args.steps - 1:
                print(f"step {step:5d}  loss {loss:.4f}  "
                      f"gnorm {grad_norms[-1]:.3f}")
            if manager and (step + 1) % args.checkpoint_every == 0:
                manager.save(step + 1, state)
        if manager:
            manager.save(args.steps, state)
            manager.wait()
        wall = time.time() - t0

        params = jax.tree.leaves(state["params"])
        result = {"final_loss": losses[-1], "first_loss": losses[0],
                  "losses": losses, "grad_norms": grad_norms,
                  "step_seconds": step_seconds,
                  "master": state["opt"]["master"],
                  "steps": args.steps - start_step, "wall_seconds": wall,
                  "param_devices": len({d for p in params
                                        for d in p.sharding.device_set}),
                  "split_params": sum(not p.sharding.is_fully_replicated
                                      for p in params)}
        print(f"done: loss {losses[0]:.4f} -> {losses[-1]:.4f} "
              f"({result['steps']} steps, {wall:.1f}s)")

        if args.analyze:
            result.update(_analyze_step(
                cfg, step_fn.lower(state, pipeline.device_batch(0)),
                args.batch, args.seq, len(devices), devices[0].device_kind))
        return result


def _analyze_step(cfg, lowered, batch: int, seq: int, chips: int,
                  device_kind: str) -> dict:
    """LEO on the train step's own compiled program, priced on the backend
    that models `device_kind`."""
    from ..configs import model_flops
    from ..configs.base import ShapeConfig
    from ..core import LeoSession, backend_for_device_kind
    from ..core.roofline import compute_roofline

    backend = backend_for_device_kind(device_kind)
    compiled = lowered.compile()
    text = compiled.as_text()
    hints = {"total_devices": chips}
    session = LeoSession()
    an = session.analyze(text, backend=backend, hints=hints)
    print(an.summary())
    roofline = compute_roofline(
        session.parse(text, hints=hints), backend.hw, chips=chips,
        model_flops=model_flops(cfg, ShapeConfig("run", seq, batch, "train")),
        cost_analysis=compiled.cost_analysis(),
        memory_analysis=compiled.memory_analysis())
    print(roofline.summary_row())
    return {"leo_backend": backend.name,
            "leo_step_seconds": an.estimated_step_seconds,
            "useful_ratio": roofline.useful_ratio,
            "compiled_text": text}


if __name__ == "__main__":
    main()
