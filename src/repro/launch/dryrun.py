"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this:
  1. builds the production mesh (16x16 single-pod / 2x16x16 multi-pod),
  2. lowers the right step (train_step / prefill_step / serve_step) with
     in/out shardings from `repro.parallel.sharding`,
  3. compiles, prints `memory_analysis()` (proves it fits) and
     `cost_analysis()` (FLOPs/bytes for the roofline),
  4. derives the three roofline terms (compute / memory / collective) from
     the compiled HLO via LEO's parser, and
  5. optionally runs the full LEO root-cause analysis (--analyze).

Artifacts land in experiments/dryrun/<arch>__<shape>__<mesh>.json (plus the
HLO text with --save-hlo) and are consumed by benchmarks and EXPERIMENTS.md.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-0.5b \
      --shape train_4k --mesh both --analyze
  PYTHONPATH=src python -m repro.launch.dryrun --arch all --shape all
"""
import argparse
import gzip
import json
import os
import time
import traceback

import jax
from jax.sharding import NamedSharding, PartitionSpec as P


def use_host_devices(count: int = 512) -> None:
    """Run on the CPU backend with `count` virtual devices, enough for the
    production meshes.  Appends to any XLA_FLAGS already set; call before
    the process first touches a device."""
    flag = f"--xla_force_host_platform_device_count={count}"
    os.environ["XLA_FLAGS"] = " ".join(
        f for f in (os.environ.get("XLA_FLAGS", ""), flag) if f)
    jax.config.update("jax_platforms", "cpu")


def _sharding_tree(mesh, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def _placed(tree, shardings):
    """Abstract operands that carry their shardings in their type: the step
    reads the mesh it runs over from its operands
    (`kernels.placement.step_mesh`)."""
    return jax.tree.map(lambda x, s: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=s), tree, shardings)


def lower_cell(cfg, shape, mesh, opts=None):
    """Lower + compile one (arch, shape, mesh) cell. Returns (lowered,
    compiled, seconds)."""
    from ..parallel.sharding import ShardingRules
    from ..runtime.steps import (
        TrainOptions,
        default_microbatch,
        make_prefill_step,
        make_serve_step,
        make_train_step,
    )
    from . import specs as S

    rules = ShardingRules(mesh, cfg)
    if opts is None:
        import numpy as np
        dp = int(np.prod([mesh.shape[a] for a in rules.dp_axes]))
        opts = TrainOptions(microbatch=default_microbatch(
            cfg, shape.global_batch, shape.seq_len, dp))
    t0 = time.time()
    with mesh:
        if shape.kind == "train":
            state = S.abstract_train_state(cfg)
            batch = S.batch_specs(cfg, shape)
            pspecs = rules.param_specs(state["params"])
            ospecs = rules.opt_specs(state["opt"], state["params"])
            state_sh = _sharding_tree(
                mesh, {"params": pspecs, "opt": ospecs, "step": P()})
            batch_sh = _sharding_tree(mesh, rules.batch_specs(cfg, shape))
            step = make_train_step(cfg, options=opts)
            lowered = jax.jit(
                step,
                donate_argnums=(0,),  # train state updates in place
                in_shardings=(state_sh, batch_sh),
                out_shardings=(state_sh, None),
            ).lower(_placed(state, state_sh), _placed(batch, batch_sh))
        elif shape.kind == "prefill":
            params = S.abstract_params(cfg)
            batch = S.batch_specs(cfg, shape)
            params_sh = _sharding_tree(mesh, rules.param_specs(params))
            batch_sh = _sharding_tree(mesh, rules.batch_specs(cfg, shape))
            step = make_prefill_step(cfg, chunk=min(512, shape.seq_len))
            lowered = jax.jit(
                step,
                in_shardings=(params_sh, batch_sh),
                out_shardings=NamedSharding(mesh, rules.logits_spec(shape)),
            ).lower(_placed(params, params_sh), _placed(batch, batch_sh))
        else:  # decode
            params = S.abstract_params(cfg)
            dstate = S.abstract_decode_state(cfg, shape)
            batch = S.batch_specs(cfg, shape)
            params_sh = _sharding_tree(mesh, rules.param_specs(params))
            state_sh = _sharding_tree(mesh,
                                      rules.decode_state_specs(dstate, shape))
            tok_sh = NamedSharding(mesh, rules.batch_specs(cfg, shape)["token"])
            pos_sh = NamedSharding(mesh, P())
            step = make_serve_step(cfg)
            lowered = jax.jit(
                step,
                donate_argnums=(1,),  # KV cache / state updates in place
                in_shardings=(params_sh, state_sh, tok_sh, pos_sh),
                out_shardings=(tok_sh, None, state_sh),
            ).lower(_placed(params, params_sh), _placed(dstate, state_sh),
                    _placed(batch["token"], tok_sh),
                    _placed(batch["pos"], pos_sh))
        compiled = lowered.compile()
    return lowered, compiled, time.time() - t0


def _parse_flags(spec: str) -> dict:
    """`name=value,...` -> {name: value}, for `models.flags.flags`."""
    out = {}
    for part in (spec or "").split(","):
        if part.strip():
            k, _, v = part.partition("=")
            out[k.strip()] = v.strip()
    return out


_SERVICES = {}
_METRICS = None


def get_metrics():
    """Process-wide MetricsRegistry shared by every per-outdir service,
    so a sweep's `--metrics-out` dump covers all cells."""
    global _METRICS
    if _METRICS is None:
        from ..serve.metrics import MetricsRegistry
        _METRICS = MetricsRegistry()
    return _METRICS


def get_service(outdir: str):
    """One disk-backed LeoService per artifact dir: every cell in this
    process shares the parse/graph/analysis caches, and a *second process*
    re-running a cell against the warm `<outdir>/.leo_cache` performs zero
    HLO parses (modules and diagnoses reload from the content-addressed
    disk tier).  The tier is bounded — 512 MiB cap, 14-day idle TTL — so a
    long-lived sweep directory cannot grow without bound."""
    from ..core import LeoService
    svc = _SERVICES.get(outdir)
    if svc is None:
        svc = LeoService(cache_dir=os.path.join(outdir, ".leo_cache"),
                         disk_cache_max_bytes=512 * 2**20,
                         disk_cache_ttl_seconds=14 * 24 * 3600.0,
                         metrics=get_metrics())
        _SERVICES[outdir] = svc
    return svc


def run_cell(arch: str, shape_name: str, mesh_kind: str, outdir: str,
             analyze: bool = False, save_hlo: bool = False,
             hw_name: str = "tpu_v5e", force: bool = False,
             model_flags: dict = None) -> dict:
    from ..configs import get_config, get_shape, model_flops, shapes_for
    from ..core import get_backend
    from ..core.roofline import compute_roofline
    from .mesh import make_production_mesh

    cfg = get_config(arch)
    shape = get_shape(shape_name)
    label = f"{arch}__{shape_name}__{mesh_kind}"
    path = os.path.join(outdir, label + ".json")
    if os.path.exists(path) and not force:
        with open(path) as f:
            return json.load(f)

    if shape.name == "long_500k" and not cfg.supports_long_context:
        result = {"label": label, "status": "skipped",
                  "reason": "full quadratic attention at 524k decode; "
                            "skip per DESIGN.md long-context applicability"}
        os.makedirs(outdir, exist_ok=True)
        with open(path, "w") as f:
            json.dump(result, f, indent=2)
        return result

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    chips = int(len(mesh.devices.flat))
    try:
        from ..models.flags import flags as flags_ctx
        with flags_ctx(**(model_flags or {})):
            lowered, compiled, secs = lower_cell(cfg, shape, mesh)
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        hlo = compiled.as_text()
        service = get_service(outdir)
        hints = {"total_devices": chips}
        module = service.parse(hlo, hints=hints)
        hw = get_backend(hw_name).hw
        rl = compute_roofline(
            module, hw, chips=chips, label=label,
            model_flops=model_flops(cfg, shape),
            cost_analysis=cost, memory_analysis=mem)
        result = {"label": label, "status": "ok", "chips": chips,
                  "compile_seconds": secs, "roofline": rl.to_dict()}
        if analyze:
            diag = service.diagnose(hlo, backend=hw_name, hints=hints)
            result["leo"] = diag.to_dict()
        if save_hlo:
            with gzip.open(os.path.join(outdir, label + ".hlo.gz"),
                           "wt") as f:
                f.write(hlo)
        print(f"[ok] {label}: compile={secs:.1f}s  {rl.summary_row()}")
        print(f"     memory: {mem}")
    except Exception as e:  # noqa: BLE001 - report failures as cell results
        result = {"label": label, "status": "error",
                  "error": f"{type(e).__name__}: {e}",
                  "traceback": traceback.format_exc()[-2000:]}
        print(f"[FAIL] {label}: {type(e).__name__}: {e}")

    os.makedirs(outdir, exist_ok=True)
    with open(path, "w") as f:
        json.dump(result, f, indent=2)
    return result


def main() -> None:
    from ..configs import ALL_ARCHS, shapes_for

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--outdir", default="experiments/dryrun")
    ap.add_argument("--analyze", action="store_true",
                    help="run LEO root-cause analysis per cell")
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--hw", default="tpu_v5e")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--flags", default="",
                    help="model flags (models/flags.py), e.g. "
                         "attention_impl=pallas_fused,"
                         "ssm_impl=pallas_fused,moe_impl=ep_shardmap")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="dump the analysis-cache/latency metrics "
                         "(Prometheus text format) to PATH after the sweep")
    args = ap.parse_args()
    use_host_devices()
    model_flags = _parse_flags(args.flags)

    archs = [c.name for c in ALL_ARCHS] if args.arch == "all" \
        else args.arch.split(",")
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    failures = 0
    for arch in archs:
        from ..configs import get_config
        cfg = get_config(arch)
        shape_names = [s.name for s in shapes_for(cfg)] + (
            ["long_500k"] if not cfg.supports_long_context else [])
        if args.shape != "all":
            shape_names = args.shape.split(",")
        for shape_name in shape_names:
            for mesh_kind in meshes:
                r = run_cell(arch, shape_name, mesh_kind, args.outdir,
                             analyze=args.analyze, save_hlo=args.save_hlo,
                             hw_name=args.hw, force=args.force,
                             model_flags=model_flags)
                if r.get("status") == "error":
                    failures += 1
    if args.metrics_out:
        with open(args.metrics_out, "w") as f:
            f.write(get_metrics().render())
        print(f"wrote metrics to {args.metrics_out}")
    print(f"\ndry-run complete; {failures} failures")
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
