"""Device time of the traced window by the train step's named scopes.

The program records the train step it traced last and its arguments'
shapes (`repro.runtime.steps.last_traced_train_step`).  Lowered again as
`bench/drivers/train.py` jits it, with the state donated, and compiled
afresh, it gives the program that ran; its HLO, parsed by LEO's own parser,
names the instruction behind each device event of the trace.
`repro.core.cct.seconds_by_scope` folds the events' seconds into the
model's scopes (`repro.models.scopes.MODEL_SCOPES`), counting leaf
instructions only: a `while` event encloses its body's events.

One chip only: the recorded shapes carry no sharding, so a step sharded
over several chips would be lowered unsharded, and its instruction names
would not be those of the trace.  On more chips the metrics are left out.

A program that records no step (one from before the scopes) gives None,
and the metrics that read it are left out of the result.  Each attribution
writes one `scopes:` line to standard error: per scope the chip-us per
token, the share of busy time and LEO's modelled FLOPs and bytes per step
(`repro.core.cct.cost_by_scope`), and the seconds the attribution took.
"""
from __future__ import annotations

import json
import math
import sys
import time

_KEY = "scope_seconds"


def compiled_text(fn, args) -> str:
    """HLO text of `fn` compiled at `args` as `bench/drivers/train.py` jits
    it, with the op_name metadata of this lowering.

    Compiled with every cache off: JAX's persistent cache key leaves debug
    information out, so a cached executable may come from a build of the
    same program with other scopes, or none."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.clear_caches()
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return jax.jit(fn, donate_argnums=(0,)).lower(*args).compile() \
            .as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
        compilation_cache.reset_cache()


def _attribute(run):
    trace = run["trace"]
    if not trace or trace["devices"] != 1:
        return None
    from repro.runtime import steps
    traced = getattr(steps, "last_traced_train_step", lambda: None)()
    if traced is None:
        return None
    from repro.core.cct import cost_by_scope, seconds_by_scope
    from repro.core.hlo_parser import parse_hlo
    from repro.models.scopes import MODEL_SCOPES
    t0 = time.perf_counter()
    text = compiled_text(*traced)
    t1 = time.perf_counter()
    module = parse_hlo(text)
    t2 = time.perf_counter()
    found = seconds_by_scope(module, trace["ops"], MODEL_SCOPES)
    t3 = time.perf_counter()
    cost = cost_by_scope(module, MODEL_SCOPES)
    tokens = run["tokens"]
    step_tokens = math.prod(traced[1][1]["tokens"].shape)
    table = {}
    for scope, sec in dict(found.by_scope, none=found.unattributed).items():
        flops, nbytes = cost.get(None if scope == "none" else scope, (0, 0))
        table[scope] = {"us_per_token": sec * 1e6 / tokens,
                        "busy_share": sec / trace["busy_s"],
                        "leo_gflop_per_step": flops / 1e9,
                        "leo_gb_per_step": nbytes / 1e9}
    print("scopes: " + json.dumps({
        "steps": tokens / step_tokens, "leaf_s": found.leaf,
        "enclosing_s": found.enclosing, "unmatched_s": found.unmatched,
        "compile_s": t1 - t0, "parse_s": t2 - t1, "fold_s": t3 - t2,
        "by_scope": table}), file=sys.stderr)
    return found


def attribution(run):
    """The run's `ScopeSeconds`, computed once per run, or None."""
    if _KEY not in run:
        run[_KEY] = _attribute(run)
    return run[_KEY]


def us_per_token(run, scope: str):
    """Chip-microseconds of leaf device time in `scope` per trained token;
    None where the scope ran nothing."""
    found = attribution(run)
    if found is None or scope not in found.by_scope or not run["tokens"]:
        return None
    return found.by_scope[scope] * 1e6 / run["tokens"]
