"""Train / serve step builders (the programs the launcher lowers).

`make_train_step` returns a pure function
    (train_state, batch) -> (train_state, metrics)
with loss, global-norm clipping, lr schedule, and AdamW update.  Options:
activation remat policy, gradient-compression (error-feedback int8 for the
DP all-reduce), microbatch accumulation via `lax.scan`.

`make_serve_step` returns
    (params, decode_state, token, pos) -> (next_token, logits, decode_state)
one-token greedy decode against the KV cache / recurrent state.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig
from ..models import decode_step as model_decode_step
from ..models import init_decode_state, init_params, loss_fn, scopes
from ..optim import (
    AdamWConfig,
    adamw_init,
    adamw_update,
    clip_by_global_norm,
    compress_gradients,
    linear_warmup_cosine,
)


@dataclass(frozen=True)
class TrainOptions:
    remat: str = "group"          # none | group
    chunk: int = 512              # attention chunk size
    clip_norm: float = 1.0
    warmup_steps: int = 100
    total_steps: int = 10_000
    grad_compression: bool = False
    grad_dtype: str = "f32"       # "bf16" halves the DP all-reduce bytes
    microbatch: int = 1           # accumulation steps via lax.scan


def default_microbatch(cfg: ArchConfig, global_batch: int, seq_len: int,
                       dp_size: int, target_bytes: float = 2e9) -> int:
    """Gradient-accumulation factor keeping layer-boundary activations
    (the tensors kept live across the backward pass under per-group remat)
    around `target_bytes` per device: B/dp/mb * S * d * 2 bytes * L."""
    per_dev = max(1, global_batch // max(dp_size, 1))
    boundary = per_dev * seq_len * cfg.d_model * 2 * cfg.n_layers
    mb = 1
    while boundary / mb > target_bytes and mb < per_dev:
        mb *= 2
    return mb


def init_train_state(rng, cfg: ArchConfig) -> Dict[str, Any]:
    params = init_params(rng, cfg)
    return {"params": params, "opt": adamw_init(params),
            "step": jnp.zeros((), jnp.int32)}


# The train step traced last in this process, with its arguments' shapes.
_LAST_TRACED: Optional[Tuple[Callable, Tuple[Any, ...]]] = None


def _record_traced(fn: Callable, *args) -> None:
    """Keep `fn` and the abstract shapes of `args` (no arrays): the body of
    a jitted step runs only while it is traced."""
    global _LAST_TRACED

    def abstract(x):
        aval = jax.typeof(x)
        return jax.ShapeDtypeStruct(aval.shape, aval.dtype,
                                    weak_type=aval.weak_type)
    _LAST_TRACED = (fn, jax.tree.map(abstract, args))


def last_traced_train_step() -> Optional[Tuple[Callable, Tuple[Any, ...]]]:
    """`(train_step, (state, batch))` of the train step traced last in this
    process, its arguments as `jax.ShapeDtypeStruct`s, or None.

    Lowering `jax.jit(train_step, donate_argnums=(0,))` at those shapes
    again gives the compiled program that ran, whose instruction names a
    device profile of the step carries; `repro.core.cct.seconds_by_scope`
    then attributes the profile's time to the model's named scopes."""
    return _LAST_TRACED


def make_train_step(cfg: ArchConfig, opt_cfg: AdamWConfig = AdamWConfig(),
                    options: TrainOptions = TrainOptions()):
    def loss_of(params, batch):
        return loss_fn(params, cfg, batch, chunk=options.chunk,
                       remat=options.remat)

    def train_step(state: Dict[str, Any], batch: Dict[str, jnp.ndarray]
                   ) -> Tuple[Dict[str, Any], Dict[str, jnp.ndarray]]:
        _record_traced(train_step, state, batch)
        params = state["params"]
        if options.microbatch > 1:
            def split(x):
                b = x.shape[0]
                return x.reshape(options.microbatch,
                                 b // options.microbatch, *x.shape[1:])
            micro = jax.tree.map(split, batch)

            def body(acc, mb):
                loss, g = jax.value_and_grad(loss_of)(params, mb)
                return (acc[0] + loss,
                        jax.tree.map(jnp.add, acc[1], g)), None
            zeros = jax.tree.map(jnp.zeros_like, params)
            (loss_sum, gsum), _ = jax.lax.scan(
                body, (jnp.zeros(()), zeros), micro)
            loss = loss_sum / options.microbatch
            grads = jax.tree.map(lambda g: g / options.microbatch, gsum)
        else:
            loss, grads = jax.value_and_grad(loss_of)(params, batch)

        if options.grad_dtype == "bf16":
            # bf16 gradient all-reduce (Megatron-style): halves DP wire
            # bytes; the f32 master update re-upcasts afterwards.
            grads = jax.tree.map(
                lambda g: g.astype(jnp.bfloat16), grads)
        if options.grad_compression:
            ef = state.get("grad_ef")
            grads, new_ef = compress_gradients(grads, ef)
        with jax.named_scope(scopes.OPTIMIZER):
            grads, gnorm = clip_by_global_norm(grads, options.clip_norm)
            lr_scale = linear_warmup_cosine(
                state["step"], options.warmup_steps, options.total_steps)
            new_params, new_opt = adamw_update(opt_cfg, grads, state["opt"],
                                               params, lr_scale)
            new_state = {"params": new_params, "opt": new_opt,
                         "step": state["step"] + 1}
        if options.grad_compression:
            new_state["grad_ef"] = new_ef
        metrics = {"loss": loss, "grad_norm": gnorm,
                   "lr_scale": lr_scale}
        return new_state, metrics

    return train_step


def make_serve_step(cfg: ArchConfig, chunk: int = 512,
                    per_slot_pos: bool = False):
    """Single-token decode step.

    ``per_slot_pos=False`` (legacy): ``pos`` is a scalar shared by every
    batch row — fine when all slots advance in lockstep.  With
    ``per_slot_pos=True`` ``pos`` is a ``(B,)`` vector and each batch slot
    decodes at its own position (vmapped over the batch axis; per-slot KV
    writes lower to scatters), which is what continuous batching needs:
    a freed slot admits a new request at pos=0 while its neighbors keep
    decoding mid-stream.
    """
    if per_slot_pos:
        def one_slot(params, state, token, pos):
            # re-insert the batch axis (=1) that vmap strips, so the
            # model sees its normal (L, B, ...) state layout
            state_b = jax.tree.map(lambda l: l[:, None], state)
            logits, new_state = model_decode_step(
                params, state_b, cfg, token[None], pos)
            return logits[0], jax.tree.map(lambda l: l[:, 0], new_state)

        vstep = jax.vmap(one_slot, in_axes=(None, 1, 0, 0),
                         out_axes=(0, 1))

        def serve_step(params, state, token: jnp.ndarray,
                       pos: jnp.ndarray):
            logits, new_state = vstep(params, state, token, pos)
            next_token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return next_token, logits, new_state

        return serve_step

    def serve_step(params, state, token: jnp.ndarray, pos: jnp.ndarray):
        logits, new_state = model_decode_step(params, state, cfg, token, pos)
        next_token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return next_token, logits, new_state

    return serve_step


def make_prefill_step(cfg: ArchConfig, chunk: int = 512):
    """Full-sequence forward used for the prefill shapes (logits only —
    cache construction for generation lives in examples/serve_demo.py)."""
    from ..models import forward

    def prefill_step(params, batch):
        logits, _ = forward(params, cfg, tokens=batch.get("tokens"),
                            embeds=batch.get("embeds"), chunk=chunk,
                            remat="none")
        return logits

    return prefill_step
