"""Model assembly: per-layer blocks, grouped `lax.scan` stacks, train/decode.

Layers are described by (mixer, ffn) descriptors derived statically from the
config, run-length encoded into homogeneous *groups*; each group's params are
stacked with a leading `reps` axis and executed with `jax.lax.scan` — one
compiled body per group regardless of depth (critical for compile time at
62 layers) and the natural unit for activation rematerialization.

Supported mixers: attn (GQA full/SWA), mla, ssm (Mamba-style), hybrid
(parallel attn+SSM heads, Hymba-style), mlstm, slstm.  FFNs: mlp (SwiGLU or
GELU), moe (capacity dispatch), none.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig
from ..parallel.sharding import batch_over_data
from . import attention as attn_mod
from . import moe as moe_mod
from . import scopes
from . import ssm as ssm_mod
from . import xlstm as xlstm_mod
from .layers import (
    dense_init,
    embed,
    init_embed,
    init_mlp,
    linear,
    mlp,
    rmsnorm,
    unembed,
)

Params = Dict


# -- static layer plan -----------------------------------------------------------

def layer_descriptors(cfg: ArchConfig) -> List[Tuple[str, str]]:
    """Per-layer (mixer, ffn) descriptors."""
    out: List[Tuple[str, str]] = []
    for i, kind in enumerate(cfg.block_kinds):
        if kind in ("mlstm", "slstm"):
            out.append((kind, "none"))
            continue
        mixer = "hybrid" if kind == "hybrid" else (
            "mla" if cfg.attention == "mla" else
            ("ssm" if kind == "ssm" else "attn"))
        if cfg.n_experts > 0 and i >= cfg.first_dense_layers:
            ffn = "moe"
        elif cfg.d_ff > 0:
            ffn = "mlp"
        else:
            ffn = "none"
        out.append((mixer, ffn))
    return out


def layer_groups(cfg: ArchConfig) -> List[Tuple[Tuple[str, str], int]]:
    """Run-length encoded descriptors -> [(descriptor, reps)]."""
    descs = layer_descriptors(cfg)
    groups: List[Tuple[Tuple[str, str], int]] = []
    for d in descs:
        if groups and groups[-1][0] == d:
            groups[-1] = (d, groups[-1][1] + 1)
        else:
            groups.append((d, 1))
    return groups


def _dtype(cfg: ArchConfig):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


# -- init -------------------------------------------------------------------------

def _init_block(key, desc: Tuple[str, str], cfg: ArchConfig) -> Params:
    mixer, ffn = desc
    dtype = _dtype(cfg)
    d = cfg.d_model
    ks = jax.random.split(key, 4)
    p: Params = {"ln1": jnp.ones((d,), dtype)}
    if mixer == "attn":
        p["attn"] = attn_mod.init_attn(ks[0], cfg, dtype)
    elif mixer == "mla":
        p["attn"] = attn_mod.init_mla(ks[0], cfg, dtype)
    elif mixer == "ssm":
        p["ssm"] = ssm_mod.init_ssm(ks[0], cfg, dtype)
    elif mixer == "hybrid":
        p["attn"] = attn_mod.init_attn(ks[0], cfg, dtype)
        p["ssm"] = ssm_mod.init_ssm(ks[3], cfg, dtype)
    elif mixer == "mlstm":
        p["mlstm"] = xlstm_mod.init_mlstm(ks[0], cfg, dtype)
    elif mixer == "slstm":
        p["slstm"] = xlstm_mod.init_slstm(ks[0], cfg, dtype)
    if ffn != "none":
        p["ln2"] = jnp.ones((d,), dtype)
        if ffn == "moe":
            p["ffn"] = moe_mod.init_moe(ks[1], cfg, dtype)
        else:
            p["ffn"] = init_mlp(ks[1], d, cfg.d_ff, cfg.mlp_kind, dtype)
    return p


def init_params(rng, cfg: ArchConfig) -> Params:
    dtype = _dtype(cfg)
    keys = jax.random.split(rng, 3 + len(layer_groups(cfg)))
    params: Params = {"embed": init_embed(keys[0], cfg.vocab_size,
                                          cfg.d_model, dtype)}
    groups = []
    for gi, (desc, reps) in enumerate(layer_groups(cfg)):
        gkeys = jax.random.split(keys[2 + gi], reps)
        stacked = jax.vmap(lambda k: _init_block(k, desc, cfg))(gkeys)
        groups.append(stacked)
    params["groups"] = groups
    params["final_norm"] = jnp.ones((cfg.d_model,), dtype)
    if not cfg.tie_embeddings:
        params["head"] = dense_init(keys[1], (cfg.d_model, cfg.vocab_size),
                                    dtype=dtype)
    return params


# -- block forward -----------------------------------------------------------------

def _block_forward(p: Params, x: jnp.ndarray, desc: Tuple[str, str],
                   cfg: ArchConfig, positions: jnp.ndarray,
                   chunk: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Full-sequence block. Returns (x, aux_loss)."""
    mixer, ffn = desc
    aux = jnp.zeros((), jnp.float32)
    with jax.named_scope(scopes.NORM):
        h = rmsnorm(x, p["ln1"], cfg.norm_eps)
    if mixer == "hybrid":
        with jax.named_scope(scopes.ATTN):
            ya = attn_mod.attn_forward(p["attn"], h, cfg, positions, chunk)
        with jax.named_scope(scopes.SSM):
            ys = ssm_mod.ssm_forward(p["ssm"], h, cfg)
        y = 0.5 * (ya + ys)
    else:
        with jax.named_scope(scopes.KIND_SCOPES.get(mixer, mixer)):
            if mixer == "attn":
                y = attn_mod.attn_forward(p["attn"], h, cfg, positions, chunk)
            elif mixer == "mla":
                y = attn_mod.mla_forward(p["attn"], h, cfg, positions, chunk)
            elif mixer == "ssm":
                y = ssm_mod.ssm_forward(p["ssm"], h, cfg)
            elif mixer == "mlstm":
                y = xlstm_mod.mlstm_forward(p["mlstm"], h, cfg)
            elif mixer == "slstm":
                y = xlstm_mod.slstm_forward(p["slstm"], h, cfg)
            else:
                raise ValueError(mixer)
    x = x + y
    if ffn != "none":
        with jax.named_scope(scopes.NORM):
            h = rmsnorm(x, p["ln2"], cfg.norm_eps)
        with jax.named_scope(scopes.KIND_SCOPES[ffn]):
            if ffn == "moe":
                from .flags import get_flags
                if get_flags().moe_impl == "ep_shardmap":
                    y, aux = moe_mod.moe_forward_ep(p["ffn"], h, cfg)
                else:
                    y, aux = moe_mod.moe_forward(p["ffn"], h, cfg)
                # named for selective remat: saving the MoE output keeps the
                # backward from re-running dispatch all-to-alls + expert FFNs
                from jax.ad_checkpoint import checkpoint_name
                y = checkpoint_name(y, "moe_out")
            else:
                y = mlp(h, p["ffn"])
        x = x + y
    return x, aux


def forward(params: Params, cfg: ArchConfig,
            tokens: Optional[jnp.ndarray] = None,
            embeds: Optional[jnp.ndarray] = None,
            chunk: int = 512,
            remat: str = "group") -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Train/prefill forward. Returns (logits (B,S,V) f32, aux_loss)."""
    dtype = _dtype(cfg)
    with jax.named_scope(scopes.EMBED):
        if embeds is not None:
            x = embeds.astype(dtype)
        else:
            x = embed(tokens, params["embed"], dtype)
    s = x.shape[1]
    positions = jnp.arange(s)
    aux_total = jnp.zeros((), jnp.float32)

    for (desc, reps), stacked in zip(layer_groups(cfg), params["groups"]):
        def body(carry, layer_p, _desc=desc):
            xc, auxc = carry
            xn, aux = _block_forward(layer_p, batch_over_data(xc), _desc,
                                     cfg, positions, chunk)
            return (xn, auxc + aux), None

        if remat == "group_save_moe":
            body = jax.checkpoint(
                body, policy=jax.checkpoint_policies.save_only_these_names(
                    "moe_out"))
        elif remat in ("group", "full"):
            body = jax.checkpoint(body)
        (x, aux_total), _ = jax.lax.scan(body, (x, aux_total), stacked)

    with jax.named_scope(scopes.HEAD_LOSS):
        x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
        if cfg.tie_embeddings:
            logits = unembed(x, params["embed"]["table"], transpose=True)
        else:
            logits = unembed(x, params["head"], transpose=False)
        return logits.astype(jnp.float32), aux_total


# -- decode -------------------------------------------------------------------------

def init_decode_state(cfg: ArchConfig, batch: int, max_len: int) -> Params:
    """Per-group stacked decode state (KV caches / recurrent states)."""
    dtype = _dtype(cfg)

    def one(desc) -> Params:
        mixer, _ = desc
        st: Params = {}
        if mixer == "attn":
            st["kv"] = attn_mod.init_attn_cache(cfg, batch, max_len, dtype)
        elif mixer == "mla":
            st["kv"] = attn_mod.init_mla_cache(cfg, batch, max_len, dtype)
        elif mixer == "ssm":
            st["ssm"] = ssm_mod.init_ssm_state(cfg, batch)
        elif mixer == "hybrid":
            st["kv"] = attn_mod.init_attn_cache(cfg, batch, max_len, dtype)
            st["ssm"] = ssm_mod.init_ssm_state(cfg, batch)
        elif mixer == "mlstm":
            st["mlstm"] = xlstm_mod.init_mlstm_state(cfg, batch)
        elif mixer == "slstm":
            st["slstm"] = xlstm_mod.init_slstm_state(cfg, batch)
        return st

    groups = []
    for desc, reps in layer_groups(cfg):
        st = one(desc)
        groups.append(jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (reps,) + a.shape), st))
    return {"groups": groups}


def _slice_state(stack: Params, li: jnp.ndarray) -> Params:
    return jax.tree.map(
        lambda s: jax.lax.dynamic_index_in_dim(s, li, 0, keepdims=False),
        stack)


def _unslice_state(stack: Params, new_s: Params, li: jnp.ndarray) -> Params:
    return jax.tree.map(
        lambda st, ns: jax.lax.dynamic_update_index_in_dim(
            st, ns.astype(st.dtype), li, 0), stack, new_s)


def _block_decode(p: Params, stack: Params, x: jnp.ndarray,
                  desc: Tuple[str, str], cfg: ArchConfig, pos: jnp.ndarray,
                  li: jnp.ndarray) -> Tuple[jnp.ndarray, Params]:
    """One layer of decode against *group-stacked* state.

    KV caches stay stacked and receive single-token in-place writes
    (`layer_idx` path in attention); small recurrent states (SSM/xLSTM) are
    sliced out and written back whole — they are KBs, not GBs."""
    mixer, ffn = desc
    h = rmsnorm(x, p["ln1"], cfg.norm_eps)
    new_stack: Params = dict(stack)
    if mixer == "attn":
        y, new_stack["kv"] = attn_mod.attn_decode(
            p["attn"], h, stack["kv"], pos, cfg, layer_idx=li)
    elif mixer == "mla":
        y, new_stack["kv"] = attn_mod.mla_decode(
            p["attn"], h, stack["kv"], pos, cfg, layer_idx=li)
    elif mixer == "ssm":
        y, ns = ssm_mod.ssm_decode(p["ssm"], h,
                                   _slice_state(stack["ssm"], li), cfg)
        new_stack["ssm"] = _unslice_state(stack["ssm"], ns, li)
    elif mixer == "hybrid":
        ya, new_stack["kv"] = attn_mod.attn_decode(
            p["attn"], h, stack["kv"], pos, cfg, layer_idx=li)
        ys, ns = ssm_mod.ssm_decode(p["ssm"], h,
                                    _slice_state(stack["ssm"], li), cfg)
        new_stack["ssm"] = _unslice_state(stack["ssm"], ns, li)
        y = 0.5 * (ya + ys)
    elif mixer == "mlstm":
        y, ns = xlstm_mod.mlstm_decode(p["mlstm"], h,
                                       _slice_state(stack["mlstm"], li), cfg)
        new_stack["mlstm"] = _unslice_state(stack["mlstm"], ns, li)
    elif mixer == "slstm":
        y, ns = xlstm_mod.slstm_decode(p["slstm"], h,
                                       _slice_state(stack["slstm"], li), cfg)
        new_stack["slstm"] = _unslice_state(stack["slstm"], ns, li)
    else:
        raise ValueError(mixer)
    x = x + y
    if ffn != "none":
        h = rmsnorm(x, p["ln2"], cfg.norm_eps)
        if ffn == "moe":
            y, _ = moe_mod.moe_forward(p["ffn"], h[:, None], cfg)
            y = y[:, 0]
        else:
            y = mlp(h, p["ffn"])
        x = x + y
    return x, new_stack


def decode_step(params: Params, state: Params, cfg: ArchConfig,
                token: jnp.ndarray, pos: jnp.ndarray
                ) -> Tuple[jnp.ndarray, Params]:
    """One decode step. token (B,) int32; pos scalar int32.

    Returns (logits (B, V) f32, new state).  Group state stays stacked as
    the scan *carry* (not ys) so caches are updated in place."""
    dtype = _dtype(cfg)
    x = embed(token, params["embed"], dtype)
    new_groups = []
    for (desc, reps), stacked_p, stacked_s in zip(
            layer_groups(cfg), params["groups"], state["groups"]):
        def body(carry, inputs, _desc=desc):
            x_c, stack = carry
            layer_p, li = inputs
            xn, stack = _block_decode(layer_p, stack, x_c, _desc, cfg, pos,
                                      li)
            return (xn, stack), None

        (x, new_s), _ = jax.lax.scan(
            body, (x, stacked_s), (stacked_p, jnp.arange(reps)))
        new_groups.append(new_s)
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    if cfg.tie_embeddings:
        logits = unembed(x, params["embed"]["table"], transpose=True)
    else:
        logits = unembed(x, params["head"], transpose=False)
    return logits.astype(jnp.float32), {"groups": new_groups}


# -- loss ---------------------------------------------------------------------------

def loss_fn(params: Params, cfg: ArchConfig, batch: Dict,
            chunk: int = 512, remat: str = "group",
            aux_weight: float = 0.01) -> jnp.ndarray:
    logits, aux = forward(params, cfg,
                          tokens=batch.get("tokens"),
                          embeds=batch.get("embeds"),
                          chunk=chunk, remat=remat)
    with jax.named_scope(scopes.HEAD_LOSS):
        labels = batch["labels"]
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
        return nll.mean() + aux_weight * aux
