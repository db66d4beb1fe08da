"""Mixture-of-Experts with capacity-based sort dispatch (GShard-style).

Routing: softmax top-k with renormalized gates.  Dispatch: tokens sorted by
expert id, ranked within expert (rank >= capacity drops, standard token
dropping), scattered into per-expert capacity buffers, processed with
batched per-expert matmuls, and combined back gate-weighted.  The (E, C, D)
buffers shard over the "model" axis (expert parallelism): XLA inserts the
all-to-all at the data->expert resharding boundary, which is exactly the
collective LEO should see in MoE cells.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig
from ..kernels.placement import step_mesh
from .layers import dense_init, init_mlp, linear, mlp

Params = Dict[str, jnp.ndarray]


def init_moe(key, cfg: ArchConfig, dtype) -> Params:
    d = cfg.d_model
    ff = cfg.moe_d_ff or cfg.d_ff
    e = cfg.n_experts
    ks = jax.random.split(key, 5)
    p: Params = {
        "router": dense_init(ks[0], (d, e), dtype=jnp.float32),
        "w_gate": dense_init(ks[1], (e, d, ff), dtype=dtype),
        "w_up": dense_init(ks[2], (e, d, ff), dtype=dtype),
        "w_down": dense_init(ks[3], (e, ff, d), dtype=dtype),
    }
    if cfg.mlp_kind != "swiglu":
        del p["w_gate"]
    if cfg.n_shared_experts:
        p["shared"] = init_mlp(ks[4], d, ff * cfg.n_shared_experts,
                               cfg.mlp_kind, dtype)
    return p


def _capacity(cfg: ArchConfig, n_tokens: int) -> int:
    c = int(cfg.capacity_factor * cfg.top_k * n_tokens / cfg.n_experts)
    return max(8, -(-c // 8) * 8)  # round up to 8


def moe_forward(p: Params, x: jnp.ndarray, cfg: ArchConfig
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x (B, S, D) -> (y (B, S, D), aux_loss scalar)."""
    b, s, d = x.shape
    t = b * s
    k = cfg.top_k
    e = cfg.n_experts
    cap = _capacity(cfg, t)
    xf = x.reshape(t, d)

    logits = linear(xf.astype(jnp.float32), p["router"])     # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_ids = jax.lax.top_k(probs, k)           # (T, k)
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9)

    # Load-balancing auxiliary loss (Switch-style).
    me = probs.mean(axis=0)
    ce = jnp.zeros((e,), jnp.float32).at[expert_ids.reshape(-1)].add(
        1.0 / (t * k))
    aux = e * jnp.sum(me * ce)

    # Sort-dispatch: flatten (T*k) assignments, sort by expert, rank.
    flat_e = expert_ids.reshape(-1)                           # (T*k,)
    flat_gate = gate_vals.reshape(-1)
    flat_tok = jnp.repeat(jnp.arange(t), k)
    order = jnp.argsort(flat_e)
    se, sg, st = flat_e[order], flat_gate[order], flat_tok[order]
    # rank within expert = position - first occurrence of that expert id
    first = jnp.searchsorted(se, se, side="left")
    rank = jnp.arange(t * k) - first
    keep = rank < cap
    dest = se * cap + jnp.minimum(rank, cap - 1)              # (T*k,)

    buf = jnp.zeros((e * cap, d), x.dtype)
    buf = buf.at[dest].add(jnp.where(keep[:, None], xf[st], 0))
    buf = buf.reshape(e, cap, d)

    # Per-expert FFN (batched over E -> expert-parallel shardable).
    if "w_gate" in p:
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf,
                                   p["w_gate"].astype(x.dtype)))
        h = h * jnp.einsum("ecd,edf->ecf", buf, p["w_up"].astype(x.dtype))
    else:
        h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", buf,
                                   p["w_up"].astype(x.dtype)))
    out_buf = jnp.einsum("ecf,efd->ecd", h, p["w_down"].astype(x.dtype))
    out_buf = out_buf.reshape(e * cap, d)

    # Combine: gather back and weight by gates.
    gathered = out_buf[dest] * jnp.where(keep, sg, 0.0)[:, None].astype(
        x.dtype)
    y = jnp.zeros((t, d), x.dtype).at[st].add(gathered)

    if "shared" in p:
        y = y + mlp(xf, p["shared"])
    return y.reshape(b, s, d), aux


# -- shard_map expert parallelism (the LEO-guided collective fix) ---------------

def _local_dispatch_compute(p: Params, xf: jnp.ndarray, cfg: ArchConfig,
                            tp_axis: str) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-shard body: local routing, all-to-all to expert shards, batched
    expert FFN with *stationary* weights, all-to-all back, local combine.

    xf: local tokens (T_l, D); expert weights in `p` are the local shard
    (E_local, D, ff).  Wire traffic per chip = 2 x the dispatch buffer
    (~capacity_factor * k * T_l * D bytes) instead of the global-sort /
    weight-gather collectives XLA derives from global-view routing.
    """
    t_l, d = xf.shape
    k = cfg.top_k
    e = cfg.n_experts
    tp = jax.lax.axis_size(tp_axis)
    e_local = e // tp
    cap = _capacity(cfg, t_l)

    logits = linear(xf.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_ids = jax.lax.top_k(probs, k)
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9)

    me = probs.mean(axis=0)
    ce = jnp.zeros((e,), jnp.float32).at[expert_ids.reshape(-1)].add(
        1.0 / (t_l * k))
    aux = e * jnp.sum(me * ce)

    flat_e = expert_ids.reshape(-1)
    flat_gate = gate_vals.reshape(-1)
    flat_tok = jnp.repeat(jnp.arange(t_l), k)
    order = jnp.argsort(flat_e)                    # local sort only
    se, sg, st = flat_e[order], flat_gate[order], flat_tok[order]
    first = jnp.searchsorted(se, se, side="left")
    rank = jnp.arange(t_l * k) - first
    keep = rank < cap
    dest = se * cap + jnp.minimum(rank, cap - 1)

    buf = jnp.zeros((e * cap, d), xf.dtype)
    buf = buf.at[dest].add(jnp.where(keep[:, None], xf[st], 0))
    buf = buf.reshape(e, cap, d)

    # dispatch: (E, C_l, D) -> (E_local, tp*C_l, D) on the owning shard
    buf = jax.lax.all_to_all(buf, tp_axis, split_axis=0, concat_axis=1,
                             tiled=True)

    if "w_gate" in p:
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf,
                                   p["w_gate"].astype(xf.dtype)))
        h = h * jnp.einsum("ecd,edf->ecf", buf, p["w_up"].astype(xf.dtype))
    else:
        h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", buf,
                                   p["w_up"].astype(xf.dtype)))
    out_buf = jnp.einsum("ecf,efd->ecd", h, p["w_down"].astype(xf.dtype))

    # return: (E_local, tp*C_l, D) -> (E, C_l, D) back on the token shards
    out_buf = jax.lax.all_to_all(out_buf, tp_axis, split_axis=1,
                                 concat_axis=0, tiled=True)
    out_buf = out_buf.reshape(e * cap, d)

    gathered = out_buf[dest] * jnp.where(keep, sg, 0.0)[:, None].astype(
        xf.dtype)
    y = jnp.zeros((t_l, d), xf.dtype).at[st].add(gathered)
    return y, aux


def moe_forward_ep(p: Params, x: jnp.ndarray, cfg: ArchConfig
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """shard_map expert-parallel MoE: routing stays shard-local, expert
    weights stay stationary, the only collectives are two all-to-alls along
    the "model" axis of the mesh `x`'s step runs over.  Falls back to the
    global path off a mesh, without a "model" axis, or where that axis does
    not divide the experts."""
    from jax.experimental.shard_map import shard_map
    from jax.sharding import PartitionSpec as P

    mesh = step_mesh(x)
    if "model" not in mesh.axis_names or \
            cfg.n_experts % mesh.shape["model"] != 0:
        return moe_forward(p, x, cfg)
    dp_axes = tuple(a for a in mesh.axis_names if a != "model")
    dp = dp_axes if len(dp_axes) > 1 else dp_axes[0]

    b, s, d = x.shape

    def body(p_local, x_local):
        bl = x_local.shape[0]
        y, aux = _local_dispatch_compute(
            p_local, x_local.reshape(bl * s, d), cfg, "model")
        if "shared" in p_local:
            y = y + mlp(x_local.reshape(bl * s, d), p_local["shared"])
        aux = jax.lax.pmean(aux, dp_axes)
        return y.reshape(bl, s, d), aux

    expert_spec = {
        "router": P(), "w_up": P("model", None, None),
        "w_down": P("model", None, None),
    }
    if "w_gate" in p:
        expert_spec["w_gate"] = P("model", None, None)
    if "shared" in p:
        expert_spec["shared"] = {k2: P() for k2 in p["shared"]}

    fn = shard_map(
        body, mesh=mesh,
        in_specs=(expert_spec, P(dp, None, None)),
        out_specs=(P(dp, None, None), P()),
        check_rep=False)
    return fn(p, x)
