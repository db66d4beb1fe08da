"""Plain float32 reference of GLM-4's decoder, as the benchmark trains it.

Nothing it computes comes from the program under test (it reads only the
names of the program's configuration fields, to refuse a program that
cannot state this model).  It follows GLM-4's equations (arXiv:2406.12793;
THUDM/glm-4-9b `config.json` and `modeling_chatglm.py`): pre-norm blocks of RMSNorm with the configuration's
eps, causal grouped-query attention with a bias on the query, key and
value projections, and a SwiGLU MLP; an untied output head.  Rotary
positions turn only the first `rotary_dim` = head_dim x
`partial_rotary_factor` dims of each query and key head, as adjacent pairs
(2i, 2i + 1) at the rate theta ** (-2i / rotary_dim); the other dims pass
through unturned.

Parameters come from the seed by the recipe `bench/reference/lm.py`
follows, the program initialiser's, and so do AdamW on a float32 master
copy, global-norm clipping, the learning-rate schedule, the layout over
several devices and the float8 control (`precision="fp8"`).  Everything is
computed in float32 at `highest` matmul precision, one layer and one block
of rows at a time.
"""
from __future__ import annotations

import math
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from bench.reference.lm import (ADAM, CLIP_NORM, F32, NEG_INF, QUERY_BLOCK,
                                init_params, init_state, lr_at, _mm,
                                _rmsnorm, spread)


def _check_program_states_the_rotary():
    """Stop, before anything compiles, a run of a program whose schema has
    no field for GLM's rotary: `bench/drivers/train.arch_config` drops keys
    the schema lacks, so that program would train full-head half-split
    rotary under this configuration's name.  Only the schema's field names
    are read; nothing this module computes comes from the program."""
    import dataclasses
    import importlib
    schema = importlib.import_module("repro.configs.base").ArchConfig
    missing = ({"partial_rotary_factor", "rope_interleave"}
               - {f.name for f in dataclasses.fields(schema)})
    if missing:
        raise SystemExit(f"bench.reference.glm4: the program's ArchConfig "
                         f"has no {sorted(missing)}; it cannot run GLM-4")


_check_program_states_the_rotary()


def _rope(x, positions, theta, rotary_dim):
    """Turn dims (2i, 2i + 1) of each head's first `rotary_dim` by the angle
    position x theta ** (-2i / rotary_dim); pass the rest through."""
    pairs = rotary_dim // 2
    freqs = 1.0 / theta ** (jnp.arange(0, rotary_dim, 2, dtype=F32)
                            / rotary_dim)
    ang = positions.astype(F32)[:, None] * freqs           # (S, pairs)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    turned = x[..., :rotary_dim].reshape(*x.shape[:-1], pairs, 2)
    x0, x1 = turned[..., 0], turned[..., 1]
    turned = jnp.stack([x0 * cos - x1 * sin, x1 * cos + x0 * sin], -1)
    return jnp.concatenate([turned.reshape(*x.shape[:-1], rotary_dim),
                            x[..., rotary_dim:]], -1)


def _attention(p, x, cfg: dict, precision: str):
    b, s, _ = x.shape
    h, kv, hd = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    rotary_dim = int(hd * cfg["partial_rotary_factor"])
    pos = jnp.arange(s)
    q = _mm("bsd,df->bsf", x, p["wq"], precision) + p["bq"]
    k = _mm("bsd,df->bsf", x, p["wk"], precision) + p["bk"]
    v = _mm("bsd,df->bsf", x, p["wv"], precision) + p["bv"]
    q = _rope(q.reshape(b, s, h, hd), pos, cfg["rope_theta"], rotary_dim)
    k = _rope(k.reshape(b, s, kv, hd), pos, cfg["rope_theta"], rotary_dim)
    v = v.reshape(b, s, kv, hd)
    k = jnp.repeat(k, h // kv, axis=2)   # query head i reads kv head i // g
    v = jnp.repeat(v, h // kv, axis=2)
    outs = []
    for lo in range(0, s, QUERY_BLOCK):
        rows = pos[lo:lo + QUERY_BLOCK]
        sc = _mm("bqhd,bkhd->bhqk", q[:, lo:lo + QUERY_BLOCK], k,
                 precision) / math.sqrt(hd)
        mask = pos[None, :] <= rows[:, None]
        probs = jax.nn.softmax(jnp.where(mask, sc, NEG_INF), axis=-1)
        outs.append(_mm("bhqk,bkhd->bqhd", probs, v, precision))
    out = jnp.concatenate(outs, axis=1).reshape(b, s, h * hd)
    return _mm("bsf,fd->bsd", out, p["wo"], precision)


def _block(p, x, cfg: dict, precision: str):
    x = x + _attention(p["attn"], _rmsnorm(x, p["ln1"], cfg["norm_eps"]),
                       cfg, precision)
    h = _rmsnorm(x, p["ln2"], cfg["norm_eps"])
    f = p["ffn"]
    g = jax.nn.silu(_mm("bsd,df->bsf", h, f["w_gate"], precision))
    u = _mm("bsd,df->bsf", h, f["w_up"], precision)
    return x + _mm("bsf,fd->bsd", g * u, f["w_down"], precision)


def loss(params, tokens, labels, cfg: dict, precision: str = "f32"):
    """Mean next-token cross-entropy over the batch, every layer recomputed in
    the backward pass and the untied head taken one row at a time."""
    x = params["embed"]["table"][tokens]
    block = jax.checkpoint(lambda x, p: _block(p, x, cfg, precision))
    x, _ = jax.lax.scan(lambda x, p: (block(x, p), None), x,
                        params["groups"][0])
    x = _rmsnorm(x, params["final_norm"], cfg["norm_eps"])

    @jax.checkpoint
    def row_nll(xr, lr):
        logits = _mm("sd,dv->sv", xr, params["head"], precision)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, lr[:, None], -1))

    total = sum(row_nll(x[i], labels[i]) for i in range(x.shape[0]))
    return total / labels.size


def make_step(cfg: dict, dtypes, precision: str = "f32", layout=None):
    """(state, tokens, labels, lr) -> (state, loss, pre-clip grad norm,
    clipped gradient).  The forward reads the master copy rounded to the
    served dtype of each leaf.  `layout`, a tree of shardings like the
    state, keeps the state and the gradient laid out as the state is."""
    a = ADAM

    def step(state, tokens, labels, lr):
        served = jax.tree.map(lambda m, dt: m.astype(dt).astype(F32),
                              state["master"], dtypes)
        value, grads = jax.value_and_grad(loss)(served, tokens, labels, cfg,
                                                precision)
        gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
        grads = jax.tree.map(
            lambda g: g * jnp.minimum(1.0, CLIP_NORM / jnp.maximum(gnorm,
                                                                   1e-12)),
            grads)
        count = state["count"] + 1
        b1c = 1.0 - a["b1"] ** count.astype(F32)
        b2c = 1.0 - a["b2"] ** count.astype(F32)
        mu = jax.tree.map(lambda m, g: a["b1"] * m + (1 - a["b1"]) * g,
                          state["mu"], grads)
        nu = jax.tree.map(lambda n, g: a["b2"] * n + (1 - a["b2"]) * g * g,
                          state["nu"], grads)
        master = jax.tree.map(
            lambda w, m, n: w - lr * ((m / b1c) / (jnp.sqrt(n / b2c) + a["eps"])
                                      + a["weight_decay"] * w),
            state["master"], mu, nu)
        new = {"master": master, "mu": mu, "nu": nu, "count": count}
        return new, value, gnorm, grads

    if layout is None:
        return jax.jit(step, donate_argnums=(0,))
    return jax.jit(step, donate_argnums=(0,),
                   out_shardings=(layout, None, None, layout["master"]))


def train(cfg: dict, key, batches: List[Tuple], norms,
          precision: str = "f32", devices=None) -> Dict:
    """The first len(batches) steps from the seed's parameters: each step's
    loss and pre-clip gradient norm, and `norms` (a traceable function of a
    tree) of the first clipped gradient and of the master weights' change,
    as the device arrays it returns.  On more than one of `devices` the
    state and the batches are laid out over all of them (`spread`)."""
    with jax.default_matmul_precision("highest"):
        dtypes = jax.tree.map(lambda p: p.dtype,
                              jax.eval_shape(lambda: init_params(key, cfg)))
        if devices is None or len(devices) == 1:
            layout = None
            state = jax.jit(lambda k: init_state(k, cfg))(key)
        else:
            mesh = Mesh(np.array(devices), ("devices",))
            layout = jax.tree.map(
                lambda a: spread(a.shape, mesh),
                jax.eval_shape(lambda: init_state(key, cfg)))
            state = jax.jit(lambda k: init_state(k, cfg),
                            out_shardings=layout)(key)
            batches = [tuple(jax.device_put(a, spread(a.shape, mesh))
                             for a in batch) for batch in batches]
        step = make_step(cfg, dtypes, precision, layout)
        losses, gnorms, first_grad = [], [], None
        for i, (tokens, labels) in enumerate(batches):
            state, value, gnorm, grads = step(state, tokens, labels,
                                              jnp.float32(lr_at(i)))
            losses.append(value)
            gnorms.append(gnorm)
            if first_grad is None:
                first_grad = jax.jit(norms)(grads)
            del grads

        def params_of(k):
            params = init_params(k, cfg)
            if layout is None:
                return params
            return jax.lax.with_sharding_constraint(params, layout["master"])
        change = jax.jit(lambda k, end: norms(jax.tree.map(
            lambda e, p: e - p.astype(F32), end, params_of(k))))
        change_norms = change(key, state["master"])
        return {"losses": [float(x) for x in losses],
                "grad_norms": [float(x) for x in gnorms],
                "first_grad": first_grad, "change": change_norms}
