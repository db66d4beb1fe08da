"""Plain float32 reference of the decoder language models the benchmark trains.

It imports nothing of the program under test.  It follows the equations of
the model as the repository defines it (see each configuration's
`departures`): pre-norm blocks of RMSNorm, a mixer and a SwiGLU MLP; the
mixer is causal grouped-query attention with rotary positions (optionally
a sliding window), or, for the hybrid blocks, the mean of that attention
and a selective state-space scan.  Parameters are made from the seed by
the same recipe the program's initialiser follows, stored in the served
dtype the configuration states, and trained with AdamW on a float32 master
copy, global-norm clipping and linear-warmup cosine decay.

Everything here is computed in float32 at `highest` matmul precision, one
layer and one block of rows at a time so that it fits beside nothing else
on one chip.  Given several devices, it lays its state and its batches over
all of them, each array split along its largest axis that divides evenly,
and lets `jax.jit` partition the same code.  `precision="fp8"` computes
every matrix product on float8 operands, one scale per tensor: e4m3
forward, e5m2 for the cotangents.  That is the control, which has to come
out as not correct.
"""
from __future__ import annotations

import math
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

F32 = jnp.float32
NEG_INF = -1e30
ADAM = {"lr": 3e-4, "b1": 0.9, "b2": 0.95, "eps": 1e-8, "weight_decay": 0.1}
SCHEDULE = {"warmup": 100, "total": 10_000, "final_frac": 0.1}
CLIP_NORM = 1.0
QUERY_BLOCK = 1024  # rows of attention scores held at once


def _dtype(name: str):
    return {"bfloat16": jnp.bfloat16, "float32": F32}[name]


# -- parameters ---------------------------------------------------------------

def _normal(key, shape, scale, dtype):
    return (scale * jax.random.normal(key, shape)).astype(dtype)


def _block_params(key, cfg: dict) -> Dict:
    d, dt = cfg["d_model"], _dtype(cfg["dtype"])
    h, kv, hd = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    ks = jax.random.split(key, 4)
    a = jax.random.split(ks[0], 4)
    attn = {"wq": _normal(a[0], (d, h * hd), 0.02, dt),
            "wk": _normal(a[1], (d, kv * hd), 0.02, dt),
            "wv": _normal(a[2], (d, kv * hd), 0.02, dt),
            "wo": _normal(a[3], (h * hd, d), 0.02, dt)}
    if cfg["qkv_bias"]:
        attn.update(bq=jnp.zeros((h * hd,), dt), bk=jnp.zeros((kv * hd,), dt),
                    bv=jnp.zeros((kv * hd,), dt))
    m = jax.random.split(ks[1], 3)
    p = {"ln1": jnp.ones((d,), dt), "attn": attn, "ln2": jnp.ones((d,), dt),
         "ffn": {"w_gate": _normal(m[0], (d, cfg["d_ff"]), 0.02, dt),
                 "w_up": _normal(m[1], (d, cfg["d_ff"]), 0.02, dt),
                 "w_down": _normal(m[2], (cfg["d_ff"], d), 0.02, dt)}}
    if cfg["family"] == "hybrid":
        din, n = cfg["ssm_expand"] * d, cfg["ssm_state"]
        s = jax.random.split(ks[3], 6)
        p["ssm"] = {
            "w_in": _normal(s[0], (d, 2 * din), 0.02, dt),
            "w_b": _normal(s[1], (din, n), 0.02, dt),
            "w_c": _normal(s[2], (din, n), 0.02, dt),
            "w_dt": _normal(s[3], (din,), 1.0, F32),
            "a_log": jnp.tile(jnp.log(jnp.arange(1, n + 1, dtype=F32)),
                              (din, 1)),
            "d_skip": jnp.ones((din,), F32),
            "w_out": _normal(s[5], (din, d), 0.02, dt)}
    return p


def init_params(key, cfg: dict) -> Dict:
    """Parameters in their served dtypes, layers stacked on a leading axis."""
    if cfg["family"] not in ("dense", "hybrid"):
        raise ValueError(f"no reference for family {cfg['family']!r}")
    dt = _dtype(cfg["dtype"])
    keys = jax.random.split(key, 4)   # 3 + one group of identical layers
    layer_keys = jax.random.split(keys[2], cfg["n_layers"])
    layers = [_block_params(k, cfg) for k in layer_keys]
    params = {"embed": {"table": _normal(keys[0], (cfg["vocab_size"],
                                                   cfg["d_model"]), 1.0, dt)},
              "groups": [jax.tree.map(lambda *x: jnp.stack(x), *layers)],
              "final_norm": jnp.ones((cfg["d_model"],), dt)}
    if not cfg["tie_embeddings"]:
        params["head"] = _normal(keys[1], (cfg["d_model"], cfg["vocab_size"]),
                                 0.02, dt)
    return params


# -- forward --------------------------------------------------------------------

def _round_fp8(x, dtype):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / float(jnp.finfo(dtype).max)
    return (x / scale).astype(dtype).astype(F32) * scale


@jax.custom_vjp
def _fp8_cotangent(y):
    return y


def _fp8_cotangent_fwd(y):
    return y, None


def _fp8_cotangent_bwd(_, g):
    return (_round_fp8(g, jnp.float8_e5m2),)


_fp8_cotangent.defvjp(_fp8_cotangent_fwd, _fp8_cotangent_bwd)


def _mm(spec: str, a, b, precision: str):
    """A matrix product.  In fp8 the forward operands are rounded to e4m3 and
    the cotangent of the product to e5m2, so that the backward products run
    on float8 operands too."""
    if precision == "fp8":
        a = a + jax.lax.stop_gradient(_round_fp8(a, jnp.float8_e4m3fn) - a)
        b = b + jax.lax.stop_gradient(_round_fp8(b, jnp.float8_e4m3fn) - b)
        return _fp8_cotangent(jnp.einsum(spec, a, b,
                                         precision=jax.lax.Precision.HIGHEST))
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, positions, theta):
    half = x.shape[-1] // 2
    freqs = 1.0 / theta ** (jnp.arange(half, dtype=F32) / half)
    ang = positions.astype(F32)[:, None] * freqs          # (S, half)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(p, x, cfg: dict, precision: str):
    b, s, _ = x.shape
    h, kv, hd = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    pos = jnp.arange(s)
    q = _mm("bsd,df->bsf", x, p["wq"], precision)
    k = _mm("bsd,df->bsf", x, p["wk"], precision)
    v = _mm("bsd,df->bsf", x, p["wv"], precision)
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = _rope(q.reshape(b, s, h, hd), pos, cfg["rope_theta"])
    k = _rope(k.reshape(b, s, kv, hd), pos, cfg["rope_theta"])
    v = v.reshape(b, s, kv, hd)
    k = jnp.repeat(k, h // kv, axis=2)   # query head i reads kv head i // g
    v = jnp.repeat(v, h // kv, axis=2)
    window = cfg["window"] if cfg["attention"] == "swa" else None
    outs = []
    for lo in range(0, s, QUERY_BLOCK):
        rows = pos[lo:lo + QUERY_BLOCK]
        sc = _mm("bqhd,bkhd->bhqk", q[:, lo:lo + QUERY_BLOCK], k,
                 precision) / math.sqrt(hd)
        mask = pos[None, :] <= rows[:, None]
        if window is not None:
            mask &= pos[None, :] > rows[:, None] - window
        probs = jax.nn.softmax(jnp.where(mask, sc, NEG_INF), axis=-1)
        outs.append(_mm("bhqk,bkhd->bqhd", probs, v, precision))
    out = jnp.concatenate(outs, axis=1).reshape(b, s, h * hd)
    return _mm("bsf,fd->bsd", out, p["wo"], precision)


def _ssm(p, x, cfg: dict, precision: str):
    """h_t = exp(-exp(a_log) dt_t) h_{t-1} + dt_t x_t B_t;  y_t = C_t . h_t,
    one position after another."""
    din = cfg["ssm_expand"] * cfg["d_model"]
    xz = _mm("bsd,df->bsf", x, p["w_in"], precision)
    xin, z = xz[..., :din], xz[..., din:]
    dt = jax.nn.softplus(xin * p["w_dt"])                      # (B, S, din)
    bsel = _mm("bsf,fn->bsn", xin, p["w_b"], precision)
    csel = _mm("bsf,fn->bsn", xin, p["w_c"], precision)
    a_rate = -jnp.exp(p["a_log"])                              # (din, N)

    def step(hstate, inp):
        dt_t, u_t, b_t, c_t = inp
        hstate = (jnp.exp(a_rate * dt_t[..., None]) * hstate
                  + (dt_t * u_t)[..., None] * b_t[:, None, :])
        return hstate, jnp.einsum("bfn,bn->bf", hstate, c_t,
                                  precision=jax.lax.Precision.HIGHEST)

    h0 = jnp.zeros((x.shape[0], din, cfg["ssm_state"]), F32)
    seq = [jnp.moveaxis(t, 1, 0) for t in (dt, xin, bsel, csel)]
    _, ys = jax.lax.scan(step, h0, seq)
    y = jnp.moveaxis(ys, 0, 1) + xin * p["d_skip"]
    y = y * jax.nn.silu(z)
    return _mm("bsf,fd->bsd", y, p["w_out"], precision)


def _block(p, x, cfg: dict, precision: str):
    h = _rmsnorm(x, p["ln1"], cfg["norm_eps"])
    y = _attention(p["attn"], h, cfg, precision)
    if cfg["family"] == "hybrid":
        y = 0.5 * (y + _ssm(p["ssm"], h, cfg, precision))
    x = x + y
    h = _rmsnorm(x, p["ln2"], cfg["norm_eps"])
    f = p["ffn"]
    g = jax.nn.silu(_mm("bsd,df->bsf", h, f["w_gate"], precision))
    u = _mm("bsd,df->bsf", h, f["w_up"], precision)
    return x + _mm("bsf,fd->bsd", g * u, f["w_down"], precision)


def loss(params, tokens, labels, cfg: dict, precision: str = "f32"):
    """Mean next-token cross-entropy over the batch, every layer recomputed in
    the backward pass and the output head taken one row at a time."""
    x = params["embed"]["table"][tokens]
    block = jax.checkpoint(lambda x, p: _block(p, x, cfg, precision))
    x, _ = jax.lax.scan(lambda x, p: (block(x, p), None), x,
                        params["groups"][0])
    x = _rmsnorm(x, params["final_norm"], cfg["norm_eps"])
    if cfg["tie_embeddings"]:
        head, spec = params["embed"]["table"], "sd,vd->sv"
    else:
        head, spec = params["head"], "sd,dv->sv"

    @jax.checkpoint
    def row_nll(xr, lr):
        logp = jax.nn.log_softmax(_mm(spec, xr, head, precision), axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, lr[:, None], -1))

    total = sum(row_nll(x[i], labels[i]) for i in range(x.shape[0]))
    return total / labels.size


# -- training -------------------------------------------------------------------

def lr_at(step: int) -> float:
    sch = SCHEDULE
    warm = min(step / sch["warmup"], 1.0)
    frac = min(max(step - sch["warmup"], 0) / (sch["total"] - sch["warmup"]),
               1.0)
    cos = 0.5 * (1.0 + math.cos(math.pi * frac))
    return ADAM["lr"] * warm * (sch["final_frac"]
                                + (1 - sch["final_frac"]) * cos)


def init_state(key, cfg: dict) -> Dict:
    served = init_params(key, cfg)
    master = jax.tree.map(lambda p: p.astype(F32), served)
    zeros = jax.tree.map(jnp.zeros_like, master)
    return {"master": master, "mu": zeros, "nu": zeros,
            "count": jnp.zeros((), jnp.int32)}


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


def spread(shape, mesh: Mesh) -> NamedSharding:
    """An array of `shape` split over every device of the one-axis `mesh`
    along its largest axis that divides evenly; replicated where none
    does."""
    even = [i for i, n in enumerate(shape) if n % mesh.size == 0]
    spec = [None] * len(shape)
    if even:
        spec[max(even, key=lambda i: shape[i])] = mesh.axis_names[0]
    return NamedSharding(mesh, PartitionSpec(*spec))


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
