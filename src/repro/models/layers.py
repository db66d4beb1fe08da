"""Shared model layers (pure functional JAX, params as nested dicts)."""
from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp

Params = Dict[str, jnp.ndarray]


def dense_init(key, shape, scale: float = 0.02, dtype=jnp.float32):
    return (scale * jax.random.normal(key, shape)).astype(dtype)


def rmsnorm(x: jnp.ndarray, scale: jnp.ndarray, eps: float = 1e-5):
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)
            ).astype(dtype)


def linear(x: jnp.ndarray, w: jnp.ndarray,
           b: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    y = jnp.einsum("...d,df->...f", x, w.astype(x.dtype))
    if b is not None:
        y = y + b.astype(y.dtype)
    return y


# -- RoPE --------------------------------------------------------------------

def rope_cos_sin(positions: jnp.ndarray, rotary_dim: int, theta: float):
    """positions (...,) -> cos/sin (..., rotary_dim//2), f32: pair i turns
    at theta ** (-2i / rotary_dim)."""
    half = rotary_dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    angles = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray,
               interleave: bool = False):
    """x (..., n_heads, head_dim); cos/sin broadcastable (..., r//2).

    Rotates the first r dims of each head and passes the rest through.
    Pair i is dims (i, i + r/2) (half-split), or (2i, 2i + 1) with
    `interleave` (GLM's adjacent pairs)."""
    rot = 2 * cos.shape[-1]
    xr = x if rot == x.shape[-1] else x[..., :rot]
    if interleave:
        pairs = xr.reshape(*xr.shape[:-1], rot // 2, 2)
        x1, x2 = pairs[..., 0], pairs[..., 1]
    else:
        x1, x2 = xr[..., :rot // 2], xr[..., rot // 2:]
    cos = cos[..., None, :].astype(jnp.float32)
    sin = sin[..., None, :].astype(jnp.float32)
    x1f, x2f = x1.astype(jnp.float32), x2.astype(jnp.float32)
    y1, y2 = x1f * cos - x2f * sin, x2f * cos + x1f * sin
    if interleave:
        out = jnp.stack([y1, y2], axis=-1).reshape(xr.shape)
    else:
        out = jnp.concatenate([y1, y2], axis=-1)
    out = out.astype(x.dtype)
    return out if xr is x else jnp.concatenate([out, x[..., rot:]], axis=-1)


# -- MLPs ---------------------------------------------------------------------

def init_mlp(key, d_model: int, d_ff: int, kind: str = "swiglu",
             dtype=jnp.float32) -> Params:
    ks = jax.random.split(key, 3)
    if kind == "swiglu":
        return {"w_gate": dense_init(ks[0], (d_model, d_ff), dtype=dtype),
                "w_up": dense_init(ks[1], (d_model, d_ff), dtype=dtype),
                "w_down": dense_init(ks[2], (d_ff, d_model), dtype=dtype)}
    return {"w_up": dense_init(ks[0], (d_model, d_ff), dtype=dtype),
            "w_down": dense_init(ks[1], (d_ff, d_model), dtype=dtype)}


def mlp(x: jnp.ndarray, p: Params) -> jnp.ndarray:
    if "w_gate" in p:
        h = jax.nn.silu(linear(x, p["w_gate"])) * linear(x, p["w_up"])
    else:
        h = jax.nn.gelu(linear(x, p["w_up"]))
    return linear(h, p["w_down"])


# -- Embedding -----------------------------------------------------------------

def init_embed(key, vocab: int, d_model: int, dtype=jnp.float32) -> Params:
    return {"table": dense_init(key, (vocab, d_model), scale=1.0,
                                dtype=dtype)}


def embed(tokens: jnp.ndarray, p: Params, dtype) -> jnp.ndarray:
    return p["table"].astype(dtype)[tokens]


def unembed(x: jnp.ndarray, table_or_w: jnp.ndarray,
            transpose: bool) -> jnp.ndarray:
    w = table_or_w.astype(x.dtype)
    if transpose:  # tied embeddings: table (V, D)
        return jnp.einsum("...d,vd->...v", x, w)
    return jnp.einsum("...d,dv->...v", x, w)
