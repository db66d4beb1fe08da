"""Pure-jnp oracles for every Pallas kernel (the allclose ground truth)."""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp


def flash_attention_ref(q, k, v, *, causal: bool = True,
                        window: Optional[int] = None) -> jnp.ndarray:
    """Naive full-matrix attention. q (B,S,H,hd), k/v (B,S,Kv,hd)."""
    b, s, h, hd = q.shape
    kv_heads = k.shape[2]
    groups = h // kv_heads
    if groups > 1:
        k = jnp.repeat(k, groups, axis=2)
        v = jnp.repeat(v, groups, axis=2)
    scale = 1.0 / math.sqrt(hd)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    qpos = jnp.arange(s)[:, None]
    kpos = jnp.arange(s)[None, :]
    mask = jnp.ones((s, s), bool)
    if causal:
        mask = kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    scores = jnp.where(mask, scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


def rmsnorm_ref(x, scale, *, eps: float = 1e-5) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) *
            scale.astype(jnp.float32)).astype(x.dtype)


def mlstm_ref(q, k, v, log_i, log_f) -> jnp.ndarray:
    """Step-by-step stabilized mLSTM recurrence (exact, O(S) sequential).

    q/k/v (B,S,H,hd); gates (B,S,H) log-space pre-activations."""
    b, s, h, hd = q.shape

    def step(carry, xs):
        c, n, m = carry
        qt, kt, vt, li, lf = xs
        qt = qt.astype(jnp.float32)
        kt = kt.astype(jnp.float32)
        vt = vt.astype(jnp.float32)
        m_new = jnp.maximum(lf + m, li)
        i_w = jnp.exp(li - m_new)
        f_w = jnp.exp(lf + m - m_new)
        c = c * f_w[..., None, None] + jnp.einsum(
            "bhd,bhe,bh->bhde", kt, vt, i_w)
        n = n * f_w[..., None] + kt * i_w[..., None]
        num = jnp.einsum("bhd,bhde->bhe", qt, c)
        den = jnp.maximum(jnp.abs(jnp.einsum("bhd,bhd->bh", qt, n)),
                          jnp.exp(-m_new))
        y = num / den[..., None]
        return (c, n, m_new), y

    init = (jnp.zeros((b, h, hd, hd), jnp.float32),
            jnp.zeros((b, h, hd), jnp.float32),
            jnp.full((b, h), -1e30, jnp.float32))
    xs = (jnp.moveaxis(q, 1, 0), jnp.moveaxis(k, 1, 0),
          jnp.moveaxis(v, 1, 0), jnp.moveaxis(log_i.astype(jnp.float32),
                                              1, 0),
          jnp.moveaxis(log_f.astype(jnp.float32), 1, 0))
    _, ys = jax.lax.scan(step, init, xs)
    return jnp.moveaxis(ys, 0, 1).astype(q.dtype)


def selective_scan_ref(dt, x, b_sel, c_sel, a_rate) -> jnp.ndarray:
    """Exact sequential h_t = exp(A dt_t) * h_{t-1} + dt_t x_t B_t;
    y_t = C_t . h_t, in f32.  dt/x (B,S,din), b_sel/c_sel (B,S,N),
    a_rate (din,N)."""
    def step(h, xs):
        dt_t, x_t, b_t, c_t = xs
        h = jnp.exp(a_rate * dt_t[..., None]) * h + \
            (dt_t * x_t)[..., None] * b_t[:, None, :]
        return h, jnp.einsum("bdn,bn->bd", h, c_t)

    b, s, din = dt.shape
    h0 = jnp.zeros((b, din, a_rate.shape[-1]), jnp.float32)
    _, ys = jax.lax.scan(step, h0, tuple(
        jnp.moveaxis(v.astype(jnp.float32), 1, 0)
        for v in (dt, x, b_sel, c_sel)))
    return jnp.moveaxis(ys, 0, 1)


def slstm_scan_ref(xg, r) -> "jnp.ndarray":
    """Exact sequential sLSTM recurrence. xg (B,S,4D); r (D,4D)."""
    b, s_len, d4 = xg.shape
    d = d4 // 4

    def step(carry, xg_t):
        c, n, h, m = carry
        g = xg_t.astype(jnp.float32) + h @ r.astype(jnp.float32)
        gi, gf = g[:, :d], g[:, d:2 * d]
        gz, go = g[:, 2 * d:3 * d], g[:, 3 * d:]
        log_f = jax.nn.log_sigmoid(gf)
        m_new = jnp.maximum(log_f + m, gi)
        i_w = jnp.exp(gi - m_new)
        f_w = jnp.exp(log_f + m - m_new)
        c = f_w * c + i_w * jnp.tanh(gz)
        n = f_w * n + i_w
        h = jax.nn.sigmoid(go) * c / jnp.maximum(n, 1.0)
        return (c, n, h, m_new), h

    z = jnp.zeros((b, d), jnp.float32)
    init = (z, z, z, jnp.full((b, d), -1e30, jnp.float32))
    _, hs = jax.lax.scan(step, init, jnp.moveaxis(xg, 1, 0))
    return jnp.moveaxis(hs, 0, 1).astype(xg.dtype)
