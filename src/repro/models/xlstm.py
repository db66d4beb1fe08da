"""xLSTM blocks: chunkwise-parallel mLSTM and sequential sLSTM.

mLSTM (matrix memory): per head, C_t = f_t C_{t-1} + i_t v_t k_t^T with
exponential gating stabilized by a running max m_t.  Training uses the
chunkwise decomposition — inter-chunk recurrence over the (hd x hd) matrix
state via `lax.scan`, intra-chunk contributions via masked gated attention —
so the S x S score matrix never materializes beyond a chunk.

sLSTM (scalar memory): strictly sequential exponential-gated recurrence per
head, `lax.scan` over time; the paper pairs it with a gated (4/3) FFN.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig
from .layers import dense_init, linear, rmsnorm

Params = Dict[str, jnp.ndarray]


# -- mLSTM ----------------------------------------------------------------------

def init_mlstm(key, cfg: ArchConfig, dtype) -> Params:
    d = cfg.d_model
    h, hd = cfg.n_heads, cfg.head_dim_
    din = h * hd
    ks = jax.random.split(key, 7)
    return {
        "w_up": dense_init(ks[0], (d, 2 * din), dtype=dtype),   # x and gate
        "wq": dense_init(ks[1], (din, h * hd), dtype=dtype),
        "wk": dense_init(ks[2], (din, h * hd), dtype=dtype),
        "wv": dense_init(ks[3], (din, h * hd), dtype=dtype),
        "w_if": dense_init(ks[4], (din, 2 * h), dtype=jnp.float32),
        "norm": jnp.ones((din,), dtype),
        "w_down": dense_init(ks[6], (din, d), dtype=dtype),
    }


def mlstm_forward(p: Params, x: jnp.ndarray, cfg: ArchConfig,
                  chunk: int = 128) -> jnp.ndarray:
    b, s, d = x.shape
    h, hd = cfg.n_heads, cfg.head_dim_
    din = h * hd
    up = linear(x, p["w_up"])
    xin, zgate = up[..., :din], up[..., din:]
    q = linear(xin, p["wq"]).reshape(b, s, h, hd)
    k = linear(xin, p["wk"]).reshape(b, s, h, hd) / (hd ** 0.5)
    v = linear(xin, p["wv"]).reshape(b, s, h, hd)
    gates = linear(xin, p["w_if"]).astype(jnp.float32)          # (B,S,2H)
    log_i = gates[..., :h]                                       # pre-act i
    log_f = jax.nn.log_sigmoid(gates[..., h:])                   # log f_t

    chunk = min(chunk, s)
    assert s % chunk == 0
    nc = s // chunk
    qc = q.reshape(b, nc, chunk, h, hd)
    kc = k.reshape(b, nc, chunk, h, hd)
    vc = v.reshape(b, nc, chunk, h, hd)
    lic = log_i.reshape(b, nc, chunk, h)
    lfc = log_f.reshape(b, nc, chunk, h)

    def chunk_step(carry, inputs):
        # Stabilized chunkwise recurrence.  Unstabilized math per target u:
        #   C_u = exp(F_u) * C_in + sum_{t<=u} exp(F_u - F_t + i_t) v_t k_t^T
        # with F_t = cumsum(log f).  Stabilizer M_u = max(m_in + F_u,
        # F_u + max_{t<=u}(i_t - F_t)) keeps every exp() <= 1.
        c_state, n_state, m_state = carry       # (B,H,hd,hd), (B,H,hd), (B,H)
        qk, kk, vk, li, lf = inputs
        qk = qk.astype(jnp.float32)
        kk = kk.astype(jnp.float32)
        vk = vk.astype(jnp.float32)
        f_cum = jnp.cumsum(lf, axis=1)                       # F_t  (B,C,H)
        f_tot = f_cum[:, -1]                                 # F_C  (B,H)
        s_t = li - f_cum                                     # i_t - F_t
        s_runmax = jax.lax.associative_scan(jnp.maximum, s_t, axis=1)
        m_u = jnp.maximum(m_state[:, None], s_runmax) + f_cum  # (B,U,H)

        idx = jnp.arange(qk.shape[1])
        causal = (idx[:, None] >= idx[None, :])[None, :, :, None]
        log_w = (f_cum[:, :, None, :] - f_cum[:, None, :, :] +
                 li[:, None, :, :] - m_u[:, :, None, :])     # (B,U,T,H)
        w = jnp.where(causal, jnp.exp(log_w), 0.0)
        qkt = jnp.einsum("buhd,bthd->buth", qk, kk)
        scores = qkt * w
        intra = jnp.einsum("buth,bthd->buhd", scores, vk)
        norm_intra = scores.sum(axis=2)                      # (B,U,H)

        d_u = jnp.exp(f_cum + m_state[:, None] - m_u)        # (B,U,H)
        inter = jnp.einsum("buhd,bhde->buhe", qk, c_state) * d_u[..., None]
        norm_inter = jnp.einsum("buhd,bhd->buh", qk, n_state) * d_u
        denom = jnp.maximum(jnp.abs(norm_inter + norm_intra),
                            jnp.exp(-m_u))
        y = (inter + intra) / denom[..., None]

        m_new = m_u[:, -1]
        carry_decay = jnp.exp(f_tot + m_state - m_new)       # (B,H)
        src_w = jnp.exp(li + (f_tot[:, None] - f_cum) - m_new[:, None])
        c_new = c_state * carry_decay[..., None, None] + jnp.einsum(
            "bthd,bthe,bth->bhde", kk, vk, src_w)
        n_new = n_state * carry_decay[..., None] + jnp.einsum(
            "bthd,bth->bhd", kk, src_w)
        return (c_new, n_new, m_new), y

    c0 = jnp.zeros((b, h, hd, hd), jnp.float32)
    n0 = jnp.zeros((b, h, hd), jnp.float32)
    m0 = jnp.full((b, h), -1e30, jnp.float32)
    xs = (jnp.moveaxis(qc, 1, 0), jnp.moveaxis(kc, 1, 0),
          jnp.moveaxis(vc, 1, 0), jnp.moveaxis(lic, 1, 0),
          jnp.moveaxis(lfc, 1, 0))
    (_, _, _), ys = jax.lax.scan(chunk_step, (c0, n0, m0), xs)
    y = jnp.moveaxis(ys, 0, 1).reshape(b, s, din).astype(x.dtype)
    y = rmsnorm(y, p["norm"], cfg.norm_eps)
    y = y * jax.nn.silu(zgate)
    return linear(y, p["w_down"])


def init_mlstm_state(cfg: ArchConfig, batch: int) -> Params:
    h, hd = cfg.n_heads, cfg.head_dim_
    return {"c": jnp.zeros((batch, h, hd, hd), jnp.float32),
            "n": jnp.zeros((batch, h, hd), jnp.float32),
            "m": jnp.full((batch, h), -1e30, jnp.float32)}


def mlstm_decode(p: Params, x: jnp.ndarray, state: Params, cfg: ArchConfig
                 ) -> Tuple[jnp.ndarray, Params]:
    b, d = x.shape
    h, hd = cfg.n_heads, cfg.head_dim_
    din = h * hd
    up = linear(x, p["w_up"])
    xin, zgate = up[..., :din], up[..., din:]
    q = linear(xin, p["wq"]).reshape(b, h, hd).astype(jnp.float32)
    k = (linear(xin, p["wk"]).reshape(b, h, hd) / (hd ** 0.5)).astype(
        jnp.float32)
    v = linear(xin, p["wv"]).reshape(b, h, hd).astype(jnp.float32)
    gates = linear(xin, p["w_if"]).astype(jnp.float32)
    log_i = gates[..., :h]
    log_f = jax.nn.log_sigmoid(gates[..., h:])
    m_new = jnp.maximum(log_f + state["m"], log_i)
    i_w = jnp.exp(log_i - m_new)
    f_w = jnp.exp(log_f + state["m"] - m_new)
    c = state["c"] * f_w[..., None, None] + \
        jnp.einsum("bhd,bhe,bh->bhde", k, v, i_w)
    n = state["n"] * f_w[..., None] + k * i_w[..., None]
    num = jnp.einsum("bhd,bhde->bhe", q, c)
    den = jnp.maximum(jnp.abs(jnp.einsum("bhd,bhd->bh", q, n)), 1.0)
    y = (num / den[..., None]).reshape(b, din).astype(x.dtype)
    y = rmsnorm(y, p["norm"], cfg.norm_eps)
    y = y * jax.nn.silu(zgate)
    return linear(y, p["w_down"]), {"c": c, "n": n, "m": m_new}


# -- sLSTM ----------------------------------------------------------------------

def init_slstm(key, cfg: ArchConfig, dtype) -> Params:
    d = cfg.d_model
    ks = jax.random.split(key, 4)
    ffd = int(d * 4 / 3)
    return {
        "w_gates": dense_init(ks[0], (d, 4 * d), dtype=dtype),  # i,f,z,o
        "r_gates": dense_init(ks[1], (d, 4 * d), scale=0.01, dtype=dtype),
        "ffn_gate": dense_init(ks[2], (d, ffd), dtype=dtype),
        "ffn_up": dense_init(ks[2], (d, ffd), dtype=dtype),
        "ffn_down": dense_init(ks[3], (ffd, d), dtype=dtype),
    }


def _slstm_cell(p: Params, xg: jnp.ndarray, state):
    """xg (B, 4D) precomputed input gates; state (c, n, h, m) each (B, D)."""
    c, n, hprev, m = state
    d = c.shape[-1]
    rec = linear(hprev, p["r_gates"]).astype(jnp.float32)
    g = xg.astype(jnp.float32) + rec
    gi, gf, gz, go = g[..., :d], g[..., d:2 * d], g[..., 2 * d:3 * d], \
        g[..., 3 * d:]
    log_f = jax.nn.log_sigmoid(gf)
    m_new = jnp.maximum(log_f + m, gi)
    i_w = jnp.exp(gi - m_new)
    f_w = jnp.exp(log_f + m - m_new)
    z = jnp.tanh(gz)
    c_new = f_w * c + i_w * z
    n_new = f_w * n + i_w
    h_new = jax.nn.sigmoid(go) * c_new / jnp.maximum(n_new, 1.0)
    return (c_new, n_new, h_new, m_new), h_new


def slstm_forward(p: Params, x: jnp.ndarray, cfg: ArchConfig) -> jnp.ndarray:
    b, s, d = x.shape
    xg = linear(x, p["w_gates"])                       # (B, S, 4D)

    def step(state, xg_t):
        return _slstm_cell(p, xg_t, state)

    init = (jnp.zeros((b, d), jnp.float32), jnp.zeros((b, d), jnp.float32),
            jnp.zeros((b, d), jnp.float32), jnp.full((b, d), -1e30,
                                                     jnp.float32))
    _, hs = jax.lax.scan(step, init, jnp.moveaxis(xg, 1, 0))
    y = jnp.moveaxis(hs, 0, 1).astype(x.dtype)
    # gated ffn (4/3)
    f = jax.nn.silu(linear(y, p["ffn_gate"])) * linear(y, p["ffn_up"])
    return linear(f, p["ffn_down"])


def init_slstm_state(cfg: ArchConfig, batch: int) -> Params:
    d = cfg.d_model
    z = jnp.zeros((batch, d), jnp.float32)
    return {"c": z, "n": z, "h": z, "m": jnp.full((batch, d), -1e30,
                                                  jnp.float32)}


def slstm_decode(p: Params, x: jnp.ndarray, state: Params, cfg: ArchConfig
                 ) -> Tuple[jnp.ndarray, Params]:
    xg = linear(x, p["w_gates"])
    (c, n, h, m), y = _slstm_cell(
        p, xg, (state["c"], state["n"], state["h"], state["m"]))
    y = y.astype(x.dtype)
    f = jax.nn.silu(linear(y, p["ffn_gate"])) * linear(y, p["ffn_up"])
    return linear(f, p["ffn_down"]), {"c": c, "n": n, "h": h, "m": m}
