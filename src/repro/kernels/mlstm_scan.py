"""Pallas chunkwise mLSTM kernel (xLSTM matrix-memory recurrence).

The (hd x hd) matrix state C, normalizer n and stabilizer m persist in VMEM
scratch across the chunk grid axis (sequential on TPU), so the recurrent
state never round-trips HBM between chunks — the same state-residency win
flash attention gets for (m, l, acc).  Grid: (batch, head, n_chunks); one
(chunk x hd) tile of q/k/v and a (chunk x 1) column of each gate per step.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .placement import on_tpu

# Mosaic contracts f32 operands in one bf16 pass unless asked for full f32.
_F32 = jax.lax.Precision.HIGHEST


def _mlstm_kernel(q_ref, k_ref, v_ref, li_ref, lf_ref, o_ref,
                  c_ref, n_ref, m_ref, *, chunk: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        c_ref[...] = jnp.zeros_like(c_ref)
        n_ref[...] = jnp.zeros_like(n_ref)
        m_ref[...] = jnp.full_like(m_ref, -1e30)

    q = q_ref[0, 0].astype(jnp.float32)              # (C, hd)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    li = li_ref[0, 0].astype(jnp.float32)            # (C, 1)
    lf = lf_ref[0, 0].astype(jnp.float32)

    # Per-chunk prefix sums and maxima as masked reductions over a (C, C)
    # tile: u (sublanes) indexes the output step, t (lanes) the source step.
    # Row (1, C) copies of the gates come from the same diagonal trick.
    u = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    t = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    causal = u >= t
    lf_row = jnp.sum(jnp.where(u == t, lf, 0.0), axis=0, keepdims=True)
    li_row = jnp.sum(jnp.where(u == t, li, 0.0), axis=0, keepdims=True)
    f_cum = jnp.sum(jnp.where(causal, lf_row, 0.0), axis=1,
                    keepdims=True)                   # F_u, (C, 1)
    f_cum_row = jnp.sum(jnp.where(u <= t, lf, 0.0), axis=0,
                        keepdims=True)               # F_t, (1, C)
    f_tot = jnp.sum(lf_row, axis=1, keepdims=True)   # (1, 1)
    s_runmax = jnp.max(jnp.where(causal, li_row - f_cum_row, -1e30),
                       axis=1, keepdims=True)        # (C, 1)
    m_prev = m_ref[...]                              # (1, 1)
    m_u = jnp.maximum(m_prev, s_runmax) + f_cum      # (C, 1)

    log_w = f_cum - f_cum_row + li_row - m_u
    w = jnp.where(causal, jnp.exp(log_w), 0.0)       # (U, T)

    qkt = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                              precision=_F32,
                              preferred_element_type=jnp.float32)
    scores = qkt * w
    intra = jax.lax.dot_general(scores, v, (((1,), (0,)), ((), ())),
                                precision=_F32,
                                preferred_element_type=jnp.float32)
    norm_intra = scores.sum(axis=1, keepdims=True)

    d_u = jnp.exp(f_cum + m_prev - m_u)              # (C, 1)
    inter = jax.lax.dot_general(q, c_ref[...], (((1,), (0,)), ((), ())),
                                precision=_F32,
                                preferred_element_type=jnp.float32) * d_u
    norm_inter = jnp.sum(q * n_ref[...], axis=1, keepdims=True) * d_u
    denom = jnp.maximum(jnp.abs(norm_inter + norm_intra), jnp.exp(-m_u))
    o_ref[0, 0] = ((inter + intra) / denom).astype(o_ref.dtype)

    m_new = m_u[chunk - 1:, :]                       # (1, 1)
    carry_decay = jnp.exp(f_tot + m_prev - m_new)
    src_w = jnp.exp(li + (f_tot - f_cum) - m_new)    # (C, 1)
    ks = k * src_w
    c_ref[...] = c_ref[...] * carry_decay + jax.lax.dot_general(
        ks, v, (((0,), (0,)), ((), ())), precision=_F32,
        preferred_element_type=jnp.float32)
    n_ref[...] = n_ref[...] * carry_decay + ks.sum(axis=0, keepdims=True)
    m_ref[...] = m_new


def mlstm_chunkwise(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    log_i: jnp.ndarray, log_f: jnp.ndarray, *,
                    chunk: int = 64,
                    interpret: Optional[bool] = None) -> jnp.ndarray:
    """q/k/v (B,S,H,hd); log_i/log_f (B,S,H) pre-activations (log-space).

    Returns the normalized hidden states (B,S,H,hd).  The kernel runs on
    head-major copies ((B,H,S,hd) and gate columns (B,H,S,1)), so that
    Mosaic tiles (chunk x hd) and (chunk x 1) blocks."""
    b, s, h, hd = q.shape
    chunk = min(chunk, s)
    assert s % chunk == 0
    nc = s // chunk
    if interpret is None:
        interpret = not on_tpu()

    kernel = functools.partial(_mlstm_kernel, chunk=chunk)
    qkv_spec = pl.BlockSpec((1, 1, chunk, hd),
                            lambda bi, hi, ci: (bi, hi, ci, 0))
    gate_spec = pl.BlockSpec((1, 1, chunk, 1),
                             lambda bi, hi, ci: (bi, hi, ci, 0))
    heads_first = lambda x: x.swapaxes(1, 2)
    gate_cols = lambda g: g.swapaxes(1, 2)[..., None]
    out = pl.pallas_call(
        kernel,
        grid=(b, h, nc),
        in_specs=[qkv_spec, qkv_spec, qkv_spec, gate_spec, gate_spec],
        out_specs=qkv_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, s, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((hd, hd), jnp.float32),
            pltpu.VMEM((1, hd), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
        ],
        interpret=interpret,
    )(heads_first(q), heads_first(k), heads_first(v), gate_cols(log_i),
      gate_cols(log_f))
    return out.swapaxes(1, 2)
