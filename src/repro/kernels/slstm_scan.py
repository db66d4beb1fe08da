"""Pallas sLSTM kernel (xLSTM scalar-memory recurrence).

The sLSTM is strictly sequential in time — per step, exponential-gated
scalar state updates plus a recurrent (D x 4D) matmul on the previous
hidden state.  Unfused, every step round-trips four (B, D) states and the
backward accumulates full-sequence gradient stacks per step (the xLSTM
authors ship fused CUDA kernels for exactly this reason).  This kernel
keeps (c, n, h, m) in VMEM scratch across the chunk grid axis and the
recurrent weight resident in VMEM, so HBM traffic is the per-chunk gate
pre-activations in and hidden states out.

Grid: (batch_blocks, n_chunks); the time recurrence runs as a fori_loop
inside the kernel.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .placement import on_tpu


def _slstm_kernel(xg_ref, r_ref, o_ref, c_ref, n_ref, h_ref, m_ref, *,
                  chunk: int, d: int):
    ic = pl.program_id(1)

    @pl.when(ic == 0)
    def _init():
        c_ref[...] = jnp.zeros_like(c_ref)
        n_ref[...] = jnp.zeros_like(n_ref)
        h_ref[...] = jnp.zeros_like(h_ref)
        m_ref[...] = jnp.full_like(m_ref, -1e30)

    r = r_ref[...]                                   # (D, 4D) resident

    def step(t, _):
        xg = xg_ref[0, t].astype(jnp.float32)        # (B, 4D)
        rec = jax.lax.dot(h_ref[...], r, precision=jax.lax.Precision.HIGHEST,
                          preferred_element_type=jnp.float32)
        g = xg + rec
        gi, gf = g[:, :d], g[:, d:2 * d]
        gz, go = g[:, 2 * d:3 * d], g[:, 3 * d:]
        log_f = jax.nn.log_sigmoid(gf)
        m_new = jnp.maximum(log_f + m_ref[...], gi)
        i_w = jnp.exp(gi - m_new)
        f_w = jnp.exp(log_f + m_ref[...] - m_new)
        c_new = f_w * c_ref[...] + i_w * jnp.tanh(gz)
        n_new = f_w * n_ref[...] + i_w
        h_new = jax.nn.sigmoid(go) * c_new / jnp.maximum(n_new, 1.0)
        c_ref[...] = c_new
        n_ref[...] = n_new
        h_ref[...] = h_new
        m_ref[...] = m_new
        o_ref[0, t] = h_new.astype(o_ref.dtype)
        return ()

    jax.lax.fori_loop(0, chunk, step, ())


def slstm_scan(xg: jnp.ndarray, r: jnp.ndarray, *, chunk: int = 64,
               interpret: Optional[bool] = None) -> jnp.ndarray:
    """xg (B, S, 4D) input gate pre-activations; r (D, 4D) recurrent weights.

    Returns hidden states (B, S, D)."""
    b, s, d4 = xg.shape
    d = d4 // 4
    chunk = min(chunk, s)
    assert s % chunk == 0
    nc = s // chunk
    if interpret is None:
        interpret = not on_tpu()

    return pl.pallas_call(
        functools.partial(_slstm_kernel, chunk=chunk, d=d),
        grid=(1, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, b, d4),
                         lambda bi, ci: (0, ci, 0, 0)),
            pl.BlockSpec((d, d4), lambda bi, ci: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, b, d),
                               lambda bi, ci: (0, ci, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, s, b, d), xg.dtype),
        scratch_shapes=[
            pltpu.VMEM((b, d), jnp.float32),
            pltpu.VMEM((b, d), jnp.float32),
            pltpu.VMEM((b, d), jnp.float32),
            pltpu.VMEM((b, d), jnp.float32),
        ],
        interpret=interpret,
    )(xg.swapaxes(0, 1)[None], r)[0].swapaxes(0, 1)
