"""Pallas selective-scan (Mamba-style SSM) kernel.

Grid: (batch, d_inner tiles, n_chunks) with each tile's (N x d_tile) state
persistent in VMEM scratch across chunks.  Inside a chunk the recurrence
h = a*h + bx runs as a `fori_loop` over time steps on (N, d_tile) vector
tiles: d_inner is the lane dimension (tiles of a multiple of 128 lanes for
the VPU), N=16 the sublane dimension.  The kernel reads (N, d_inner)-major
copies of the terms, since N in the lane dimension would pad 16 lanes to
128 and blow the VMEM budget at real widths.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_MAX_TILE_LANES = 1024


def _ssm_kernel(a_ref, bx_ref, c_ref, o_ref, h_ref, *, chunk: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    def step(t, h):
        a_t = a_ref[0, t].astype(jnp.float32)       # (N, d_tile)
        bx_t = bx_ref[0, t].astype(jnp.float32)     # (N, d_tile)
        c_t = c_ref[0, t].astype(jnp.float32)       # (N, 1)
        h = a_t * h + bx_t
        y = jnp.sum(h * c_t, axis=0, keepdims=True)  # (1, d_tile)
        o_ref[0, pl.ds(t, 1), :] = y.astype(o_ref.dtype)
        return h

    h_ref[...] = jax.lax.fori_loop(0, chunk, step, h_ref[...])


def _d_tile(din: int) -> int:
    """Largest multiple of 128 lanes that divides d_inner (capped), or all
    of d_inner when it is not lane-aligned."""
    if din % 128:
        return din
    return max(t for t in range(128, min(din, _MAX_TILE_LANES) + 1, 128)
               if din % t == 0)


def ssm_scan(a: jnp.ndarray, bx: jnp.ndarray, c: jnp.ndarray, *,
             chunk: int = 16,
             interpret: Optional[bool] = None) -> jnp.ndarray:
    """a/bx (B,S,din,N) discretized recurrence terms; c (B,S,N) readout.

    Returns y (B,S,din) with y_t = C_t . h_t, h_t = a_t * h_{t-1} + bx_t."""
    b, s, din, n = a.shape
    chunk = min(chunk, s)
    assert s % chunk == 0
    nc = s // chunk
    dt = _d_tile(din)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    term_spec = pl.BlockSpec((1, chunk, n, dt),
                             lambda bi, di, ci: (bi, ci, 0, di))
    return pl.pallas_call(
        functools.partial(_ssm_kernel, chunk=chunk),
        grid=(b, din // dt, nc),
        in_specs=[term_spec, term_spec,
                  pl.BlockSpec((1, chunk, n, 1),
                               lambda bi, di, ci: (bi, ci, 0, 0))],
        out_specs=pl.BlockSpec((1, chunk, dt),
                               lambda bi, di, ci: (bi, ci, di)),
        out_shape=jax.ShapeDtypeStruct((b, s, din), jnp.float32),
        scratch_shapes=[pltpu.VMEM((n, dt), jnp.float32)],
        interpret=interpret,
    )(a.swapaxes(2, 3), bx.swapaxes(2, 3), c[..., None])
