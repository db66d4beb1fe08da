"""Pallas selective-scan (Mamba-style SSM) kernel with its own backward.

`selective_scan` is the training scan of the SSM layers: a forward that
runs on a TPU for a step on one device (`models/ssm.py`), fed the
per-token terms, and a backward kernel that recomputes each chunk's states
from the one the forward saved at the chunk's start.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .placement import on_tpu

_LANES = 128
_MAX_TILE_LANES = 1024


def _d_tile(din: int) -> int:
    """The largest multiple of 128 lanes, up to 1024, that divides the
    lane-aligned d_inner."""
    return max(t for t in range(_LANES, min(din, _MAX_TILE_LANES) + 1,
                                _LANES)
               if din % t == 0)


# Inputs are the per-token terms, never their B x S x d_inner x N outer
# product: dt and x (B, S, d_inner), the selections B and C (B, S, N) and
# the decay rates A (d_inner, N).  Each grid cell (batch, chunk, d_inner
# tile) forms a_t = exp(A dt_t) and bx_t = dt_t x_t B_t in VMEM and runs
# h = a*h + bx one time step after another on (N, d_tile) tiles, with
# d_inner on the lanes.  The d_inner tile is the innermost grid axis, so a
# chunk's B and C blocks are fetched once for all tiles, and each tile's
# state stays in VMEM scratch from one chunk to the next.  B and C arrive
# broadcast over one vreg's 128 lanes, (B, S, N, 128), so that a time
# step's selection is a whole (N, 128) tile.

_VMEM_BUDGET = 12 * 2**20       # under the 16 MiB a v5e kernel may scope
_CHUNKS = (256, 128, 64, 32, 16, 8)
# Time steps per loop iteration.  Mosaic unrolls a loop fully or not at
# all, so the kernels unroll by hand; 4 ran fastest of 1, 2, 4 and 8 on a
# v5e at hymba-1.5b's width.
_UNROLL = 4


def _scan_vmem_bytes(chunk: int, n: int, d_tile: int, din: int) -> int:
    """VMEM the backward kernel holds (the forward holds less): its double-
    buffered blocks, then its scratch: x in f32, the chunk's recomputed
    states and the carried cotangents."""
    rows = 5 * chunk * d_tile * 4          # dt, x, dy in; d dt, dx out
    sel = 4 * chunk * n * _LANES * 4       # B, C in; dB, dC out
    tiles = 3 * n * d_tile * 4             # A, boundary state in; dA out
    scratch = chunk * d_tile + (chunk + 1) * n * d_tile + n * din
    return 2 * (rows + sel + tiles) + 4 * scratch


def selective_scan_chunk(s: int, din: int, n: int) -> Optional[int]:
    """The kernel's time chunk for these shapes: the longest that divides
    the sequence and keeps the backward within the VMEM budget.  None when
    the kernel does not apply (d_inner not lane-aligned, or no chunk
    divides the sequence)."""
    if din % _LANES:
        return None
    d_tile = _d_tile(din)
    for chunk in _CHUNKS:
        if s % chunk == 0 and \
                _scan_vmem_bytes(chunk, n, d_tile, din) <= _VMEM_BUDGET:
            return chunk
    return None


def _lanes(sel, groups: int):
    """(N, 128) selection -> (N, groups * 128), one copy per lane group."""
    return jnp.concatenate([sel] * groups, axis=1) if groups > 1 else sel


def _fold(v):
    """(N, d_tile) -> (N, 128): the sum of the tile's lane groups."""
    out = v[:, :_LANES]
    for j in range(1, v.shape[1] // _LANES):
        out = out + v[:, j * _LANES:(j + 1) * _LANES]
    return out


def _loop(n: int, body, carry):
    """fori_loop(0, n, body, carry), _UNROLL steps an iteration."""
    def steps(i, c):
        for j in range(_UNROLL):
            c = body(i * _UNROLL + j, c)
        return c
    return jax.lax.fori_loop(0, n // _UNROLL, steps, carry)


def _row(ref, t):
    return ref[0, pl.ds(t, 1), :].astype(jnp.float32)    # (1, d_tile)


def _scan_fwd_kernel(dt_ref, x_in, b_ref, c_ref, a_ref, y_ref, hs_ref,
                     x_ref, h_scr, *, chunk: int):
    ci, di = pl.program_id(1), pl.program_id(2)
    x_ref[0] = x_in[0].astype(jnp.float32)

    @pl.when(ci == 0)
    def _init():
        h_scr[di] = jnp.zeros(h_scr.shape[1:], jnp.float32)

    h0 = h_scr[di]
    hs_ref[0, 0] = h0                  # the state entering this chunk
    groups = h0.shape[1] // _LANES

    def step(t, h):
        dt_t = _row(dt_ref, t)
        a_t = jnp.exp(a_ref[...] * dt_t)
        h = a_t * h + _lanes(b_ref[0, t], groups) * (dt_t * _row(x_ref, t))
        y_ref[0, pl.ds(t, 1), :] = jnp.sum(
            h * _lanes(c_ref[0, t], groups), axis=0, keepdims=True)
        return h

    h_scr[di] = _loop(chunk, step, h0)


def _scan_bwd_kernel(dt_ref, x_in, b_ref, c_ref, a_ref, hs_ref, dy_ref,
                     ddt_ref, dx_ref, db_ref, dc_ref, da_ref,
                     x_ref, hseq, p_scr, *, chunk: int):
    ci, di = pl.program_id(1), pl.program_id(2)
    x_ref[0] = x_in[0].astype(jnp.float32)
    a_rate = a_ref[...]
    groups = a_rate.shape[1] // _LANES

    @pl.when(ci == 0)
    def _init_carry():   # no cotangent flows in past the last position
        p_scr[di] = jnp.zeros(p_scr.shape[1:], jnp.float32)

    @pl.when(di == 0)
    def _init_sums():    # dB and dC sum over the d_inner tiles
        db_ref[...] = jnp.zeros_like(db_ref)
        dc_ref[...] = jnp.zeros_like(dc_ref)

    # The chunk's states again, from the one saved where it starts:
    # hseq[t] = h_{t-1}, hseq[t + 1] = h_t.  dC_t = sum_d dy_t h_t.
    hseq[0] = hs_ref[0, 0]

    def forward(t, h):
        dt_t = _row(dt_ref, t)
        h = jnp.exp(a_rate * dt_t) * h + \
            _lanes(b_ref[0, t], groups) * (dt_t * _row(x_ref, t))
        hseq[t + 1] = h
        dc_ref[0, t] += _fold(h * _row(dy_ref, t))
        return h

    _loop(chunk, forward, hseq[0])

    # Backwards through the chunk.  g_t = dL/dh_t = C_t dy_t + a_{t+1}
    # g_{t+1}; the carry p is a_{t+1} g_{t+1}, across chunks in p_scr.
    def backward(i, carry):
        p, da = carry
        t = chunk - 1 - i
        dt_t, x_t = _row(dt_ref, t), _row(x_ref, t)
        a_t = jnp.exp(a_rate * dt_t)
        g = p + _lanes(c_ref[0, t], groups) * _row(dy_ref, t)
        s = g * hseq[t] * a_t                  # dL/d(A dt_t), elementwise
        du = jnp.sum(g * _lanes(b_ref[0, t], groups), axis=0, keepdims=True)
        ddt_ref[0, pl.ds(t, 1), :] = (
            jnp.sum(s * a_rate, axis=0, keepdims=True) + du * x_t)
        dx_ref[0, pl.ds(t, 1), :] = du * dt_t
        db_ref[0, t] += _fold(g * (dt_t * x_t))
        return a_t * g, da + s * dt_t

    p, da = _loop(chunk, backward, (p_scr[di], jnp.zeros_like(a_rate)))
    p_scr[di] = p
    da_ref[0, 0] = da


def _scan_specs(b: int, s: int, din: int, n: int, chunk: int, reverse: bool):
    nc, d_tile = s // chunk, _d_tile(din)
    at = (lambda ci: nc - 1 - ci) if reverse else (lambda ci: ci)
    row = pl.BlockSpec((1, chunk, d_tile),
                       lambda bi, ci, di: (bi, at(ci), di))
    sel = pl.BlockSpec((1, chunk, n, _LANES),
                       lambda bi, ci, di: (bi, at(ci), 0, 0))
    tile = pl.BlockSpec((n, d_tile), lambda bi, ci, di: (0, di))
    state = pl.BlockSpec((1, 1, n, d_tile),
                         lambda bi, ci, di: (bi, at(ci), 0, di))
    return (b, nc, din // d_tile), row, sel, tile, state


def _broadcast_lanes(sel):
    return jnp.broadcast_to(sel[..., None], sel.shape + (_LANES,))


def _scan_forward(chunk, interpret, dt, x, b_sel, c_sel, a_rate):
    bsz, s, din = dt.shape
    n = b_sel.shape[-1]
    grid, row, sel, tile, state = _scan_specs(bsz, s, din, n, chunk, False)
    y, hs = pl.pallas_call(
        functools.partial(_scan_fwd_kernel, chunk=chunk),
        grid=grid,
        in_specs=[row, row, sel, sel, tile],
        out_specs=[row, state],
        out_shape=[jax.ShapeDtypeStruct((bsz, s, din), jnp.float32),
                   jax.ShapeDtypeStruct((bsz, grid[1], n, din),
                                        jnp.float32)],
        scratch_shapes=[pltpu.VMEM((1, chunk, din // grid[2]), jnp.float32),
                        pltpu.VMEM((grid[2], n, din // grid[2]),
                                   jnp.float32)],
        interpret=interpret,
        name="selective_scan_fwd",
    )(dt, x, _broadcast_lanes(b_sel), _broadcast_lanes(c_sel), a_rate.T)
    return y, hs


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _selective_scan(chunk, interpret, dt, x, b_sel, c_sel, a_rate):
    return _scan_forward(chunk, interpret, dt, x, b_sel, c_sel, a_rate)[0]


def _selective_scan_fwd(chunk, interpret, dt, x, b_sel, c_sel, a_rate):
    y, hs = _scan_forward(chunk, interpret, dt, x, b_sel, c_sel, a_rate)
    return y, (dt, x, b_sel, c_sel, a_rate, hs)


def _selective_scan_bwd(chunk, interpret, res, dy):
    dt, x, b_sel, c_sel, a_rate, hs = res
    bsz, s, din = dt.shape
    n = b_sel.shape[-1]
    grid, row, sel, tile, state = _scan_specs(bsz, s, din, n, chunk, True)
    ddt, dx, db, dc, da = pl.pallas_call(
        functools.partial(_scan_bwd_kernel, chunk=chunk),
        grid=grid,
        in_specs=[row, row, sel, sel, tile, state, row],
        out_specs=[row, row, sel, sel, state],
        out_shape=[jax.ShapeDtypeStruct((bsz, s, din), jnp.float32),
                   jax.ShapeDtypeStruct((bsz, s, din), jnp.float32),
                   jax.ShapeDtypeStruct((bsz, s, n, _LANES), jnp.float32),
                   jax.ShapeDtypeStruct((bsz, s, n, _LANES), jnp.float32),
                   jax.ShapeDtypeStruct((bsz, grid[1], n, din),
                                        jnp.float32)],
        scratch_shapes=[pltpu.VMEM((1, chunk, din // grid[2]), jnp.float32),
                        pltpu.VMEM((chunk + 1, n, din // grid[2]),
                                   jnp.float32),
                        pltpu.VMEM((grid[2], n, din // grid[2]),
                                   jnp.float32)],
        interpret=interpret,
        name="selective_scan_bwd",
    )(dt, x, _broadcast_lanes(b_sel), _broadcast_lanes(c_sel), a_rate.T, hs,
      dy)
    return (ddt, dx.astype(x.dtype), db.sum(-1), dc.sum(-1),
            da.sum((0, 1)).T)


_selective_scan.defvjp(_selective_scan_fwd, _selective_scan_bwd)


def selective_scan(dt: jnp.ndarray, x: jnp.ndarray, b_sel: jnp.ndarray,
                   c_sel: jnp.ndarray, a_rate: jnp.ndarray, *,
                   chunk: Optional[int] = None,
                   interpret: Optional[bool] = None) -> jnp.ndarray:
    """y_t = C_t . h_t with h_t = exp(A dt_t) * h_{t-1} + dt_t x_t B_t, in f32.

    dt (B, S, din) f32 step sizes; x (B, S, din) the scanned input, any
    float dtype (cast to f32 in VMEM); b_sel, c_sel (B, S, N) f32; a_rate
    (din, N) f32, the decay rates A.  Returns y (B, S, din) f32.
    Differentiable in every input: the backward is a kernel of its own
    that recomputes each chunk's states from the one the forward saved at
    the chunk's start.  `chunk` defaults to `selective_scan_chunk`."""
    bsz, s, din = dt.shape
    n = b_sel.shape[-1]
    if chunk is None:
        chunk = selective_scan_chunk(s, din, n)
    assert chunk and s % chunk == 0 and chunk % _UNROLL == 0 and \
        din % _LANES == 0, (s, din, chunk)
    if interpret is None:
        interpret = not on_tpu()
    return _selective_scan(chunk, interpret, dt, x, b_sel, c_sel, a_rate)
