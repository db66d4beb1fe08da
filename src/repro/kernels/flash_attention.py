"""Pallas TPU flash attention with its own backward (causal or sliding
window, GQA).

`flash_attention` is the softmax core of a one-device training or prefill
step on a TPU (`models/attention.attn_forward`): a `jax.custom_vjp` whose
forward (`flash_attention_fwd`) saves the output and each row's
log-sum-exp, and whose backward recomputes the probabilities from them,
block by block, in two kernels: `flash_attention_bwd_dkv` sweeps the
query blocks that see a key block, `flash_attention_bwd_dq` the key
blocks a query block sees.  No S x S tensor ever reaches HBM.

Layout and schedule:
  * the kernels run on head-major copies, q as (B, Kv, G, S, hd) and k, v
    as (B, Kv, S, hd), so a grid cell holds one KV group: its K and V
    tiles are fetched once for the G query heads that share them, and
    those heads are a static loop inside the cell;
  * the last grid axis walks a band: only the key blocks a query block
    can see (causal: up to the diagonal; a window: ceil(window / block_k)
    + 1 blocks), or in the dK/dV pass the query blocks that see a key
    block.  The axis is as long as the widest band, and a step past a
    narrower band repeats the band's last block index, so it fetches
    nothing, and computes nothing;
  * only blocks that straddle the diagonal or the window's edge are
    masked; the others skip the mask;
  * precision is the XLA path's (`models/attention.chunked_attention`):
    q is scaled by 1/sqrt(hd) in its own dtype before the kernels, matmul
    operands are in the inputs' dtype (q, k, v, P, dO and dS), sums in
    f32, and the softmax statistics and elementwise work in f32;
  * the per-row statistics (log-sum-exp, and D = rowsum(dO * O)) travel
    in HBM as f32 rows (B, Kv, G, S).  The dK/dV pass computes scores
    transposed, (key, query), so that they broadcast as rows there;
    the forward and the dQ pass turn them between rows and columns once
    per query block.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .placement import on_tpu

_NEG_INF = -1e30
_LANES = 128
_NT = (((1,), (1,)), ((), ()))     # a @ b.T
_NN = (((1,), (0,)), ((), ()))     # a @ b
_VMEM_BUDGET = 12 * 2**20          # under the 16 MiB a v5e kernel may scope
_BLOCKS = (512, 256, 128)


def _block_vmem_bytes(block: int, hd: int, groups: int) -> int:
    """VMEM the dK/dV kernel holds (the others hold less) at square blocks,
    counting 4-byte elements: its double-buffered blocks (q and dO for G
    heads, their statistics, k, v, dk and dv), its two f32 accumulators
    and about six (block x block) f32 intermediates a head."""
    blocks = 2 * (2 * groups * block * hd + 2 * 8 * block + 4 * block * hd)
    return 4 * (blocks + 2 * block * hd + 6 * block * block)


def flash_attention_blocks(s: int, hd: int, groups: int,
                           window: Optional[int] = None
                           ) -> Optional[Tuple[int, int]]:
    """(block_q, block_k) for these shapes: the largest square block that
    divides the sequence, leaves a window at least two blocks wide, and
    keeps the dK/dV kernel within the VMEM budget.  None when the kernel
    does not apply."""
    for block in _BLOCKS:
        if s % block or (window is not None and window < 2 * block):
            continue
        if _block_vmem_bytes(block, hd, groups) <= _VMEM_BUDGET:
            return block, block
    return None


class _Spec(NamedTuple):
    causal: bool
    window: Optional[int]
    block_q: int
    block_k: int
    interpret: bool


def _div(a, b):
    """a // b for a >= 0, on Python ints or traced int32 scalars."""
    return a // b if isinstance(a, int) else jax.lax.div(a, b)


def _max(a, b):
    return max(a, b) if isinstance(a, int) else jnp.maximum(a, b)


def _min(a, b):
    return min(a, b) if isinstance(a, int) else jnp.minimum(a, b)


def _key_band(iq, spec: _Spec, n_k: int):
    """First and last key block that query block `iq` sees."""
    bq, bk = spec.block_q, spec.block_k
    first = 0 if spec.window is None else \
        _div(_max(iq * bq - spec.window + 1, 0), bk)
    last = _div((iq + 1) * bq - 1, bk) if spec.causal else n_k - 1
    return first, last


def _query_band(kb, spec: _Spec, n_q: int):
    """First and last query block that sees key block `kb`."""
    bq, bk = spec.block_q, spec.block_k
    first = _div(kb * bk, bq) if spec.causal else 0
    last = n_q - 1 if spec.window is None else \
        _min(_div(kb * bk + bk + spec.window - 2, bq), n_q - 1)
    return first, last


def _band_width(band, n_outer: int, n_inner: int) -> int:
    return max(last - first + 1
               for first, last in (band(i, n_inner) for i in range(n_outer)))


def _needs_mask(iq, kb, spec: _Spec):
    """Whether block (iq, kb) holds a pair the mask hides."""
    bq, bk = spec.block_q, spec.block_k
    needs = False
    if spec.causal:
        needs = kb * bk + bk - 1 > iq * bq
    if spec.window is not None:
        needs = jnp.logical_or(needs,
                               kb * bk <= iq * bq + bq - 1 - spec.window)
    return needs


def _visible(q_pos, k_pos, spec: _Spec):
    mask = jnp.ones(q_pos.shape, jnp.bool_)
    if spec.causal:
        mask = k_pos <= q_pos
    if spec.window is not None:
        mask = jnp.logical_and(mask, k_pos > q_pos - spec.window)
    return mask


def _positions(rows: int, cols: int, row0, col0):
    shape = (rows, cols)
    return (row0 + jax.lax.broadcasted_iota(jnp.int32, shape, 0),
            col0 + jax.lax.broadcasted_iota(jnp.int32, shape, 1))


def _row(col):
    """(n, 1) -> (1, n)."""
    return jnp.broadcast_to(col, (col.shape[0], _LANES)).T[:1]


def _col(row):
    """(1, n) -> (n, 1)."""
    return jnp.broadcast_to(row, (_LANES, row.shape[1])).T[:, :1]


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _branch(real, needs, step):
    """step(masked) on an in-band block, masked only where it must be."""
    @pl.when(jnp.logical_and(real, needs))
    def _masked():
        step(True)

    @pl.when(jnp.logical_and(real, jnp.logical_not(needs)))
    def _plain():
        step(False)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, spec: _Spec, n_k: int, groups: int):
    iq, j = pl.program_id(2), pl.program_id(3)
    first, last = _key_band(iq, spec, n_k)
    kb = first + j
    bq, bk = spec.block_q, spec.block_k

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def step(masked: bool):
        k, v = k_ref[0, 0], v_ref[0, 0]
        if masked:
            mask = _visible(*_positions(bq, bk, iq * bq, kb * bk), spec)
        for g in range(groups):
            s = _dot(q_ref[0, 0, g], k, _NT)                  # (bq, bk)
            if masked:
                s = jnp.where(mask, s, _NEG_INF)
            m_prev = m_scr[g]                                 # (bq, 1)
            m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_scr[g] = l_scr[g] * corr + p.sum(axis=1, keepdims=True)
            acc_scr[g] = acc_scr[g] * corr + _dot(p.astype(v.dtype), v, _NN)
            m_scr[g] = m_new

    _branch(kb <= last, _needs_mask(iq, kb, spec), step)

    @pl.when(kb == last)
    def _finalize():
        for g in range(groups):
            l = jnp.maximum(l_scr[g], 1e-30)
            o_ref[0, 0, g] = (acc_scr[g] / l).astype(o_ref.dtype)
            lse_ref[0, 0, g:g + 1, :] = _row(m_scr[g] + jnp.log(l))


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, d_ref, dk_ref, dv_ref,
                dk_scr, dv_scr, *, spec: _Spec, n_q: int, groups: int):
    """Scores transposed, (key, query): the rows' statistics broadcast as
    rows, and dK, dV are plain (key, query) @ (query, hd) products."""
    kb, j = pl.program_id(2), pl.program_id(3)
    first, last = _query_band(kb, spec, n_q)
    iq = first + j
    bq, bk = spec.block_q, spec.block_k

    @pl.when(j == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def step(masked: bool):
        k, v = k_ref[0, 0], v_ref[0, 0]                       # (bk, hd)
        if masked:
            k_pos, q_pos = _positions(bk, bq, kb * bk, iq * bq)
            mask = _visible(q_pos, k_pos, spec)
        dk = jnp.zeros(dk_scr.shape, jnp.float32)
        dv = jnp.zeros(dv_scr.shape, jnp.float32)
        for g in range(groups):
            q, do = q_ref[0, 0, g], do_ref[0, 0, g]           # (bq, hd)
            st = _dot(k, q, _NT)                              # (bk, bq)
            if masked:
                st = jnp.where(mask, st, _NEG_INF)
            pt = jnp.exp(st - lse_ref[0, 0, g:g + 1, :])
            dv = dv + _dot(pt.astype(do.dtype), do, _NN)
            dpt = _dot(v, do, _NT)
            dst = pt * (dpt - d_ref[0, 0, g:g + 1, :])
            dk = dk + _dot(dst.astype(q.dtype), q, _NN)
        dk_scr[...] += dk
        dv_scr[...] += dv

    _branch(iq <= last, _needs_mask(iq, kb, spec), step)

    @pl.when(iq == last)
    def _finalize():
        dk_ref[0, 0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, d_ref, dq_ref,
               dq_scr, lse_scr, d_scr, *, spec: _Spec, n_k: int,
               groups: int):
    iq, j = pl.program_id(2), pl.program_id(3)
    first, last = _key_band(iq, spec, n_k)
    kb = first + j
    bq, bk = spec.block_q, spec.block_k

    @pl.when(j == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)
        for g in range(groups):
            lse_scr[g] = _col(lse_ref[0, 0, g:g + 1, :])
            d_scr[g] = _col(d_ref[0, 0, g:g + 1, :])

    def step(masked: bool):
        k, v = k_ref[0, 0], v_ref[0, 0]
        if masked:
            mask = _visible(*_positions(bq, bk, iq * bq, kb * bk), spec)
        for g in range(groups):
            q, do = q_ref[0, 0, g], do_ref[0, 0, g]
            s = _dot(q, k, _NT)                               # (bq, bk)
            if masked:
                s = jnp.where(mask, s, _NEG_INF)
            p = jnp.exp(s - lse_scr[g])
            ds = p * (_dot(do, v, _NT) - d_scr[g])
            dq_scr[g] += _dot(ds.astype(k.dtype), k, _NN)

    _branch(kb <= last, _needs_mask(iq, kb, spec), step)

    @pl.when(kb == last)
    def _finalize():
        for g in range(groups):
            dq_ref[0, 0, g] = dq_scr[g].astype(dq_ref.dtype)


def _params(interpret: bool):
    if interpret:
        return None
    return pltpu.CompilerParams(dimension_semantics=(
        "parallel", "parallel", "parallel", "arbitrary"))


def _query_major_specs(spec: _Spec, groups: int, hd: int, n_k: int):
    """Grid cells (batch, group, query block, key band step)."""
    bq, bk = spec.block_q, spec.block_k

    def kb(i, j):
        first, last = _key_band(i, spec, n_k)
        return _min(first + j, last)
    rows = pl.BlockSpec((1, 1, groups, bq, hd),
                        lambda b, g, i, j: (b, g, 0, i, 0))
    stats = pl.BlockSpec((1, 1, groups, bq), lambda b, g, i, j: (b, g, 0, i))
    keys = pl.BlockSpec((1, 1, bk, hd), lambda b, g, i, j: (b, g, kb(i, j), 0))
    return rows, stats, keys


def _forward(q, k, v, spec: _Spec):
    b, kv, groups, s, hd = q.shape
    n_q, n_k = s // spec.block_q, s // spec.block_k
    rows, stats, keys = _query_major_specs(spec, groups, hd, n_k)
    band = _band_width(lambda i, n: _key_band(i, spec, n), n_q, n_k)
    kernel = functools.partial(_fwd_kernel, spec=spec, n_k=n_k,
                               groups=groups)
    return pl.pallas_call(
        kernel,
        grid=(b, kv, n_q, band),
        in_specs=[rows, keys, keys],
        out_specs=[rows, stats],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(q.shape[:-1], jnp.float32)],
        scratch_shapes=[pltpu.VMEM((groups, spec.block_q, 1), jnp.float32),
                        pltpu.VMEM((groups, spec.block_q, 1), jnp.float32),
                        pltpu.VMEM((groups, spec.block_q, hd), jnp.float32)],
        compiler_params=_params(spec.interpret),
        interpret=spec.interpret,
        name="flash_attention_fwd",
    )(q, k, v)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _flash(q, k, v, spec: _Spec):
    return _forward(q, k, v, spec)[0]


def _flash_fwd(q, k, v, spec: _Spec):
    o, lse = _forward(q, k, v, spec)
    return o, (q, k, v, o, lse)


def _flash_bwd(spec: _Spec, res, do):
    q, k, v, o, lse = res
    b, kv, groups, s, hd = q.shape
    bq, bk = spec.block_q, spec.block_k
    n_q, n_k = s // bq, s // bk
    d = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)

    def qb(kb, j):
        first, last = _query_band(kb, spec, n_q)
        return _min(first + j, last)
    q_rows = pl.BlockSpec((1, 1, groups, bq, hd),
                          lambda b, g, kb, j: (b, g, 0, qb(kb, j), 0))
    q_stats = pl.BlockSpec((1, 1, groups, bq),
                           lambda b, g, kb, j: (b, g, 0, qb(kb, j)))
    own_keys = pl.BlockSpec((1, 1, bk, hd), lambda b, g, kb, j: (b, g, kb, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, spec=spec, n_q=n_q, groups=groups),
        grid=(b, kv, n_k,
              _band_width(lambda i, n: _query_band(i, spec, n), n_k, n_q)),
        in_specs=[q_rows, own_keys, own_keys, q_rows, q_stats, q_stats],
        out_specs=[own_keys, own_keys],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, hd), jnp.float32),
                        pltpu.VMEM((bk, hd), jnp.float32)],
        compiler_params=_params(spec.interpret),
        interpret=spec.interpret,
        name="flash_attention_bwd_dkv",
    )(q, k, v, do, lse, d)

    rows, stats, keys = _query_major_specs(spec, groups, hd, n_k)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, spec=spec, n_k=n_k, groups=groups),
        grid=(b, kv, n_q,
              _band_width(lambda i, n: _key_band(i, spec, n), n_q, n_k)),
        in_specs=[rows, keys, keys, rows, stats, stats],
        out_specs=rows,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((groups, bq, hd), jnp.float32),
                        pltpu.VMEM((groups, bq, 1), jnp.float32),
                        pltpu.VMEM((groups, bq, 1), jnp.float32)],
        compiler_params=_params(spec.interpret),
        interpret=spec.interpret,
        name="flash_attention_bwd_dq",
    )(q, k, v, do, lse, d)
    return dq, dk, dv


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, window: Optional[int] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: Optional[bool] = None) -> jnp.ndarray:
    """q (B,S,H,hd); k/v (B,S,Kv,hd) with H % Kv == 0. Returns (B,S,H,hd).

    Query head h attends with KV head h // (H / Kv), as
    `models/attention.chunked_attention` repeats them.  Position k is
    visible from position q when k <= q (causal) and k > q - window.
    Differentiable in q, k and v.  The blocks default to
    `flash_attention_blocks`."""
    b, s, h, hd = q.shape
    kv = k.shape[2]
    groups = h // kv
    if block_q is None or block_k is None:
        blocks = flash_attention_blocks(s, hd, groups, window)
        assert blocks, (s, hd, groups, window)
        block_q, block_k = blocks
    block_q, block_k = min(block_q, s), min(block_k, s)
    assert s % block_q == 0 and s % block_k == 0, (s, block_q, block_k)
    if interpret is None:
        interpret = not on_tpu()
    spec = _Spec(causal, window, block_q, block_k, interpret)
    qh = (q * (1.0 / math.sqrt(hd))).reshape(b, s, kv, groups, hd)
    qh = qh.transpose(0, 2, 3, 1, 4)
    o = _flash(qh, k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3), spec)
    return o.transpose(0, 3, 1, 2, 4).reshape(b, s, h, hd)
