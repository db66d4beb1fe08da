"""Pallas TPU flash attention (causal / sliding-window, GQA-aware).

TPU adaptation notes (vs the CUDA flash-attention algorithm):
  * the grid's last axis iterates *sequentially* on a TPU core, so the
    online-softmax running state (m, l, acc) lives in VMEM scratch that
    persists across key-block grid steps — no atomics or shared-memory
    staging as on GPUs;
  * BlockSpec index maps pin one (batch, q-head) pair per outer step and
    stream (block_q x head_dim) / (block_k x head_dim) tiles of head-major
    copies of q/k/v through VMEM;
    GQA maps the q-head grid index onto its KV head in the index map, so
    KV tiles are fetched once per group without materializing repeats;
  * block shapes default to 128 x head_dim — MXU-aligned (128 lanes) and
    well under VMEM (128*256*4B = 128 KiB per tile);
  * causal + window skipping is structural: off-band key blocks are
    `pl.when`-skipped entirely (no masked FLOPs, unlike an S x S mask).

This kernel eliminates the HBM round-trips of the XLA chunked-softmax path
(the `acc` loop-carry traffic LEO's §Perf baseline attributes) by keeping
the running state resident in VMEM for the whole key sweep.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, block_q: int, block_k: int, n_kv: int,
                  causal: bool, window: Optional[int]):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    in_band = True
    if causal:
        in_band = ik <= iq
    if window is not None:
        wb = -(-window // block_k)  # ceil
        in_band = jnp.logical_and(in_band, ik >= iq - wb)

    @pl.when(in_band)
    def _compute():
        q = q_ref[0, 0]                             # (Bq, hd)
        k = k_ref[0, 0]                             # (Bk, hd)
        v = v_ref[0, 0]
        scores = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (Bq, Bk)
        q_pos = iq * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        k_pos = ik * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = jnp.ones_like(scores, dtype=jnp.bool_)
        if causal:
            mask = k_pos <= q_pos
        if window is not None:
            mask = jnp.logical_and(mask, k_pos > q_pos - window)
        scores = jnp.where(mask, scores, _NEG_INF)

        m_prev = m_ref[...]                         # (Bq, 1)
        l_prev = l_ref[...]
        m_new = jnp.maximum(m_prev, scores.max(axis=1, keepdims=True))
        p = jnp.exp(scores - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_prev * corr + p.sum(axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    last_ik = iq if causal else (n_kv - 1)

    @pl.when(ik == last_ik)
    def _finalize():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, window: Optional[int] = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: Optional[bool] = None) -> jnp.ndarray:
    """q (B,S,H,hd); k/v (B,S,Kv,hd) with H % Kv == 0. Returns (B,S,H,hd).

    The kernel runs on head-major (B,H,S,hd) copies: Mosaic tiles the last
    two block dimensions, which must be (block x hd), not (1 x hd)."""
    b, s, h, hd = q.shape
    kv_heads = k.shape[2]
    groups = h // kv_heads
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    assert s % block_q == 0 and s % block_k == 0
    n_q = s // block_q
    n_kv = s // block_k
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    scale = 1.0 / math.sqrt(hd)

    kernel = functools.partial(
        _flash_kernel, scale=scale, block_q=block_q, block_k=block_k,
        n_kv=n_kv, causal=causal, window=window)
    q_spec = pl.BlockSpec((1, 1, block_q, hd),
                          lambda bi, hi, qi, ki: (bi, hi, qi, 0))
    kv_spec = pl.BlockSpec((1, 1, block_k, hd),
                           lambda bi, hi, qi, ki, g=groups:
                           (bi, hi // g, ki, 0))
    out = pl.pallas_call(
        kernel,
        grid=(b, h, n_q, n_kv),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, s, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        interpret=interpret,
    )(q.swapaxes(1, 2), k.swapaxes(1, 2), v.swapaxes(1, 2))
    return out.swapaxes(1, 2)
