"""Pallas RMSNorm — two variants reproducing the HipKittens case study.

The paper's §VI-D(b): an expert-tuned RMSNorm still left 20-58% of stall
cycles on memory because loads were compiler-lowered to scalar accesses;
LEO's diagnosis led to *multi-row software pipelining with split s_waitcnt
counters*, worth 1.07-1.24x.

TPU analogue:

* `rmsnorm_baseline` — one row-block per grid step through the implicit
  BlockSpec pipeline.  Correct, but each grid step's compute waits on its
  own block arrival (the synchronous-load pattern LEO flags as exposed
  `mem_waitcnt` stalls).
* `rmsnorm_pipelined` — rows live in ANY (HBM) memory space; the kernel
  issues explicit `make_async_copy` DMAs into a double-buffered VMEM
  scratch with one DMA semaphore per buffer — literally "split waitcnt
  counters": while block i computes, block i+1 is in flight.  LEO's jaxpr
  front-end sees the dma_start/dma_wait pairs and traces `mem_waitcnt`
  edges through them (tests/test_kernels.py::test_leo_traces_rmsnorm_dma).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .placement import on_tpu


# -- baseline: implicit blockspec pipeline ------------------------------------

def _rmsnorm_kernel(x_ref, scale_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    o_ref[...] = (x * jax.lax.rsqrt(var + eps) *
                  scale_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def rmsnorm_baseline(x: jnp.ndarray, scale: jnp.ndarray, *,
                     eps: float = 1e-5, block_rows: int = 8,
                     interpret: Optional[bool] = None) -> jnp.ndarray:
    """x (R, D); scale (D,)."""
    r, d = x.shape
    block_rows = min(block_rows, r)
    assert r % block_rows == 0
    if interpret is None:
        interpret = not on_tpu()
    return pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=(r // block_rows,),
        in_specs=[pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
                  pl.BlockSpec((d,), lambda i: (0,))],
        out_specs=pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r, d), x.dtype),
        interpret=interpret,
    )(x, scale)


# -- pipelined: explicit double-buffered DMA (split waitcnt counters) ----------

def _rmsnorm_pipelined_kernel(x_hbm, scale_ref, o_ref, buf, sems, *,
                              eps: float, block_rows: int, n_blocks: int):
    i = pl.program_id(0)
    slot = jax.lax.rem(i, 2)
    next_slot = jax.lax.rem(i + 1, 2)

    @pl.when(i == 0)
    def _prime():
        pltpu.make_async_copy(
            x_hbm.at[pl.ds(0, block_rows)], buf.at[0], sems.at[0]).start()

    @pl.when(i + 1 < n_blocks)
    def _prefetch():
        pltpu.make_async_copy(
            x_hbm.at[pl.ds((i + 1) * block_rows, block_rows)],
            buf.at[next_slot], sems.at[next_slot]).start()

    pltpu.make_async_copy(
        x_hbm.at[pl.ds(i * block_rows, block_rows)], buf.at[slot],
        sems.at[slot]).wait()

    x = buf[slot].astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    o_ref[...] = (x * jax.lax.rsqrt(var + eps) *
                  scale_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def rmsnorm_pipelined(x: jnp.ndarray, scale: jnp.ndarray, *,
                      eps: float = 1e-5, block_rows: int = 8,
                      interpret: Optional[bool] = None) -> jnp.ndarray:
    """x (R, D); scale (D,) — double-buffered manual DMA variant."""
    r, d = x.shape
    block_rows = min(block_rows, r)
    assert r % block_rows == 0
    n_blocks = r // block_rows
    if interpret is None:
        interpret = not on_tpu()
    return pl.pallas_call(
        functools.partial(_rmsnorm_pipelined_kernel, eps=eps,
                          block_rows=block_rows, n_blocks=n_blocks),
        grid=(n_blocks,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec((d,), lambda i: (0,))],
        out_specs=pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r, d), x.dtype),
        scratch_shapes=[
            pltpu.VMEM((2, block_rows, d), x.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        interpret=interpret,
    )(x, scale)
