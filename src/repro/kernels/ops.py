"""Jitted forms of the Pallas kernels, for the kernel tests and
`chip_smoke.py`.

`interpret=None` auto-selects: compiled Mosaic in a TPU process, interpret
mode elsewhere (`placement.on_tpu`).  The model calls the kernels
themselves (`flash_attention.flash_attention`, `ssm_scan.selective_scan`)
inside its own jitted step, where `placement.on_one_tpu` allows.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax

from .flash_attention import flash_attention
from .mlstm_scan import mlstm_chunkwise
from .rmsnorm import rmsnorm_baseline, rmsnorm_pipelined
from .slstm_scan import slstm_scan
from .ssm_scan import selective_scan

flash_attention_op = jax.jit(
    flash_attention,
    static_argnames=("causal", "window", "block_q", "block_k", "interpret"))

rmsnorm_op = jax.jit(
    rmsnorm_pipelined,
    static_argnames=("eps", "block_rows", "interpret"))

rmsnorm_baseline_op = jax.jit(
    rmsnorm_baseline,
    static_argnames=("eps", "block_rows", "interpret"))

mlstm_chunkwise_op = jax.jit(
    mlstm_chunkwise, static_argnames=("chunk", "interpret"))

selective_scan_op = jax.jit(selective_scan,
                            static_argnames=("chunk", "interpret"))

slstm_scan_op = jax.jit(slstm_scan, static_argnames=("chunk", "interpret"))

__all__ = [
    "flash_attention", "flash_attention_op", "mlstm_chunkwise",
    "mlstm_chunkwise_op", "rmsnorm_baseline", "rmsnorm_baseline_op",
    "rmsnorm_pipelined", "rmsnorm_op", "selective_scan", "selective_scan_op",
    "slstm_scan", "slstm_scan_op",
]
