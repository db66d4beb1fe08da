"""Selective state-space mixer (Mamba-style), chunked for TPU.

Recurrence per channel c with state size N:
    h_t = exp(dt_t * A) * h_{t-1} + dt_t * B_t * x_t          (N-vector)
    y_t = C_t . h_t + D * x_t

Which scan a full-sequence forward runs is chosen in `ssm_forward`:

- Where the one placement rule of the Pallas kernels holds
  (`kernels/placement.on_one_tpu`: a TPU process, a step on one device),
  with d_inner a multiple of 128 and a kernel chunk that divides S: one
  Pallas kernel with its own backward (`kernels/ssm_scan.selective_scan`),
  fed the per-token terms dt, x, B, C; the B x S x d_inner x N terms exist
  only in VMEM.
- Everywhere else: a `lax.scan` over sequence *chunks* with an associative
  scan inside each chunk (`_xla_scan`, shaped by the `ssm_impl` flag).
  That is every CPU process (the tests, LEO's dry-run and hillclimb), and
  every step sharded over more than one device: XLA cannot partition a
  Mosaic kernel across a mesh.

Decode carries `h` as O(1) state, which is what makes `long_500k` feasible
for SSM archs.
"""
from __future__ import annotations

import contextlib
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig
from ..kernels.placement import on_one_tpu
from ..kernels.ssm_scan import selective_scan, selective_scan_chunk
from .flags import FUSED_REGION_MARK, get_flags
from .layers import dense_init, linear

Params = Dict[str, jnp.ndarray]


def init_ssm(key, cfg: ArchConfig, dtype) -> Params:
    d = cfg.d_model
    din = cfg.ssm_expand * d
    n = cfg.ssm_state
    ks = jax.random.split(key, 6)
    return {
        "w_in": dense_init(ks[0], (d, 2 * din), dtype=dtype),   # x and z
        "w_b": dense_init(ks[1], (din, n), dtype=dtype),
        "w_c": dense_init(ks[2], (din, n), dtype=dtype),
        "w_dt": dense_init(ks[3], (din,), scale=1.0, dtype=jnp.float32),
        "a_log": jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32)
                         )[None, :].repeat(din, 0),             # (din, N)
        "d_skip": jnp.ones((din,), jnp.float32),
        "w_out": dense_init(ks[5], (din, d), dtype=dtype),
    }


def _discretize(p: Params, xin: jnp.ndarray):
    """xin (..., din) -> (a (...,din,N), bx (...,din,N), c (...,N))."""
    dt = jax.nn.softplus(xin.astype(jnp.float32) * p["w_dt"])  # (..., din)
    a = jnp.exp(-jnp.exp(p["a_log"]) * dt[..., None])          # (..., din, N)
    bsel = linear(xin, p["w_b"]).astype(jnp.float32)           # (..., N)
    csel = linear(xin, p["w_c"]).astype(jnp.float32)           # (..., N)
    bx = (dt * xin.astype(jnp.float32))[..., None] * bsel[..., None, :]
    return a, bx, csel


def _xla_scan(p: Params, xin: jnp.ndarray, cfg: ArchConfig,
              chunk: int) -> jnp.ndarray:
    """The chunked associative scan in XLA ops: xin (B, S, din) -> y
    (B, S, din) f32, before the D skip and the gate."""
    b, s, din = xin.shape
    chunk = min(chunk, s)
    assert s % chunk == 0
    nc = s // chunk

    def combine(p1, p2):
        a1, b1 = p1
        a2, b2 = p2
        return a1 * a2, b1 * a2 + b2

    model_flags = get_flags()
    if model_flags.ssm_impl != "xla":
        # Discretize per chunk inside the scan: (a, bx) exist only as
        # (B, chunk, din, N) transients fused into the chunk body — the
        # B x S x din x N materialization LEO flags in the baseline is gone.
        xin_c = jnp.moveaxis(xin.reshape(b, nc, chunk, din), 1, 0)

        def chunk_step(h0, xin_chunk):
            ac, bxc, cc = _discretize(p, xin_chunk)
            a_cum, bx_cum = jax.lax.associative_scan(
                combine, (ac, bxc), axis=1)
            h = a_cum * h0[:, None] + bx_cum
            y = jnp.einsum("bcdn,bcn->bcd", h, cc)
            return h[:, -1], y

        h0 = jnp.zeros((b, din, cfg.ssm_state), jnp.float32)
        # "pallas_fused" cost-models the validated Pallas selective-scan
        # kernel (kernels/ssm_scan.py): discretized terms and the
        # associative-scan stages live in VMEM; HBM traffic is the xin
        # chunks in and y chunks out.
        with (jax.named_scope(FUSED_REGION_MARK)
              if model_flags.ssm_impl == "pallas_fused"
              else contextlib.nullcontext()):
            _, ys = jax.lax.scan(chunk_step, h0, xin_c)
    else:
        a, bx, csel = _discretize(p, xin)
        a = a.reshape(b, nc, chunk, din, cfg.ssm_state)
        bx = bx.reshape(b, nc, chunk, din, cfg.ssm_state)
        csel = csel.reshape(b, nc, chunk, cfg.ssm_state)

        def chunk_step(h0, inputs):
            ac, bxc, cc = inputs  # (B, chunk, din, N), ...

            a_cum, bx_cum = jax.lax.associative_scan(
                combine, (ac, bxc), axis=1)
            h = a_cum * h0[:, None] + bx_cum          # (B, chunk, din, N)
            y = jnp.einsum("bcdn,bcn->bcd", h, cc)    # (B, chunk, din)
            return h[:, -1], y

        h0 = jnp.zeros((b, din, cfg.ssm_state), jnp.float32)
        _, ys = jax.lax.scan(chunk_step, h0,
                             (jnp.moveaxis(a, 1, 0), jnp.moveaxis(bx, 1, 0),
                              jnp.moveaxis(csel, 1, 0)))
    return jnp.moveaxis(ys, 0, 1).reshape(b, s, din)


def _kernel_scan(p: Params, xin: jnp.ndarray,
                 interpret: bool = False) -> jnp.ndarray:
    """The same recurrence as one Pallas kernel with its own backward,
    fed the per-token terms (kernels/ssm_scan.selective_scan)."""
    dt = jax.nn.softplus(xin.astype(jnp.float32) * p["w_dt"])
    bsel = linear(xin, p["w_b"]).astype(jnp.float32)
    csel = linear(xin, p["w_c"]).astype(jnp.float32)
    return selective_scan(dt, xin, bsel, csel, -jnp.exp(p["a_log"]),
                          interpret=interpret)


def _scan_in_kernel(xin: jnp.ndarray, cfg: ArchConfig) -> bool:
    """Whether this forward scans in the Pallas kernel: where
    `on_one_tpu(xin)` holds and the kernel takes the shapes."""
    _, s, din = xin.shape
    return (on_one_tpu(xin)
            and selective_scan_chunk(s, din, cfg.ssm_state) is not None)


def ssm_forward(p: Params, x: jnp.ndarray, cfg: ArchConfig,
                chunk: int = 128) -> jnp.ndarray:
    """x (B, S, D) -> (B, S, D).  The scan runs in the Pallas kernel where
    `_scan_in_kernel` says so, else in `_xla_scan` (module docstring)."""
    din = cfg.ssm_expand * x.shape[-1]
    xz = linear(x, p["w_in"])
    xin, z = xz[..., :din], xz[..., din:]
    if _scan_in_kernel(xin, cfg):
        y = _kernel_scan(p, xin)
    else:
        y = _xla_scan(p, xin, cfg, chunk)
    y = y + xin.astype(jnp.float32) * p["d_skip"]
    y = (y * jax.nn.silu(z.astype(jnp.float32))).astype(x.dtype)
    return linear(y, p["w_out"])


def init_ssm_state(cfg: ArchConfig, batch: int) -> Params:
    din = cfg.ssm_expand * cfg.d_model
    return {"h": jnp.zeros((batch, din, cfg.ssm_state), jnp.float32)}


def ssm_decode(p: Params, x: jnp.ndarray, state: Params, cfg: ArchConfig
               ) -> Tuple[jnp.ndarray, Params]:
    """x (B, D) one token; O(1) state update."""
    din = cfg.ssm_expand * cfg.d_model
    xz = linear(x, p["w_in"])
    xin, z = xz[..., :din], xz[..., din:]
    a, bx, csel = _discretize(p, xin)          # (B, din, N) x2, (B, N)
    h = a * state["h"] + bx
    y = jnp.einsum("bdn,bn->bd", h, csel)
    y = y + xin.astype(jnp.float32) * p["d_skip"]
    y = (y * jax.nn.silu(z.astype(jnp.float32))).astype(x.dtype)
    return linear(y, p["w_out"]), {"h": h}
