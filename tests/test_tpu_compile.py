"""Compile for a described TPU v5e, without a chip.

The TPU compiler is installed beside the CPU backend, and it compiles for a
topology that is described and not attached.  That catches what interpret
mode cannot: Mosaic refusing a kernel's tiling or VMEM budget, and the
shape of the HLO the chip's compiler emits, where every matmul is a
`convolution`.  Nothing here runs on a device.

The topology is described inside a fixture, never while a module is
imported: only one process at a time may load the TPU library, and pytest
workers import every test file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import parse_hlo
from repro.kernels import ops


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described v5e:2x2, with the persistent compilation
    cache off: a program compiled for a described chip is written to it
    but cannot be read back without one."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    saved_log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"  # the compiler logs nowhere
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - any failure means no TPU lib
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_on)
        if saved_log_dir is None:
            os.environ.pop("TPU_LOG_DIR", None)
        else:
            os.environ["TPU_LOG_DIR"] = saved_log_dir


def _compile(fn, shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


BF16, F32 = jnp.bfloat16, jnp.float32

# Each kernel at a real model width: flash at qwen2-0.5b's heads (14 q / 2
# kv, head_dim 64), rmsnorm at its d_model, ssm at hymba-1.5b's d_inner 3200
# and state 16, mLSTM (4 heads of 192) and sLSTM (d 768) at xlstm-125m's.
KERNELS = {
    "flash_attention": (
        lambda q, k, v: ops.flash_attention_op(q, k, v, interpret=False),
        [((1, 1024, 14, 64), BF16), ((1, 1024, 2, 64), BF16),
         ((1, 1024, 2, 64), BF16)]),
    "rmsnorm_baseline": (
        lambda x, s: ops.rmsnorm_baseline_op(x, s, interpret=False),
        [((1024, 896), BF16), ((896,), F32)]),
    "rmsnorm_pipelined": (
        lambda x, s: ops.rmsnorm_op(x, s, interpret=False),
        [((1024, 896), BF16), ((896,), F32)]),
    "ssm_scan": (
        lambda a, bx, c: ops.ssm_scan_op(a, bx, c, chunk=64,
                                         interpret=False),
        [((1, 256, 3200, 16), F32), ((1, 256, 3200, 16), F32),
         ((1, 256, 16), F32)]),
    "mlstm_chunkwise": (
        lambda q, k, v, i, f: ops.mlstm_chunkwise_op(q, k, v, i, f, chunk=64,
                                                     interpret=False),
        [((2, 512, 4, 192), F32)] * 3 + [((2, 512, 4), F32)] * 2),
    "slstm_scan": (
        lambda xg, r: ops.slstm_scan_op(xg, r, chunk=64, interpret=False),
        [((4, 256, 3072), F32), ((768, 3072), F32)]),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_with_mosaic(name, one_chip):
    fn, shapes = KERNELS[name]
    compiled = _compile(fn, shapes, one_chip)
    assert "tpu_custom_call" in compiled.as_text()


def _attention_grad(q, k):
    scores = lambda k: jnp.einsum("bqhd,bkhd->bhqk", q, k,
                                  preferred_element_type=F32).sum()
    return jax.grad(scores)(k)


# The convolution shapes a train step compiles to on the chip.
CONVOLUTIONS = {
    "plain": (lambda x, w: x @ w,
              [((1024, 2048), BF16), ((2048, 4096), BF16)], "bf_io->bf"),
    "transposed_operand": (lambda x, w: jnp.einsum("kb,kf->bf", x, w),
                           [((2048, 1024), BF16), ((2048, 4096), BF16)],
                           "fb_io->bf"),
    "batch_as_window": (
        lambda x, w: jax.grad(lambda w: jnp.einsum(
            "bsd,df->bsf", x, w).astype(F32).sum())(w),
        [((4, 1024, 896), BF16), ((896, 4864), BF16)], "window={size=4}"),
    "attention_scores": (
        lambda q, k: jnp.einsum("bqhd,bkhd->bhqk", q, k,
                                preferred_element_type=F32),
        [((4, 512, 14, 64), BF16)] * 2, "lhs_dilate=4x14"),
    "attention_values": (
        lambda p, v: jnp.einsum("bhqk,bkhd->bqhd", p, v),
        [((4, 14, 512, 512), BF16), ((4, 512, 14, 64), BF16)],
        "lhs_dilate=4x14"),
    "attention_grad": (_attention_grad, [((4, 512, 14, 64), BF16)] * 2,
                       "lhs_dilate=4x14"),
}


@pytest.mark.parametrize("name", sorted(CONVOLUTIONS))
def test_convolution_flops_match_chip_compiler(name, one_chip):
    fn, shapes, pattern = CONVOLUTIONS[name]
    compiled = _compile(fn, shapes, one_chip)
    text = compiled.as_text()
    convs = [line for line in text.splitlines() if " convolution(" in line]
    assert convs and any(pattern in line for line in convs), convs
    xla = compiled.cost_analysis()["flops"]
    assert parse_hlo(text).total_flops() == pytest.approx(xla, rel=0.05)
