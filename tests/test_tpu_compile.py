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
import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import parse_hlo
from repro.kernels import ops


@pytest.fixture(scope="module")
def v5e_2x2():
    """A described v5e:2x2, with the persistent compilation cache off: a
    program compiled for a described chip is written to it but cannot be
    read back without one."""
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
        yield topo
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_on)
        if saved_log_dir is None:
            os.environ.pop("TPU_LOG_DIR", None)
        else:
            os.environ["TPU_LOG_DIR"] = saved_log_dir


@pytest.fixture(scope="module")
def one_chip(v5e_2x2):
    """One device of the described v5e:2x2."""
    return SingleDeviceSharding(v5e_2x2.devices[0])


def _compile(fn, shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


BF16, F32 = jnp.bfloat16, jnp.float32

# Each kernel at a real model width: flash at qwen2-0.5b's heads (14 q / 2
# kv, head_dim 64), and its forward and backward pair at the one-chip
# cells' rows (qwen2-0.5b 4 x 1024, causal; hymba-1.5b 1 x 4096, 25 q / 5
# kv, window 1024), rmsnorm at qwen2-0.5b's d_model, the selective scan at
# hymba-1.5b's d_inner 3200 and state 16 over its 4096-token rows (the
# backward with the forward it differentiates), mLSTM (4 heads of 192) and
# sLSTM (d 768) at xlstm-125m's.
FLASH_ARGS = {"qwen": ((4, 1024, 14, 2, 64), None),
              "hymba": ((1, 4096, 25, 5, 64), 1024)}


def _flash_case(cell, grad):
    (b, s, h, kv, hd), window = FLASH_ARGS[cell]

    def attend(q, k, v):
        return ops.flash_attention_op(q, k, v, window=window,
                                      interpret=False)
    fn = attend if not grad else jax.grad(
        lambda *a: attend(*a).astype(F32).sum(), argnums=(0, 1, 2))
    return fn, [((b, s, h, hd), BF16), ((b, s, kv, hd), BF16),
                ((b, s, kv, hd), BF16)]


SELECTIVE_SCAN_ARGS = [((1, 4096, 3200), F32), ((1, 4096, 3200), BF16),
                       ((1, 4096, 16), F32), ((1, 4096, 16), F32),
                       ((3200, 16), F32)]


def _selective_scan_grad(*args):
    loss = lambda *a: ops.selective_scan_op(*a, interpret=False).sum()
    return jax.grad(loss, argnums=tuple(range(len(args))))(*args)


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
    "selective_scan_fwd": (
        lambda *a: ops.selective_scan_op(*a, interpret=False),
        SELECTIVE_SCAN_ARGS),
    "selective_scan_bwd": (_selective_scan_grad, SELECTIVE_SCAN_ARGS),
    "flash_attention_fwd_qwen": _flash_case("qwen", grad=False),
    "flash_attention_bwd_qwen": _flash_case("qwen", grad=True),
    "flash_attention_fwd_hymba": _flash_case("hymba", grad=False),
    "flash_attention_bwd_hymba": _flash_case("hymba", grad=True),
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


def _train_step(arch: str, layers: int, batch: int, seq_len: int,
                shardings):
    """An architecture's train step at its widths, cut to `layers` layers,
    and its arguments' shapes, placed by `shardings(cfg, state)`, which
    gives the state's and the batch's shardings."""
    from repro.configs import get_config
    from repro.runtime.steps import init_train_state, make_train_step
    cfg = dataclasses.replace(get_config(arch), n_layers=layers)
    state = jax.eval_shape(lambda: init_train_state(jax.random.PRNGKey(0),
                                                    cfg))
    batch = {k: jax.ShapeDtypeStruct((batch, seq_len), jnp.int32)
             for k in ("tokens", "labels")}
    state_sh, batch_sh = shardings(cfg, state)
    place = lambda a, sh: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh)
    args = (jax.tree.map(place, state, state_sh),
            {k: place(v, batch_sh[k]) for k, v in batch.items()})
    return jax.jit(make_train_step(cfg), donate_argnums=(0,)), args


def _hymba_step(layers: int, batch: int, seq_len: int, shardings):
    return _train_step("hymba-1.5b", layers, batch, seq_len, shardings)


def _on(sharding):
    return lambda cfg, state: (jax.tree.map(lambda _: sharding, state),
                               {"tokens": sharding, "labels": sharding})


def _kernels(module):
    return [i for i in module.all_instructions()
            if i.attributes.get("custom_call_target", "").strip('"')
            == "tpu_custom_call"]


def _ssm_carries(text, d_inner=3200):
    """The chunk scans' `while` loops over an f32[batch, d_inner, state]
    carry (hymba-1.5b's state is 16)."""
    return [line for line in text.splitlines()
            if " while(" in line and f",{d_inner},16]" in line]


def _kernel_scopes(module, prefix):
    """{kernel name: the model scopes of its calls} for the Mosaic kernels
    whose name starts with `prefix`."""
    from repro.core.cct import InstructionScopes
    from repro.models.scopes import MODEL_SCOPES
    scope = InstructionScopes(module, MODEL_SCOPES)
    found = {}
    for i in _kernels(module):
        name = i.name.rsplit(".", 1)[0]
        if name.startswith(prefix):
            found.setdefault(name, set()).add(scope(i))
    return found


def test_hymba_step_scans_ssm_in_the_kernel(one_chip, monkeypatch):
    """On one chip, in a process whose default backend is the TPU, each
    layer's SSM scan is the selective-scan kernel pair, inside the model's
    `ssm` scope, and no chunk scan over an f32[batch, d_inner, state]
    carry is left."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    step, args = _hymba_step(1, 1, 256, _on(one_chip))
    text = step.lower(*args).compile().as_text()
    scopes = _kernel_scopes(parse_hlo(text), "selective_scan")
    assert scopes == {"selective_scan_bwd": {"ssm"},
                      "selective_scan_fwd": {"ssm"}}, scopes
    assert not _ssm_carries(text)


def _attention_carries(text):
    """`chunked_attention`'s key-chunk `while` loops: their carry holds
    the f32 accumulator [batch, heads, 512, 64]."""
    return [line for line in text.splitlines()
            if " while(" in line and ",512,64]" in line and "f32[" in line]


FLASH_KERNELS = {"flash_attention_bwd_dkv": {"attn"},
                 "flash_attention_bwd_dq": {"attn"},
                 "flash_attention_fwd": {"attn"}}


@pytest.mark.parametrize("arch,batch,seq_len", [("qwen2-0.5b", 4, 1024),
                                                ("hymba-1.5b", 1, 4096)])
def test_one_chip_step_attends_in_the_flash_kernels(arch, batch, seq_len,
                                                    one_chip, monkeypatch):
    """On one chip, in a process whose default backend is the TPU, a layer
    of each one-chip cell's width at the cell's rows attends in the flash
    kernel pair, inside the model's `attn` scope, and no key-chunk `while`
    of `chunked_attention` is left."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    step, args = _train_step(arch, 1, batch, seq_len, _on(one_chip))
    text = step.lower(*args).compile().as_text()
    scopes = _kernel_scopes(parse_hlo(text), "flash_attention")
    assert scopes == FLASH_KERNELS, scopes
    assert not _attention_carries(text)


def test_sharded_step_keeps_chunked_attention(v5e_2x2, monkeypatch):
    """qwen2-0.5b's step with `launch/train.py`'s shardings over the whole
    v5e:2x2 attends in `chunked_attention`'s key-chunk loops and holds no
    flash kernel: a Mosaic kernel cannot be partitioned across devices."""
    from repro.launch.mesh import make_host_mesh
    from repro.launch.train import train_shardings
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = make_host_mesh(2, devices=v5e_2x2.devices)
    step, args = _train_step(
        "qwen2-0.5b", 1, 4, 1024,
        lambda cfg, state: train_shardings(mesh, cfg, state))
    text = step.lower(*args).compile().as_text()
    assert not _kernel_scopes(parse_hlo(text), "flash_attention")
    assert _attention_carries(text)


def test_kernel_scan_cuts_hymba_step_temporaries(one_chip, monkeypatch):
    """At hymba-1.5b's 4096-token row, one layer, the compiler plans under
    0.4x the temporaries for the kernel scan that it plans for the XLA
    chunk scan (3.80 GB against 13.03 GB when written): the kernel never
    holds the B x S x d_inner x N terms in HBM."""
    temps = {}
    for backend in ("tpu", "cpu"):   # the kernel scan, then the XLA scan
        monkeypatch.setattr(jax, "default_backend", lambda b=backend: b)
        step, args = _hymba_step(1, 1, 4096, _on(one_chip))
        temps[backend] = step.lower(*args).compile().memory_analysis(
            ).temp_size_in_bytes
    assert temps["tpu"] < 0.4 * temps["cpu"], temps


@pytest.mark.parametrize("model_parallel", [1, 2])
def test_sharded_hymba_step_keeps_the_xla_scan(model_parallel, v5e_2x2,
                                               monkeypatch):
    """With `launch/train.py`'s shardings over the whole v5e:2x2, the step
    compiles: it scans in XLA, since a Mosaic kernel cannot be partitioned
    across devices."""
    from repro.launch.mesh import make_host_mesh
    from repro.launch.train import train_shardings
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = make_host_mesh(model_parallel, devices=v5e_2x2.devices)
    step, args = _hymba_step(
        1, 4, 256, lambda cfg, state: train_shardings(mesh, cfg, state))
    text = step.lower(*args).compile().as_text()
    assert not _kernels(parse_hlo(text))
    assert _ssm_carries(text, 3200 // model_parallel)


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
