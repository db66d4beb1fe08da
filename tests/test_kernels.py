"""Pallas kernel validation: interpret-mode execution vs pure-jnp oracles,
shape/dtype sweeps, and LEO's waitcnt tracing through kernel DMA jaxprs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


def _rand(key, shape, dtype):
    return (jax.random.normal(key, shape) * 0.5).astype(dtype)


class TestFlashAttention:
    @pytest.mark.parametrize("s,h,kv,hd", [
        (128, 4, 4, 64),    # MHA
        (256, 4, 2, 32),    # GQA
        (128, 8, 1, 64),    # MQA
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_causal_matches_ref(self, s, h, kv, hd, dtype):
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = _rand(ks[0], (2, s, h, hd), dtype)
        k = _rand(ks[1], (2, s, kv, hd), dtype)
        v = _rand(ks[2], (2, s, kv, hd), dtype)
        out = ops.flash_attention_op(q, k, v, causal=True, block_q=64,
                                     block_k=64, interpret=True)
        expect = ref.flash_attention_ref(q, k, v, causal=True)
        tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(expect, np.float32),
                                   atol=tol, rtol=tol)

    def test_sliding_window(self):
        ks = jax.random.split(jax.random.PRNGKey(1), 3)
        q = _rand(ks[0], (1, 256, 2, 32), jnp.float32)
        k = _rand(ks[1], (1, 256, 2, 32), jnp.float32)
        v = _rand(ks[2], (1, 256, 2, 32), jnp.float32)
        out = ops.flash_attention_op(q, k, v, causal=True, window=64,
                                     block_q=32, block_k=32, interpret=True)
        expect = ref.flash_attention_ref(q, k, v, causal=True, window=64)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                                   atol=2e-5, rtol=2e-5)

    def test_matches_model_attention(self):
        """The model's chunked XLA path and the kernel agree."""
        from repro.models.attention import chunked_attention
        ks = jax.random.split(jax.random.PRNGKey(2), 3)
        q = _rand(ks[0], (2, 128, 4, 32), jnp.float32)
        k = _rand(ks[1], (2, 128, 2, 32), jnp.float32)
        v = _rand(ks[2], (2, 128, 2, 32), jnp.float32)
        out_kernel = ops.flash_attention_op(q, k, v, block_q=64, block_k=64,
                                            interpret=True)
        out_xla = chunked_attention(q, k, v, chunk=64)
        np.testing.assert_allclose(np.asarray(out_kernel),
                                   np.asarray(out_xla), atol=2e-5, rtol=2e-5)


# (b, s, h, kv, hd, window, block_q, block_k): GQA groups 1, 5 and 7,
# several query and key blocks in each, causal and windows of 70 and 100
# (not multiples of the blocks), square and unequal blocks.
FLASH_CASES = [(2, 128, 2, 2, 32, None, 32, 64),
               (1, 192, 5, 1, 32, 70, 32, 32),
               (2, 128, 7, 1, 32, None, 64, 32),
               (1, 256, 7, 1, 32, 100, 64, 32)]


def _flash_inputs(b, s, h, kv, hd, dtype=jnp.float32, seed=7):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (_rand(ks[0], (b, s, h, hd), dtype),
            _rand(ks[1], (b, s, kv, hd), dtype),
            _rand(ks[2], (b, s, kv, hd), dtype),
            _rand(ks[3], (b, s, h, hd), jnp.float32))


def _flash_grads(attn, q, k, v, w):
    """d/d(q, k, v) of sum(attn(q, k, v) * w)."""
    return jax.grad(lambda q, k, v: jnp.sum(
        attn(q, k, v).astype(jnp.float32) * w), (0, 1, 2))(q, k, v)


class TestFlashAttentionGrad:
    """The kernel pair through its custom VJP against the naive oracle and
    against the gradients of the model's chunked XLA path."""

    @staticmethod
    def _paths(window, bq, bk):
        from repro.models.attention import chunked_attention
        return {
            "kernel": lambda q, k, v: ops.flash_attention_op(
                q, k, v, window=window, block_q=bq, block_k=bk,
                interpret=True),
            "ref": lambda q, k, v: ref.flash_attention_ref(q, k, v,
                                                           window=window),
            "xla": lambda q, k, v: chunked_attention(q, k, v, chunk=32,
                                                     window=window)}

    @pytest.mark.parametrize("b,s,h,kv,hd,window,bq,bk", FLASH_CASES)
    def test_forward_matches_ref_and_xla_path(self, b, s, h, kv, hd, window,
                                              bq, bk):
        q, k, v, _ = _flash_inputs(b, s, h, kv, hd)
        paths = self._paths(window, bq, bk)
        with jax.default_matmul_precision("highest"):
            out = {name: np.asarray(f(q, k, v)) for name, f in paths.items()}
        for name in ("ref", "xla"):
            np.testing.assert_allclose(out["kernel"], out[name], atol=2e-5,
                                       rtol=2e-5, err_msg=name)

    @pytest.mark.parametrize("b,s,h,kv,hd,window,bq,bk", FLASH_CASES)
    def test_grad_matches_ref_and_xla_path(self, b, s, h, kv, hd, window,
                                           bq, bk):
        q, k, v, w = _flash_inputs(b, s, h, kv, hd)
        paths = self._paths(window, bq, bk)
        with jax.default_matmul_precision("highest"):
            grads = {name: _flash_grads(f, q, k, v, w)
                     for name, f in paths.items()}
        for name in ("ref", "xla"):
            for arg, g, e in zip("qkv", grads["kernel"], grads[name]):
                scale = float(jnp.max(jnp.abs(e)))
                np.testing.assert_allclose(
                    np.asarray(g), np.asarray(e), atol=1e-5 * scale,
                    rtol=1e-4, err_msg=f"d{arg} against {name}")

    def test_bf16_grad_matches_xla_path(self):
        """In bf16, the MXU operands of both paths (q, k, v, P, dO, dS)
        are bf16 and their sums f32: the gradients agree to a few bf16
        roundings."""
        q, k, v, w = _flash_inputs(1, 128, 4, 2, 32, jnp.bfloat16)
        paths = self._paths(None, 32, 32)
        grads = {name: _flash_grads(paths[name], q, k, v, w)
                 for name in ("kernel", "xla")}
        for arg, g, e in zip("qkv", grads["kernel"], grads["xla"]):
            g, e = (np.asarray(x, np.float32) for x in (g, e))
            np.testing.assert_allclose(g, e, atol=2e-2 * np.abs(e).max(),
                                       err_msg=f"d{arg}")

    @pytest.mark.parametrize("s,hd,groups,window,blocks", [
        (1024, 64, 7, None, (512, 512)),    # qwen2-0.5b
        (4096, 64, 5, 1024, (512, 512)),    # hymba-1.5b
        (768, 64, 1, None, (256, 256)),     # 512 does not divide S
        (4096, 64, 5, 512, (256, 256)),     # a window under two blocks
        (1024, 128, 32, None, (128, 128)),  # VMEM bounds the block
        (1000, 64, 1, None, None),          # no block divides S
        (1024, 64, 1, 128, None),           # a window under two blocks
        (1024, 512, 64, None, None),        # no block fits the VMEM
    ])
    def test_blocks_follow_shapes_window_and_vmem(self, s, hd, groups,
                                                  window, blocks):
        from repro.kernels.flash_attention import flash_attention_blocks
        got = flash_attention_blocks(s, hd, groups, window)
        assert got == blocks
        if got:
            assert all(s % blk == 0 for blk in got)


class TestAttentionPathByPlatform:
    @pytest.mark.parametrize("backend,devices,s,vd,kernel", [
        ("tpu", 1, 1024, 64, True),
        ("cpu", 1, 1024, 64, False),    # the CPU tests, dry-run, hillclimb
        ("tpu", 4, 1024, 64, False),    # a sharded step: no partitioning
        ("tpu", 1, 1000, 64, False),    # no kernel block divides S
        ("tpu", 1, 1024, 32, False),    # a value width unlike the key's
    ])
    def test_attention_path_follows_backend_devices_and_shapes(
            self, monkeypatch, backend, devices, s, vd, kernel):
        from jax.sharding import AbstractMesh, AxisType
        from repro.configs.lm_archs import QWEN2_0_5B
        from repro.models import attention
        monkeypatch.setattr(jax, "default_backend", lambda: backend)
        mesh = AbstractMesh((devices, 1), ("data", "model"),
                            axis_types=(AxisType.Auto,) * 2)
        q = jax.ShapeDtypeStruct((1, s, 14, 64), jnp.bfloat16)
        k = jax.ShapeDtypeStruct((1, s, 2, 64), jnp.bfloat16)
        v = jax.ShapeDtypeStruct((1, s, 2, vd), jnp.bfloat16)
        with jax.sharding.use_abstract_mesh(mesh):
            chosen = []
            jax.jit(lambda q, k, v: chosen.append(
                attention._attend_in_kernel(q, k, v, QWEN2_0_5B))
            ).trace(q, k, v)
        assert chosen == [kernel]


class TestRmsnorm:
    @pytest.mark.parametrize("r,d", [(32, 128), (64, 256), (8, 512)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("variant", ["baseline", "pipelined"])
    def test_matches_ref(self, r, d, dtype, variant):
        ks = jax.random.split(jax.random.PRNGKey(0), 2)
        x = _rand(ks[0], (r, d), dtype)
        scale = 1.0 + 0.1 * _rand(ks[1], (d,), jnp.float32)
        fn = ops.rmsnorm_baseline_op if variant == "baseline" \
            else ops.rmsnorm_op
        out = fn(x, scale, block_rows=8, interpret=True)
        expect = ref.rmsnorm_ref(x, scale)
        tol = 3e-2 if dtype == jnp.bfloat16 else 1e-5
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(expect, np.float32),
                                   atol=tol, rtol=tol)

    def test_leo_traces_rmsnorm_dma(self):
        """HipKittens case-study analogue: LEO's jaxpr front-end must trace
        mem_waitcnt edges through the pipelined kernel's DMA semaphores."""
        from repro.core import (
            EdgeKind, TPU_V5E, analyze_module, from_function,
        )
        from repro.kernels.rmsnorm import rmsnorm_pipelined

        x = jnp.zeros((32, 128), jnp.float32)
        scale = jnp.ones((128,), jnp.float32)
        module = from_function(
            lambda a, b: rmsnorm_pipelined(a, b, interpret=True), x, scale)
        # the pallas_call body must contain counted-semaphore sync ops
        sync_ops = [i for i in module.all_instructions()
                    if i.sync.sets or i.sync.waits]
        assert sync_ops, "expected dma_start/dma_wait in kernel jaxpr"
        an = analyze_module(module, TPU_V5E)
        waitcnt_edges = [e for e in an.graph.edges
                         if e.kind is EdgeKind.MEM_WAITCNT]
        assert waitcnt_edges, "LEO must trace through DMA semaphores"


class TestMlstmKernel:
    @pytest.mark.parametrize("s,h,hd,chunk", [(64, 2, 32, 16),
                                              (128, 1, 64, 32)])
    def test_matches_sequential_ref(self, s, h, hd, chunk):
        ks = jax.random.split(jax.random.PRNGKey(3), 5)
        b = 2
        q = _rand(ks[0], (b, s, h, hd), jnp.float32)
        k = _rand(ks[1], (b, s, h, hd), jnp.float32) / (hd ** 0.5)
        v = _rand(ks[2], (b, s, h, hd), jnp.float32)
        log_i = _rand(ks[3], (b, s, h), jnp.float32)
        log_f = jax.nn.log_sigmoid(_rand(ks[4], (b, s, h), jnp.float32) + 2.0)
        out = ops.mlstm_chunkwise_op(q, k, v, log_i, log_f, chunk=chunk,
                                     interpret=True)
        expect = ref.mlstm_ref(q, k, v, log_i, log_f)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                                   atol=1e-4, rtol=1e-4)


def _scan_inputs(b, s, din, n, seed=6):
    """The model's SSM parameters (f32) and a scanned input."""
    from repro.configs.base import ArchConfig
    from repro.models import ssm
    cfg = ArchConfig(name="scan", family="ssm", n_layers=1,
                     d_model=din // 2, n_heads=1, n_kv_heads=1, d_ff=0,
                     vocab_size=8, ssm_state=n, ssm_expand=2)
    key = jax.random.PRNGKey(seed)
    p = ssm.init_ssm(key, cfg, jnp.float32)
    xin = _rand(jax.random.fold_in(key, 1), (b, s, din), jnp.float32)
    return cfg, p, xin


def _scan_terms(p, xin):
    """dt, x, B, C and A as the model feeds the selective-scan kernel."""
    from repro.models.layers import linear
    return (jax.nn.softplus(xin * p["w_dt"]), xin,
            linear(xin, p["w_b"]), linear(xin, p["w_c"]),
            -jnp.exp(p["a_log"]))


# (b, s, din, n, chunk): several chunks in every case; one, two (1280 =
# 2 x 640) and three (2304 = 3 x 768) d_inner tiles; chunk None is the
# kernel's own choice.
SCAN_CASES = [(2, 64, 256, 16, 16), (2, 32, 1280, 8, 8),
              (2, 48, 2304, 16, 16), (3, 128, 128, 16, None)]


class TestSelectiveScan:
    @pytest.mark.parametrize("b,s,din,n,chunk", SCAN_CASES)
    def test_forward_matches_xla_path_and_sequential_ref(self, b, s, din, n,
                                                         chunk):
        from repro.models import ssm
        cfg, p, xin = _scan_inputs(b, s, din, n)
        terms = _scan_terms(p, xin)
        with jax.default_matmul_precision("highest"):
            out = ops.selective_scan_op(*terms, chunk=chunk, interpret=True)
            xla = ssm._xla_scan(p, xin, cfg, chunk=16)
            seq = ref.selective_scan_ref(*terms)
        for expect in (xla, seq):
            np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                                       atol=1e-4, rtol=1e-4)

    @pytest.mark.parametrize("b,s,din,n,chunk", SCAN_CASES)
    def test_grad_matches_xla_path(self, b, s, din, n, chunk):
        """Through the custom VJP, the gradient in dt, x, B, C and A is
        the one autodiff of the plain recurrence gives."""
        cfg, p, xin = _scan_inputs(b, s, din, n)
        terms = _scan_terms(p, xin)
        w = _rand(jax.random.PRNGKey(9), (b, s, din), jnp.float32)
        args = tuple(range(5))

        def loss(scan):
            return lambda *t: jnp.sum(scan(*t) * w)
        with jax.default_matmul_precision("highest"):
            got = jax.grad(loss(lambda *t: ops.selective_scan_op(
                *t, chunk=chunk, interpret=True)), args)(*terms)
            want = jax.grad(loss(ref.selective_scan_ref), args)(*terms)
        for name, g, e in zip(("dt", "x", "B", "C", "A"), got, want):
            scale = float(jnp.max(jnp.abs(e)))
            np.testing.assert_allclose(np.asarray(g), np.asarray(e),
                                       atol=1e-4 * scale, rtol=1e-4,
                                       err_msg=name)

    @pytest.mark.parametrize("b,s,din,n,chunk", SCAN_CASES[:2])
    def test_model_grads_match_xla_path(self, b, s, din, n, chunk):
        """The SSM's parameters and input get the same gradient through
        the kernel as through the model's XLA chunk scan."""
        from repro.models import ssm
        cfg, p, xin = _scan_inputs(b, s, din, n)
        w = _rand(jax.random.PRNGKey(9), (b, s, din), jnp.float32)
        with jax.default_matmul_precision("highest"):
            got = jax.grad(lambda p, x: jnp.sum(ssm._kernel_scan(
                p, x, interpret=True) * w), (0, 1))(p, xin)
            want = jax.grad(lambda p, x: jnp.sum(ssm._xla_scan(
                p, x, cfg, chunk=16) * w), (0, 1))(p, xin)
        flat_got = jax.tree_util.tree_leaves_with_path(got)
        flat_want = jax.tree_util.tree_leaves(want)
        for (path, g), e in zip(flat_got, flat_want):
            scale = float(jnp.max(jnp.abs(e))) or 1.0
            np.testing.assert_allclose(np.asarray(g), np.asarray(e),
                                       atol=1e-4 * scale, rtol=1e-4,
                                       err_msg=jax.tree_util.keystr(path))

    @pytest.mark.parametrize("impl", ["xla", "chunk_fused", "pallas_fused"])
    def test_ssm_impl_shapes_the_xla_scan(self, impl):
        """Every `ssm_impl` scans to the default's values, and only
        "pallas_fused" tags the chunk scan as a region LEO prices as one
        kernel."""
        from repro.core import parse_hlo
        from repro.models import ssm
        from repro.models.flags import FUSED_REGION_MARK, flags
        cfg, p, xin = _scan_inputs(2, 64, 256, 16)

        def scan():  # a new jit each time: the flag is read while tracing
            return jax.jit(lambda p, x: ssm._xla_scan(p, x, cfg, chunk=16))
        with jax.default_matmul_precision("highest"):
            want = scan()(p, xin)
            with flags(ssm_impl=impl):
                got = scan()(p, xin)
                hlo = scan().lower(p, xin).compile().as_text()
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-6, rtol=1e-6)
        marked = [i for i in parse_hlo(hlo).all_instructions()
                  if FUSED_REGION_MARK in i.op_name]
        assert bool(marked) == (impl == "pallas_fused")

    @pytest.mark.parametrize("s,din,n,chunk", [
        (4096, 3200, 16, 64),   # hymba-1.5b: the backward's VMEM bounds it
        (256, 128, 8, 256),
        (96, 256, 16, 32),
        (100, 256, 16, None),   # no chunk divides the sequence
        (256, 200, 16, None),   # d_inner is not lane-aligned
    ])
    def test_chunk_follows_shapes_and_vmem(self, s, din, n, chunk):
        from repro.kernels.ssm_scan import selective_scan_chunk
        assert selective_scan_chunk(s, din, n) == chunk


@pytest.mark.parametrize("name", ["attention_impl", "ssm_impl", "moe_impl"])
def test_unknown_flag_value_is_refused(name):
    """A value no path reads (say a typo in the dry-run's `--flags`) raises,
    and leaves the flags as they were."""
    from repro.models.flags import ModelFlags, flags, get_flags
    with pytest.raises(ValueError, match=name):
        with flags(**{name: "pallas"}):
            pass
    assert get_flags() == ModelFlags()


class TestScanPathByPlatform:
    def test_cpu_lowers_hymba_step_without_the_kernel(self):
        """On the CPU backend the hymba-width step holds no Pallas call."""
        import dataclasses
        from repro.configs.lm_archs import HYMBA_1_5B
        from repro.runtime.steps import init_train_state, make_train_step
        cfg = dataclasses.replace(HYMBA_1_5B, n_layers=1)
        state = jax.eval_shape(lambda: init_train_state(
            jax.random.PRNGKey(0), cfg))
        batch = {k: jax.ShapeDtypeStruct((1, 1024), jnp.int32)
                 for k in ("tokens", "labels")}
        text = jax.jit(make_train_step(cfg)).lower(state, batch).as_text()
        assert "tpu_custom_call" not in text
        assert "selective_scan" not in text

    @pytest.mark.parametrize("backend,devices,s,din,kernel", [
        ("tpu", 1, 256, 256, True),
        ("cpu", 1, 256, 256, False),    # the CPU tests, dry-run, hillclimb
        ("tpu", 4, 256, 256, False),    # a sharded step: no partitioning
        ("tpu", 1, 100, 256, False),    # no kernel chunk divides S
        ("tpu", 1, 256, 200, False),    # d_inner is not lane-aligned
    ])
    def test_scan_path_follows_backend_devices_and_shapes(
            self, monkeypatch, backend, devices, s, din, kernel):
        from jax.sharding import AbstractMesh, AxisType
        from repro.models import ssm
        cfg, _, _ = _scan_inputs(1, 8, 256, 16)
        monkeypatch.setattr(jax, "default_backend", lambda: backend)
        mesh = AbstractMesh((devices, 1), ("data", "model"),
                            axis_types=(AxisType.Auto,) * 2)
        xin = jax.ShapeDtypeStruct((1, s, din), jnp.bfloat16)
        with jax.sharding.use_abstract_mesh(mesh):
            chosen = []
            jax.jit(lambda x: chosen.append(ssm._scan_in_kernel(x, cfg))
                    ).trace(xin)
        assert chosen == [kernel]


class TestSlstmKernel:
    @pytest.mark.parametrize("s,d,chunk", [(32, 64, 8), (64, 128, 16)])
    def test_matches_sequential_ref(self, s, d, chunk):
        ks = jax.random.split(jax.random.PRNGKey(5), 2)
        b = 2
        xg = _rand(ks[0], (b, s, 4 * d), jnp.float32)
        r = _rand(ks[1], (d, 4 * d), jnp.float32) * 0.1
        out = ops.slstm_scan_op(xg, r, chunk=chunk, interpret=True)
        expect = ref.slstm_scan_ref(xg, r)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                                   atol=1e-4, rtol=1e-4)
