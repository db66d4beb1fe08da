"""HLO parser unit tests: shapes, instructions, costs, trip counts,
collectives, metadata — validated against both fixtures and a real compiled
XLA program."""
import pytest

from repro.core.hlo_parser import _valid_taps, parse_hlo, parse_shape
from repro.core.isa import OpClass, ShapeInfo, SyncKind
from repro.core.collectives import (
    collective_operand_bytes,
    collective_summary,
    total_collective_bytes,
)


class TestShapeParsing:
    def test_array(self):
        s = parse_shape("bf16[4,128]{1,0}")
        assert s.dtype == "bf16" and s.dims == (4, 128)
        assert s.byte_size == 4 * 128 * 2

    def test_layout_with_tiling(self):
        s = parse_shape("f32[16,1024]{1,0:T(8,128)}")
        assert s.dims == (16, 1024) and s.byte_size == 16 * 1024 * 4

    def test_scalar(self):
        s = parse_shape("pred[]")
        assert s.dtype == "pred" and s.dims == () and s.num_elements == 1

    def test_tuple(self):
        s = parse_shape("(f32[2,4]{1,0}, s32[])")
        assert s.is_tuple and len(s.elements) == 2
        assert s.byte_size == 2 * 4 * 4 + 4

    def test_token(self):
        assert parse_shape("token[]").byte_size == 0


class TestFixtureParsing:
    def test_structure(self, async_hlo_text):
        mod = parse_hlo(async_hlo_text, hints={"total_devices": 8})
        assert mod.entry == "main.1"
        assert set(mod.computations) == {
            "add.1", "body.1", "cond.1", "main.1"}
        assert mod.computations["body.1"].kind == "loop_body"
        assert mod.computations["cond.1"].kind == "loop_cond"

    def test_trip_count_from_condition(self, async_hlo_text):
        mod = parse_hlo(async_hlo_text)
        loop = mod.computations["main.1"].get("loop")
        assert loop.trip_count == 5

    def test_async_pair_sync_info(self, async_hlo_text):
        mod = parse_hlo(async_hlo_text)
        main = mod.computations["main.1"]
        start = main.get("ag-start")
        done = main.get("ag-done")
        assert start.op_class is OpClass.SYNC_SET
        assert start.sync.kind is SyncKind.BARRIER
        assert start.sync.sets == ("ag-start",)
        assert done.op_class is OpClass.SYNC_WAIT
        assert done.sync.waits == ("ag-start",)

    def test_token_sync_info(self, async_hlo_text):
        mod = parse_hlo(async_hlo_text)
        tok = mod.computations["main.1"].get("tok0")
        assert tok.sync.kind is SyncKind.TOKEN

    def test_metadata(self, async_hlo_text):
        mod = parse_hlo(async_hlo_text)
        dot = mod.computations["main.1"].get("dot.1")
        assert dot.op_name == "jit(step)/model/layer/mlp/dot_general"
        assert dot.source_file == "model.py" and dot.source_line == 42

    def test_dot_flops(self, async_hlo_text):
        mod = parse_hlo(async_hlo_text)
        dot = mod.computations["main.1"].get("dot.1")
        assert dot.flops == 2 * 128 * 128 * 128

    def test_collective_bytes(self, async_hlo_text):
        mod = parse_hlo(async_hlo_text, hints={"total_devices": 8})
        start = mod.computations["main.1"].get("ag-start")
        # all-gather over groups of 4: out_bytes * (n-1)/n
        assert start.comm_bytes == pytest.approx(
            128 * 128 * 4 * 3 / 4)

    def test_trip_aware_flops(self, async_hlo_text):
        mod = parse_hlo(async_hlo_text)
        # multiply in loop body: 128*128 flops x 5 trips contributes
        # body: multiply (128*128) + iv add (1); cond: compare (1)
        diff = mod.total_flops(True) - mod.total_flops(False)
        assert diff == pytest.approx(4 * (128 * 128 + 2))  # 4 extra trips


class TestAgainstRealXLA:
    def test_flops_match_cost_analysis(self, small_compiled_step):
        ca = small_compiled_step.cost_analysis()
        mod = parse_hlo(small_compiled_step.as_text())
        # XLA counts loop bodies once; our trip-unaware total should agree
        # within 20% (fusion/layout noise).
        ours = mod.total_flops(trip_aware=False)
        assert ours == pytest.approx(ca["flops"], rel=0.2)

    def test_trip_aware_exceeds_xla(self, small_compiled_step):
        mod = parse_hlo(small_compiled_step.as_text())
        assert mod.total_flops(True) > 2.0 * mod.total_flops(False)

    def test_all_instructions_have_shapes(self, small_compiled_step):
        mod = parse_hlo(small_compiled_step.as_text())
        for instr in mod.all_instructions():
            assert isinstance(instr.shape, ShapeInfo)


def _taps_by_enumeration(in_n, out_n, k_n, stride, pad_lo, lhs_dilate,
                         rhs_dilate):
    """The (output, kernel) pairs that hit a real input element, counted
    one by one as XLA's cost analysis does."""
    count = 0
    for k in range(k_n):
        for o in range(out_n):
            u = o * stride - pad_lo + k * rhs_dilate
            if u % lhs_dilate == 0 and 0 <= u // lhs_dilate < in_n:
                count += 1
    return count


_CONV_HLO = """\
HloModule conv_fixture

ENTRY %main (a: bf16[4,512,14,64], b: bf16[4,14,512,512], c: bf16[896,896,1], d: bf16[4,1024,896]) -> (f32[4,14,512,64], bf16[4,1024,896]) {
  %a = bf16[4,512,14,64]{1,3,2,0} parameter(0)
  %b = bf16[4,14,512,512]{2,3,1,0} parameter(1)
  %c = bf16[896,896,1]{1,0,2} parameter(2)
  %d = bf16[4,1024,896]{1,2,0} parameter(3)
  %attn = f32[4,14,512,64]{2,3,1,0} convolution(%a, %b), window={size=4x14 stride=4x14 pad=3_3x13_13 lhs_dilate=3x13 rhs_reversal=1x1}, dim_labels=0f1b_01oi->01fb
  %proj = bf16[4,1024,896]{1,2,0} convolution(%c, %d), window={size=4 pad=3_3 rhs_reversal=1}, dim_labels=bf0_0oi->0fb
  ROOT %out = (f32[4,14,512,64], bf16[4,1024,896]) tuple(%attn, %proj)
}
"""


# XLA prints an index comment before every fifth operand of a long list.
LONG_OPERANDS_HLO = """\
HloModule long_operands

ENTRY %main (a: f32[4], b: f32[4], c: f32[4], d: f32[4], e: f32[4], f: f32[64]) -> (f32[4], f32[4], f32[4], f32[4], f32[4], f32[64]) {
  %a = f32[4]{0} parameter(0)
  %b = f32[4]{0} parameter(1)
  %c = f32[4]{0} parameter(2)
  %d = f32[4]{0} parameter(3)
  %e = f32[4]{0} parameter(4)
  %f = f32[64]{0} parameter(5)
  %concatenate.1 = f32[84]{0} concatenate(%a, %b, %c, %d, %e, /*index=5*/%f), dimensions={0}
  ROOT %tuple.1 = (f32[4], f32[4], f32[4], f32[4], f32[4], /*index=5*/f32[64]) tuple(%a, %b, %c, %d, %e, /*index=5*/%f)
}
"""


class TestOperandParsing:
    def test_operands_behind_index_comments_are_kept(self):
        main = parse_hlo(LONG_OPERANDS_HLO).entry_computation
        assert main.get("tuple.1").operands == ("a", "b", "c", "d", "e", "f")
        concat = main.get("concatenate.1")
        assert concat.operands[-1] == "f"
        assert concat.bytes_read == (5 * 4 + 64) * 4


class TestConvolutionFlops:
    """The TPU compiler's convolutions: each output of a window that the
    padding and lhs dilation leave one real tap costs one multiply-add per
    (input feature, output feature, batch) triple, not a whole window."""

    @pytest.mark.parametrize("seed", range(4))
    def test_valid_taps_match_enumeration(self, seed):
        import random
        rng = random.Random(seed)
        for _ in range(500):
            case = (rng.randint(1, 12), rng.randint(1, 12),
                    rng.randint(1, 12), rng.randint(1, 5),
                    rng.randint(-4, 6), rng.randint(1, 5),
                    rng.randint(1, 4))
            assert _valid_taps(*case) == _taps_by_enumeration(*case), case

    def test_base_dilated_attention_gradient(self):
        conv = parse_hlo(_CONV_HLO).entry_computation.get("attn")
        # one tap per output along both windowed dims (batch 4, heads 14)
        assert conv.flops == 2 * 512 * 512 * 64 * 4 * 14

    def test_padded_batch_window(self):
        conv = parse_hlo(_CONV_HLO).entry_computation.get("proj")
        # a 896 x 896 weight gradient over 4 x 1024 tokens
        assert conv.flops == 2 * 896 * 896 * 4 * 1024


class TestCollectiveExtraction:
    def test_operand_bytes_prescription(self, async_hlo_text):
        stats = collective_operand_bytes(async_hlo_text)
        assert "all-gather" in stats
        assert stats["all-gather"].op_count == 1
        assert stats["all-gather"].operand_bytes == 128 * 128 * 4

    def test_total_wire_bytes(self, async_hlo_text):
        mod = parse_hlo(async_hlo_text, hints={"total_devices": 8})
        assert total_collective_bytes(mod) > 0

    def test_collective_in_loop_scales_with_trips(self):
        text = """\
HloModule loop_coll
%add.9 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %r = f32[] add(%a, %b)
}
%body.9 (p: (s32[], f32[64])) -> (s32[], f32[64]) {
  %p = (s32[], f32[64]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %c1 = s32[] constant(1)
  %i2 = s32[] add(%i, %c1)
  %x = f32[64] get-tuple-element(%p), index=1
  %ar = f32[64] all-reduce(%x), replica_groups=[1,4]<=[4], to_apply=%add.9
  ROOT %t = (s32[], f32[64]) tuple(%i2, %ar)
}
%cond.9 (p2: (s32[], f32[64])) -> pred[] {
  %p2 = (s32[], f32[64]) parameter(0)
  %i3 = s32[] get-tuple-element(%p2), index=0
  %lim = s32[] constant(7)
  ROOT %lt = pred[] compare(%i3, %lim), direction=LT
}
ENTRY %e (a0: f32[64]) -> (s32[], f32[64]) {
  %a0 = f32[64] parameter(0)
  %z = s32[] constant(0)
  %init = (s32[], f32[64]) tuple(%z, %a0)
  ROOT %w = (s32[], f32[64]) while(%init), condition=%cond.9, body=%body.9
}
"""
        mod = parse_hlo(text, hints={"total_devices": 4})
        summary = collective_summary(mod, trip_aware=True)
        per_op = 2 * 64 * 4 * 3 / 4
        assert summary["all-reduce"].wire_bytes == pytest.approx(7 * per_op)
        unaware = collective_summary(mod, trip_aware=False)
        assert unaware["all-reduce"].wire_bytes == pytest.approx(per_op)
