"""Named model scopes and the attribution of device time to them.

`repro.models.scopes` names the parts of the train step; `repro.core.cct`
finds them on op_name paths (`scope_of`) and folds a device trace's
seconds into them (`seconds_by_scope`).  The compiled steps here are the
tiny dense and hybrid cells of `bench/tests/tiny.py`.
"""
import contextlib
import re

import jax
import jax.numpy as jnp
import pytest

from bench.tests import tiny
from repro.core import build_cct, parse_hlo
from repro.core.cct import (
    ENCLOSING_OPCODES,
    InstructionScopes,
    cost_by_scope,
    scope_of,
    seconds_by_scope,
)
from repro.models import scopes
from repro.models.scopes import MODEL_SCOPES
from repro.models.flags import FUSED_REGION_MARK, flags

# op_name paths as JAX writes them into the compiled train step.
OP_NAMES = [
    ("jit(train_step)/jvp()/while/body/closed_call/attn/...d,df->...f/"
     "dot_general", "attn"),
    ("jit(train_step)/transpose(jvp(head_loss))/jit(log_softmax)/add_any",
     "head_loss"),
    ("jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/mlp/...d,df->...f/dot_general", "mlp"),
    ("jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "norm/mul", "norm"),
    ("jit(train_step)/jvp()/while/body/closed_call/ssm/closed_call/while/"
     "body/closed_call/mul", "ssm"),
    ("jit(train_step)/jvp()/while/body/closed_call/attn/"
     f"{FUSED_REGION_MARK}/closed_call/while/body/closed_call/exp", "attn"),
    ("jit(train_step)/transpose(jvp(embed))/scatter-add", "embed"),
    ("jit(train_step)/optimizer/jit(clip)/min", "optimizer"),
    ("jit(train_step)/optimizer/transpose(jvp(attn))/mul", "attn"),
    ("jit(train_step)/attn/jit(optimizer)/mul", "optimizer"),
    ("jit(train_step)/jvp()/while/body/dynamic_slice", None),
    ("jit(train_step)/jvp()/while/cond/lt", None),
    ("", None),
]


@pytest.mark.parametrize("op_name,scope", OP_NAMES)
def test_scope_of_finds_the_innermost_named_scope(op_name, scope):
    assert scope_of(op_name, MODEL_SCOPES) == scope


# A step with a scanned layer: a fusion whose own op_name is the residual
# add outside any scope, a layout copy with no op_name, the scan's slicing
# of the stacked weights, the loop counter, a zero-filled buffer hoisted
# out of the loop and named after the enclosing call, and the optimizer
# after the loop.
_SCOPED = "jit(train_step)/jvp()/while/body/closed_call"
STEP_HLO = f"""\
HloModule synthetic_step

%fused_mlp (p0: bf16[8,16], p1: bf16[16,16]) -> bf16[8,16] {{
  %p0 = bf16[8,16]{{1,0}} parameter(0)
  %p1 = bf16[16,16]{{1,0}} parameter(1)
  %dot.1 = bf16[8,16]{{1,0}} dot(%p0, %p1), lhs_contracting_dims={{1}}, rhs_contracting_dims={{0}}, metadata={{op_name="{_SCOPED}/mlp/dot_general"}}
  ROOT %add.1 = bf16[8,16]{{1,0}} add(%dot.1, %p0), metadata={{op_name="{_SCOPED}/add"}}
}}

%body (p: (s32[], bf16[8,16], bf16[4,16,16], f32[8,16])) -> (s32[], bf16[8,16], bf16[4,16,16], f32[8,16]) {{
  %p = (s32[], bf16[8,16], bf16[4,16,16], f32[8,16]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %x = bf16[8,16]{{1,0}} get-tuple-element(%p), index=1
  %ws = bf16[4,16,16]{{2,1,0}} get-tuple-element(%p), index=2
  %g = f32[8,16]{{1,0}} get-tuple-element(%p), index=3
  %zero = s32[] constant(0)
  %dynamic-slice.2 = bf16[1,16,16]{{2,1,0}} dynamic-slice(%ws, %i, %zero, %zero), dynamic_slice_sizes={{1,16,16}}, metadata={{op_name="jit(train_step)/jvp()/while/body/dynamic_slice"}}
  %w = bf16[16,16]{{1,0}} bitcast(%dynamic-slice.2)
  %fusion.7 = bf16[8,16]{{1,0}} fusion(%x, %w), kind=kOutput, calls=%fused_mlp, metadata={{op_name="{_SCOPED}/add"}}
  %copy.3 = bf16[8,16]{{0,1}} copy(%fusion.7)
  %one = s32[] constant(1)
  %add.4 = s32[] add(%i, %one), metadata={{op_name="jit(train_step)/jvp()/while/body/add"}}
  ROOT %t = (s32[], bf16[8,16], bf16[4,16,16], f32[8,16]) tuple(%add.4, %copy.3, %ws, %g)
}}

%cond (c: (s32[], bf16[8,16], bf16[4,16,16], f32[8,16])) -> pred[] {{
  %c = (s32[], bf16[8,16], bf16[4,16,16], f32[8,16]) parameter(0)
  %ci = s32[] get-tuple-element(%c), index=0
  %n = s32[] constant(4)
  ROOT %lt = pred[] compare(%ci, %n), direction=LT
}}

ENTRY %main (x0: bf16[8,16], ws0: bf16[4,16,16]) -> bf16[8,16] {{
  %x0 = bf16[8,16]{{1,0}} parameter(0)
  %ws0 = bf16[4,16,16]{{2,1,0}} parameter(1)
  %c0 = s32[] constant(0)
  %c0f = f32[] constant(0)
  %zeros.5 = f32[8,16]{{1,0}} broadcast(%c0f), dimensions={{}}, metadata={{op_name="jit(train_step)/transpose(jvp())/while/body/closed_call"}}
  %init = (s32[], bf16[8,16], bf16[4,16,16], f32[8,16]) tuple(%c0, %x0, %ws0, %zeros.5)
  %while.1 = (s32[], bf16[8,16], bf16[4,16,16], f32[8,16]) while(%init), condition=%cond, body=%body, metadata={{op_name="{_SCOPED}/mlp/while"}}
  %y = bf16[8,16]{{1,0}} get-tuple-element(%while.1), index=1
  ROOT %multiply.9 = bf16[8,16]{{1,0}} multiply(%y, %y), metadata={{op_name="jit(train_step)/optimizer/mul"}}
}}
"""

_EVENTS = ("fusion.7", "copy.3", "dynamic-slice.2", "add.4", "zeros.5",
           "while.1", "multiply.9", "gone.1")
# Trace event names of the same instructions: the TPU's carry the
# instruction's text, the CPU's its bare name.
EVENT_NAMES = {
    "tpu": {"fusion.7": "%fusion.7 = bf16[8,16]{1,0} fusion(%x, %w), "
                        "kind=kOutput, calls=%fused_mlp",
            "copy.3": "%copy.3 = bf16[8,16]{0,1} copy(%fusion.7)",
            "dynamic-slice.2": "%dynamic-slice.2 = bf16[1,16,16]{2,1,0} "
                               "dynamic-slice(%ws, %i, %zero, %zero)",
            "add.4": "%add.4 = s32[] add(%i, %one)",
            "zeros.5": "%zeros.5 = f32[8,16]{1,0} broadcast(%c0f), "
                       "dimensions={}",
            "while.1": "%while.1 = (s32[], bf16[8,16], bf16[4,16,16], "
                       "f32[8,16]) while(%init), condition=%cond, "
                       "body=%body",
            "multiply.9": "%multiply.9 = bf16[8,16]{1,0} multiply(%y, %y)",
            "gone.1": "%gone.1 = f32[] add(%a, %b)"},
    "cpu": {n: n for n in _EVENTS},
}


@pytest.mark.parametrize("style", sorted(EVENT_NAMES))
def test_seconds_by_scope_counts_leaf_instructions_by_scope(style):
    names = EVENT_NAMES[style]
    seconds = {"fusion.7": 3.0, "copy.3": 1.0, "dynamic-slice.2": 0.5,
               "add.4": 0.125, "zeros.5": 0.75, "while.1": 10.0,
               "multiply.9": 2.0, "gone.1": 0.25}
    found = seconds_by_scope(parse_hlo(STEP_HLO),
                             {names[k]: v for k, v in seconds.items()},
                             MODEL_SCOPES)
    # The fusion takes its matmul's scope, the copy its operand's, the
    # weight slice and the hoisted zero-fill the scope of what consumes
    # them; the loop counter has none.  The while event encloses the
    # others and is left out of the leaf total.
    assert found.by_scope == {"mlp": 5.25, "optimizer": 2.0}
    assert found.unattributed == 0.125
    assert found.enclosing == 10.0
    assert found.unmatched == 0.25
    assert found.leaf == 7.375


def test_cost_by_scope_counts_each_loop_body_per_trip():
    module = parse_hlo(STEP_HLO)
    cost = cost_by_scope(module, MODEL_SCOPES)
    by_name = {i.name: i for i in module.all_instructions()}
    fusion = by_name["fusion.7"]
    assert by_name["while.1"].trip_count == 4
    assert fusion.flops > 0 and by_name["multiply.9"].flops > 0
    assert cost["mlp"][0] == pytest.approx(4 * fusion.flops)
    assert cost["optimizer"][0] == by_name["multiply.9"].flops
    assert sum(f for f, _ in cost.values()) == \
        pytest.approx(module.total_flops())
    assert cost["mlp"][1] >= 4 * (fusion.bytes_read + fusion.bytes_written)


def _abstract_args(config):
    from bench.drivers import train
    arch = train.arch_config(config)
    traffic = tiny.TRAFFIC[config["name"]]
    state = jax.eval_shape(train.build_init(arch), jax.random.PRNGKey(0))
    rows = jax.ShapeDtypeStruct((traffic["batch"], traffic["seq_len"]),
                                jnp.int32)
    return train.build_step(arch, traffic), state, {"tokens": rows,
                                                    "labels": rows}


def _compiled_text(config):
    step, state, batch = _abstract_args(config)
    return step.lower(state, batch).compile().as_text()


CONFIGS = {"dense": tiny.DENSE, "hybrid": tiny.HYBRID}
EXPECTED = {"dense": {"embed", "norm", "attn", "mlp", "head_loss",
                      "optimizer"},
            "hybrid": {"embed", "norm", "attn", "ssm", "mlp", "head_loss",
                       "optimizer"}}


@pytest.fixture(scope="module")
def compiled():
    return {k: _compiled_text(c) for k, c in CONFIGS.items()}


def _leaf_instructions(module):
    for comp in module.computations.values():
        if comp.kind in ("entry", "loop_body"):
            yield from (i for i in comp.instructions
                        if i.opcode not in ENCLOSING_OPCODES)


@pytest.mark.parametrize("kind", sorted(CONFIGS))
def test_the_compiled_steps_leaf_instructions_map_to_their_scopes(
        compiled, kind):
    module = parse_hlo(compiled[kind])
    scope = InstructionScopes(module, MODEL_SCOPES)
    found = {i.name: scope(i) for i in _leaf_instructions(module)}
    assert set(found.values()) - {None} == EXPECTED[kind]
    # Nearly all of the step's arithmetic lies in a named scope.
    flops = [(i.flops, found[i.name]) for i in _leaf_instructions(module)]
    scoped = sum(f for f, s in flops if s is not None)
    assert scoped >= 0.99 * sum(f for f, _ in flops) > 0


@pytest.mark.parametrize("kind", sorted(CONFIGS))
def test_leos_cct_of_the_step_has_the_scope_nodes(compiled, kind):
    names = {node.name for node in build_cct(parse_hlo(compiled[kind])).walk()}
    assert {"attn", "mlp", "optimizer"} <= names
    assert ("ssm" in names) == (kind == "hybrid")


def test_the_fused_region_mark_is_found_inside_the_attention_scope():
    with flags(attention_impl="pallas_fused"):
        module = parse_hlo(_compiled_text(tiny.DENSE))
    marked = [i for i in module.all_instructions()
              if FUSED_REGION_MARK in i.op_name]
    assert marked
    assert {scope_of(i.op_name, MODEL_SCOPES) for i in marked} == {"attn"}
    # `core/fusion_model` priced the region as one kernel: no HBM traffic.
    assert all(i.bytes_read == i.bytes_written == 0 for i in marked)


def _structure(hlo_text):
    """The compiled program without its metadata: no `metadata={...}`, no
    stack-frame tables, and instructions renamed in order of appearance
    (XLA derives instruction names from the traced op's name)."""
    lines = [line for line in hlo_text.splitlines() if not re.match(
        r"^(FileNames|FunctionNames|FileLocations|StackFrames|\d+ )", line)]
    text = re.sub(r",? metadata=\{[^}]*\}", "", "\n".join(lines))
    names = {}
    return re.sub(r"%[\w.\-]+",
                  lambda m: names.setdefault(m.group(0), f"%v{len(names)}"),
                  text)


@pytest.mark.parametrize("kind", sorted(CONFIGS))
def test_the_scopes_change_only_the_programs_metadata(compiled, kind,
                                                      monkeypatch):
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = _compiled_text(CONFIGS[kind])
    assert "optimizer/" not in bare and "optimizer/" in compiled[kind]
    assert _structure(bare) == _structure(compiled[kind])


def test_every_scope_is_named_once():
    assert len(set(scopes.MODEL_SCOPES)) == len(scopes.MODEL_SCOPES)
    assert set(scopes.KIND_SCOPES.values()) <= set(scopes.MODEL_SCOPES)
