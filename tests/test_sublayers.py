"""Device time by sublayer (ISSUE 42): the vocabulary of ``ff.*`` named
scopes and the parser of a compiled program's ``op_name``s. What is asked
of a served family's step programs is in tests/family_cases.py and runs in
the family's own file; what is asked of a serving engine's map in
tests/test_decoder_families.py.
"""
import pytest

from flexflow_tpu.obs.sublayers import (
    SUBLAYERS,
    parse_scope_map,
    sublayer,
    sublayer_of,
)

# ---------------------------------------------------------------------------
# the op_name parser and the vocabulary


@pytest.mark.parametrize("op_name, want", [
    ("jit(ff_step_c1)/jit(main)/ff.glue/while/body/ff.attn.proj/dot_general",
     "ff.attn.proj"),                       # innermost wins, under while/body
    ("jit(f)/ff.moe.route/ff.ffn/pallas_call", "ff.ffn"),
    ("jit(f)/ff.ffn/ff.moe.route/sort", "ff.moe.route"),
    ("jit(f)/jit(main)/while/body/dot_general", None),       # no ff.*
    ("jit(f)/mla.project/dot_general", None),                # an old name
    ("jit(f)/ff.attn.proj_extra/dot_general", None),         # not a component
    ("jit(f)/ff.nonsense/ff.glue/add", "ff.glue"),  # outside the vocabulary
    ("", None),
])
def test_sublayer_of(op_name, want):
    assert sublayer_of(op_name) == want


def test_a_name_outside_the_vocabulary_is_refused():
    with pytest.raises(ValueError, match="no sublayer 'atn.proj'"):
        sublayer("atn.proj")
    with pytest.raises(ValueError):
        sublayer("ff.ffn")  # the name goes without its prefix
    for name in SUBLAYERS:
        with sublayer(name):
            pass


HLO = '''HloModule jit_ff_step_c1, entry_computation_layout={()->f32[]}

%fused_computation.1 (param_0: f32[4]) -> f32[4] {
  %param_0 = f32[4]{0} parameter(0)
  %mul.1 = f32[4]{0} multiply(%param_0, %param_0), metadata={op_name="jit(ff_step_c1)/jit(main)/ff.glue/mul"}
  ROOT %add.2 = f32[4]{0} add(%mul.1, %param_0), metadata={op_name="jit(ff_step_c1)/jit(main)/ff.glue/while/body/ff.ffn/add" source_file="x.py" source_line=3}
}

%fused_computation.2 (param_0.1: f32[4]) -> (f32[4], f32[4]) {
  %param_0.1 = f32[4]{0} parameter(0)
  ROOT %tuple.3 = (f32[4]{0}, f32[4]{0}) tuple(%param_0.1, %param_0.1)
}

%body (arg: f32[4]) -> f32[4] {
  %arg = f32[4]{0} parameter(0)
  %fusion.7 = f32[4]{0} fusion(%arg), kind=kLoop, calls=%fused_computation.1
  %fusion.8 = f32[4]{0} fusion(%arg), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(ff_step_c1)/jit(main)/ff.glue/while/body/ff.attn.proj/dot_general"}
  %fusion.9 = (f32[4]{0}, f32[4]{0}) fusion(%arg), kind=kLoop, calls=%fused_computation.2
  %ff_ragged_paged_c1.3 = f32[4]{0} custom-call(%fusion.8), custom_call_target="tpu_custom_call", metadata={op_name="jit(ff_step_c1)/jit(main)/ff.glue/while/body/ff.attn.core/pallas_call"}
  ROOT %copy.4 = f32[4]{0} copy(%ff_ragged_paged_c1.3)
}

ENTRY %main.5 (Arg_0.1: f32[4]) -> f32[4] {
  %Arg_0.1 = f32[4]{0} parameter(0), metadata={op_name="params"}
  ROOT %while.6 = f32[4]{0} while(%Arg_0.1), condition=%cond, body=%body, metadata={op_name="jit(ff_step_c1)/jit(main)/ff.glue/while"}
}
'''


def test_parse_scope_map():
    m = parse_scope_map(HLO)
    assert m["fusion.7"] == "ff.ffn"         # no name of its own: its root's
    assert m["fusion.8"] == "ff.attn.proj"   # its own name first
    assert m["fusion.9"] is None             # a root that has none
    assert m["ff_ragged_paged_c1.3"] == "ff.attn.core"
    assert m["copy.4"] is None and m["Arg_0.1"] is None
    assert m["while.6"] == "ff.glue"
    assert m["mul.1"] == "ff.glue" and m["add.2"] == "ff.ffn"
    # every instruction is in the map: one it lacks is another program's
    assert set(m) == {"param_0", "mul.1", "add.2", "param_0.1", "tuple.3",
                      "arg", "fusion.7", "fusion.8", "fusion.9",
                      "ff_ragged_paged_c1.3", "copy.4", "Arg_0.1", "while.6"}


def test_parse_instructions():
    """What ``parse_scope_map`` and ``scripts/route_ops.py`` read of a
    compiled text: each instruction's computation, opcode, result
    shape less its layout, own ``op_name`` and called computation."""
    from flexflow_tpu.obs.sublayers import Instruction, parse_instructions

    got = parse_instructions(HLO)
    assert list(got) == list(parse_scope_map(HLO))
    assert got["fusion.9"] == Instruction(
        "body", False, "fusion", "(f32[4], f32[4])", "", "fused_computation.2")
    assert got["add.2"] == Instruction(
        "fused_computation.1", True, "add", "f32[4]",
        "jit(ff_step_c1)/jit(main)/ff.glue/while/body/ff.ffn/add", None)
    assert got["ff_ragged_paged_c1.3"].opcode == "custom-call"
    assert got["while.6"].computation == "main.5" and got["while.6"].root
