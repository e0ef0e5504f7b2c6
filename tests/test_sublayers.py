"""Device time by sublayer (ISSUE 42): the ``ff.*`` named scopes inside
the step programs, the parser of a compiled program's ``op_name``s, and
the scope map the engine gives on demand without tracing, compiling or
dispatching anything.

Tiny widths on the CPU; the Pallas kernels in interpret mode, so the
attention and grouped-matmul call sites are the served ones.
"""
import contextlib
import re

import jax
import jax.numpy as jnp
import pytest

from flexflow_tpu.models import (
    deepseek_v3,
    granite_hybrid,
    laguna,
    lfm2_moe,
    llama,
    longcat_flash,
    minicpm_sala,
    mistral,
    mixtral,
    olmo_hybrid,
    qwen3_next,
    smallthinker,
)
from flexflow_tpu.obs import sublayers
from flexflow_tpu.obs.sublayers import (
    SUBLAYERS,
    parse_scope_map,
    scope_maps,
    sublayer,
    sublayer_of,
)
from flexflow_tpu.serve import InferenceEngine, RequestManager, ServingConfig

CHUNK, PAGE, SLOTS = 16, 16, 4
ATTENTION = {"ff.attn.proj", "ff.attn.core", "ff.attn.write"}
ALWAYS = ATTENTION | {"ff.ffn", "ff.head", "ff.glue"}
# family -> (module, the sublayers its paged step has)
FAMILIES = {
    "dense": (mistral, ALWAYS),
    "llama": (llama, ALWAYS),
    "routed": (mixtral, ALWAYS | {"ff.moe.route"}),
    "minicpm_sala": (minicpm_sala, ALWAYS | {"ff.mixer", "ff.attn.select"}),
    "lfm2_moe": (lfm2_moe, ALWAYS | {"ff.mixer", "ff.moe.route"}),
    "deepseek_v3": (deepseek_v3, ALWAYS | {"ff.moe.route"}),
    "olmo_hybrid": (olmo_hybrid, ALWAYS | {"ff.mixer"}),
    "granite_hybrid": (granite_hybrid, ALWAYS | {"ff.mixer"}),
    # full and window layers alike, the router at the top of the block
    "smallthinker": (smallthinker, ALWAYS | {"ff.moe.route"}),
    # routed experts behind a recurrent mixer: both beside attention
    "qwen3_next": (qwen3_next, ALWAYS | {"ff.mixer", "ff.moe.route"}),
    # heads by kind, a gate a head, a leading dense layer, a shared expert
    "laguna": (laguna, ALWAYS | {"ff.moe.route"}),
    # two attentions and two dense FFNs a layer, the routed block (its
    # identity outputs' part too) on a shortcut across the second pair
    "longcat_flash": (longcat_flash, ALWAYS | {"ff.moe.route"}),
}
# the operations that do a step's work: none may lie outside the scopes
WORK = ("dot", "convolution", "sort", "scatter", "gather", "custom-call")
_OPCODE = re.compile(
    r"^\s+(?:ROOT\s+)?%?([\w.\-]+) = (?:\([^=]*\)|\S+) ([\w\-]+)\(", re.M)


def _serve(mod, *, sanitizers=("retrace",), max_seq=128):
    """A tiny paged server of family ``mod`` that has run one prompt of
    two chunks and a few decode steps: its mixed and C=1 programs are
    compiled."""
    cfg = mod.tiny(dtype=jnp.float32)
    params = mod.init_params(jax.random.PRNGKey(0), cfg)
    eng = InferenceEngine(mod, cfg, params, ServingConfig(
        kv_layout="paged", kernels="pallas", page_size=PAGE,
        max_requests_per_batch=SLOTS, max_sequence_length=max_seq,
        prefill_chunk=CHUNK, cache_dtype=jnp.float32,
        sanitizers=sanitizers))
    rm = RequestManager(eng)
    rid = rm.submit([(7 * i + 3) % 250 for i in range(CHUNK + 2)],
                    max_new_tokens=4)
    while not rm.result(rid).profile.finish_time:
        rm.step()
    rm.drain()
    return eng, rm


@pytest.fixture(scope="module", autouse=True)
def _release_compiled_programs():
    """Twelve families' servers compile thousands of small programs,
    each a few memory maps of its worker's process, which has 65530 (a
    worker that passes the limit aborts inside a later file's compile;
    tests/test_longcat_flash.py has the measurement): drop them when
    the file is done."""
    yield
    jax.clear_caches()


@pytest.fixture(scope="module")
def served():
    """family -> (engine, manager, {program: HLO text}, {program: map}),
    each family built on first use and kept for the module."""
    made = {}

    def get(family):
        if family not in made:
            eng, rm = _serve(FAMILIES[family][0])
            texts = eng.step_program_texts()
            made[family] = (eng, rm, texts, {
                name: parse_scope_map(text) for name, text in texts.items()})
        return made[family]

    return get


# ---------------------------------------------------------------------------
# (a) every working operation of every family's step lies under a scope


@pytest.mark.parametrize("step", ["c1", "mixed"])
@pytest.mark.parametrize("family", list(FAMILIES))
def test_every_working_operation_has_a_sublayer(served, family, step):
    _, _, texts, maps = served(family)
    names = [n for n in texts if n.startswith("ff_step_c")
             and (n == "ff_step_c1") == (step == "c1")]
    assert names, sorted(texts)
    met = set()
    for name in names:
        scopes = maps[name]
        # an instruction the compiler made of others carries no op_name
        # at all (XLA:CPU's second dot of a three-operand einsum): that is
        # what step.sub_ms.unscoped is for, and no scope could reach it
        named = {m.group(1) for m in _OPCODE.finditer(texts[name])
                 if "op_name=" in texts[name][m.end():].split("\n", 1)[0]}
        opcodes = dict(_OPCODE.findall(texts[name]))
        work = {i: op for i, op in opcodes.items()
                if op in WORK and i in named}
        assert work, f"{name}: no working operation parsed"
        bare = {i: op for i, op in work.items() if scopes[i] is None}
        assert not bare, f"{name}: under no ff.* scope: {bare}"
        # a matmul is some sublayer's work, never the step's glue
        glue = {i: op for i, op in work.items()
                if op in ("dot", "convolution") and scopes[i] == "ff.glue"}
        assert not glue, f"{name}: matmuls under ff.glue: {glue}"
        met |= {s for s in scopes.values() if s is not None}
    assert met == FAMILIES[family][1]
    assert met <= {"ff." + s for s in SUBLAYERS}


# ---------------------------------------------------------------------------
# (b) the op_name parser and the vocabulary


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


# ---------------------------------------------------------------------------
# (c) asking for the map traces, compiles and dispatches nothing


def test_asking_for_the_map_is_no_retrace(served):
    eng, rm, _, _ = served("dense")      # sanitizers=("retrace",): strict
    counts = dict(eng.retrace_guard.compile_counts())
    before = (rm.stats.compiles, rm.stats.retraces, eng.dispatch_count,
              len(eng.retrace_guard.events))
    assert counts and set(counts.values()) == {1}
    maps = scope_maps([eng])              # raises under the strict sentinel
    assert eng.retrace_guard.compile_counts() == counts
    assert (rm.stats.compiles, rm.stats.retraces, eng.dispatch_count,
            len(eng.retrace_guard.events)) == before
    for name in ("jit_ff_step_c1", f"jit_ff_step_c{CHUNK}"):
        assert set(maps[name].values()) - {None} == FAMILIES["dense"][1]
    # and the server still serves on the programs it had
    rid = rm.submit(list(range(1, CHUNK + 3)), max_new_tokens=3)
    while not rm.result(rid).profile.finish_time:
        rm.step()
    rm.drain()
    assert eng.retrace_guard.compile_counts() == counts


def test_an_engine_is_not_kept_alive_by_the_registry():
    import gc
    import weakref

    eng, _ = _serve(mistral, sanitizers=())
    assert sublayers.live_engines()[-1] is eng
    assert "jit_ff_step_c1" in scope_maps()   # the newest engine's stands
    ref = weakref.ref(eng)
    del eng, _
    gc.collect()
    assert ref() is None and None not in sublayers.live_engines()


# ---------------------------------------------------------------------------
# (d) the scopes are metadata: the same equations in the same order


def test_the_scopes_change_no_equation(monkeypatch):
    mod = lfm2_moe  # conv, attention, dense and routed layers in one step
    cfg = mod.tiny(dtype=jnp.float32)
    params = jax.eval_shape(lambda: mod.init_params(jax.random.PRNGKey(0), cfg))
    cache = jax.eval_shape(lambda: mod.init_paged_kv_cache(
        cfg, 2 * SLOTS, PAGE, jnp.float32, num_slots=SLOTS))

    def jaxpr(pack):
        def step(params, cache, tokens, positions, idx, table):
            return mod.serve_step_paged(
                params, cache, tokens, positions, idx, None, None, table,
                cfg=cfg, cache_len=2 * PAGE, kernels="pallas", pack=pack)

        i32 = jnp.int32
        return jax.make_jaxpr(step)(
            params, cache, jax.ShapeDtypeStruct((SLOTS, CHUNK), i32),
            jax.ShapeDtypeStruct((SLOTS, CHUNK), i32),
            jax.ShapeDtypeStruct((SLOTS,), i32),
            jax.ShapeDtypeStruct((SLOTS, 2), i32))

    def scopes(j):
        return {str(e.source_info.name_stack) for e in j.jaxpr.eqns}

    for pack in (None, 2 * CHUNK):
        with_scopes = jaxpr(pack)
        assert any("ff.glue" in s for s in scopes(with_scopes))
        with monkeypatch.context() as m:
            m.setattr(sublayers, "_named_scope",
                      lambda name: contextlib.nullcontext())
            without = jaxpr(pack)
        assert not any("ff." in s for s in scopes(without))
        assert str(with_scopes) == str(without)
        assert len(with_scopes.jaxpr.eqns) == len(without.jaxpr.eqns)
