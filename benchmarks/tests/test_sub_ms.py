"""The ``step.sub_ms.*`` reduction (``harness/sublayers.py``): on
hand-made events, where every number is computed by hand, and on a small
recorded stretch of the chip's trace with the scope map of the programs
that ran in it (``trace_sample_sublayers.json``: cut from a traced run
of ``mistral-7b.prefill-closed`` by ``tools/phases.cut``, the map from
``obs.sublayers.scope_maps`` in the same process, kept for the
instructions the stretch holds)."""
import json
import os

import pytest

from benchmarks.harness import reduce, spec, sublayers
from benchmarks.tools import phases

HERE = os.path.dirname(os.path.abspath(__file__))
SUFFIXES = tuple(sublayers.METRICS)


def _trace(ops, modules, lo=0, hi=10_000):
    return reduce.Trace({
        "/device:TPU:0": {"XLA Ops": ops, "XLA Modules": modules},
        "/host:CPU": {"python": [("bench.traced", lo, hi - lo, {})]}})


K = ', custom_call_target="tpu_custom_call"'
OPS = [
    # jit_ff_step_c1(11): [1000, 2000)
    ("%while.3 = (s32[]) while(%t), body=%b", 1000, 900, {}),   # a container
    ("%fusion.1 = bf16[16,4096]{1,0} fusion(%p)", 1000, 200, {}),
    ("%ff_ragged_paged_c1.2 = bf16[16,1,8,4,128]{4,3,2,1,0} custom-call(%q)" + K,
     1200, 300, {}),
    ("%fusion.7 = bf16[16,14336]{1,0} fusion(%p)", 1500, 250, {}),
    ("%copy.4 = bf16[16,4096]{1,0} copy(%fusion.7)", 1750, 50, {}),   # maps to None
    ("%fusion.99 = f32[16]{0} fusion(%p)", 1800, 100, {}),    # the map lacks it
    # between the programs: another program's operation
    ("%fusion.1 = f32[8]{0} fusion(%p)", 2200, 400, {}),
    # jit_ff_step_c128_t512(12): [3000, 5000)
    ("%conditional.2 = (f32[]) conditional(%p), branch_computations={%a,%b}",
     3000, 1500, {}),                                               # a container
    ("%fusion.1 = bf16[512,4096]{1,0} fusion(%p)", 3000, 1000, {}),  # other map
    ("%fusion.7 = bf16[512,14336]{1,0} fusion(%p)", 4000, 600, {}),
    ("%call.5 = f32[] call(%p), to_apply=%c", 4600, 100, {}),       # a container
    ("%sort.9 = (s32[512]{0}) sort(%p), dimensions={0}", 4700, 300, {}),
    # jit_ff_step_c1(13): [6000, 7000), the decode program again
    ("%fusion.1 = bf16[16,4096]{1,0} fusion(%p)", 6000, 300, {}),
    ("%fusion.7 = bf16[16,14336]{1,0} fusion(%p)", 6300, 350, {}),
    # a step that ends past the traced window: left out with its operation
    ("%fusion.1 = bf16[16,4096]{1,0} fusion(%p)", 9500, 100, {}),
]
MODULES = [
    ("jit_ff_step_c1(11)", 1000, 1000, {"run_id": 1}),
    ("jit_ff_commit(5)", 2100, 600, {"run_id": 2}),
    ("jit_ff_step_c128_t512(12)", 3000, 2000, {"run_id": 3}),
    ("jit_ff_step_c1(13)", 6000, 1000, {"run_id": 4}),
    ("jit_ff_step_c1(14)", 9400, 1000, {"run_id": 5}),
]
MAPS = {
    "jit_ff_step_c1": {
        "fusion.1": "ff.attn.proj", "ff_ragged_paged_c1.2": "ff.attn.core",
        "fusion.7": "ff.ffn", "copy.4": None, "while.3": "ff.glue"},
    "jit_ff_step_c128_t512": {
        "fusion.1": "ff.glue", "fusion.7": "ff.ffn", "sort.9": "ff.moe.route",
        "conditional.2": "ff.glue", "call.5": None},
}


def test_the_table_by_hand():
    tab = sublayers.reduce_sublayers(_trace(OPS, MODULES), MAPS)
    assert tab.steps == 3                    # the commit and the cut step: out
    ms = {k: v * 1e6 for k, v in tab.ms.items()}           # back to ns
    assert ms == pytest.approx({
        "ff.attn.proj": 200 + 300, "ff.attn.core": 300,
        "ff.ffn": 250 + 600 + 350, "ff.glue": 1000, "ff.moe.route": 300,
        None: 50 + 100})
    # containers are out of every sum; so is the operation between programs
    assert sum(ms.values()) == pytest.approx(3450)
    assert tab.per_step("ff.ffn") == pytest.approx(1200e-6 / 3)
    assert tab.per_step("ff.attn.core") == pytest.approx(300e-6 / 3)
    assert tab.per_step(None) == pytest.approx(150e-6 / 3)
    assert tab.per_step("ff.mixer") is None       # no operation under it
    # a name the map lacks: unscoped, and counted
    assert {k: v * 1e6 for k, v in tab.unmatched.items()} == pytest.approx(
        {("jit_ff_step_c1", "fusion.99"): 100})
    assert set(tab.unscoped) == {("jit_ff_step_c1", "copy.4 copy"),
                                 ("jit_ff_step_c1", "fusion.99 fusion")}
    assert tab.by_program["jit_ff_step_c1"]["steps"] == 2
    assert tab.by_program["jit_ff_step_c128_t512"]["ms"]["ff.glue"] == (
        pytest.approx(1000e-6))


def test_a_program_without_a_map_is_unscoped_and_unmatched():
    tab = sublayers.reduce_sublayers(
        _trace(OPS, MODULES), {"jit_ff_step_c1": MAPS["jit_ff_step_c1"]})
    assert tab.ms[None] * 1e6 == pytest.approx(150 + 1000 + 600 + 300)
    assert len(tab.unmatched) == 1 + 3
    assert "ff.glue" not in tab.ms


class _Ctx:
    """What a reader is handed, as far as these ten read it."""

    def __init__(self, trace):
        self.trace = trace
        self.lines = []

    def log(self, line):
        self.lines.append(line)


def _readers():
    return {s: spec.load_module("per_layer", f"step.sub_ms.{s}").read
            for s in SUFFIXES}


def test_the_ten_readers_share_one_table(monkeypatch):
    asked = []
    monkeypatch.setattr(sublayers, "program_maps",
                        lambda programs: asked.append(programs) or MAPS)
    ctx = _Ctx(_trace(OPS, MODULES))
    got = {s: read(ctx) for s, read in _readers().items()}
    assert asked == [{"jit_ff_step_c1", "jit_ff_step_c128_t512"}]  # once
    assert got == pytest.approx({
        "attn_proj": 500e-6 / 3, "attn_core": 300e-6 / 3, "kv_write": None,
        "attn_select": None, "mixer": None, "ffn": 1200e-6 / 3,
        "moe_route": 300e-6 / 3, "head": None, "glue": 1000e-6 / 3,
        "unscoped": 150e-6 / 3})
    # the ten sum to the mean step's operation time
    assert sum(v for v in got.values() if v) == pytest.approx(3450e-6 / 3)
    lines = [l for l in ctx.lines if l.startswith("[sublayers]")]
    assert "names the maps lack: 1" in lines[0]
    assert any(l.startswith("[sublayers] jit_ff_step_c1 2 x ") for l in lines)
    assert any("unmatched" in l and "fusion.99" in l for l in lines)


@pytest.mark.parametrize("maps", [None, {}])
def test_without_a_map_every_reader_reads_nothing(monkeypatch, maps):
    """The parent's tree: no ``obs.sublayers`` to import, or nothing in
    it; none raises and the line leaves the ten out."""
    monkeypatch.setattr(sublayers, "program_maps", lambda programs: maps)
    ctx = _Ctx(_trace(OPS, MODULES))
    assert [read(ctx) for read in _readers().values()] == [None] * 10


def test_without_a_trace_every_reader_reads_nothing(monkeypatch):
    monkeypatch.setattr(sublayers, "program_maps", lambda programs: 1 / 0)
    ctx = _Ctx(reduce.NoTrace())                        # a CPU rehearsal
    assert [read(ctx) for read in _readers().values()] == [None] * 10


#: the sublayers only some families have, and the cells that run them: the
#: driver wants a metric with no ``workloads`` list in EVERY cell's line
LISTED = {
    "attn_select": ["minicpm-sala.longdoc-closed"],
    "mixer": ["minicpm-sala.longdoc-closed",
              "lfm2-24b-a2b.decode-wide-closed"],
    "moe_route": ["mixtral-8x7b.prefill-closed",
                  "lfm2-24b-a2b.decode-wide-closed",
                  "deepseek-v3.doc8k-closed"],
}


def test_the_benchmark_lists_the_ten():
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        entries = {m["name"]: m for m in json.load(f)["per_layer"]}
    for suffix in SUFFIXES:
        m = entries[f"step.sub_ms.{suffix}"]
        want = {"name": m["name"], "unit": "ms", "better": "lower",
                "source": "device_trace", "layer": "model step",
                "moves": "out_tokens_per_s"}    # every cell, no list ...
        if suffix in LISTED:                    # ... but where a family
            want["workloads"] = LISTED[suffix]  # has no such sublayer
        assert m == want
    cell = spec.Cell("lfm2-24b-a2b.decode-wide-closed")
    assert set(entries) >= {m["name"] for m in cell.per_layer} >= {
        f"step.sub_ms.{s}" for s in SUFFIXES if s != "attn_select"}
    dense = {m["name"] for m in spec.Cell("mistral-7b.decode-closed").per_layer}
    assert {s for s in SUFFIXES if f"step.sub_ms.{s}" in dense} == (
        set(SUFFIXES) - set(LISTED))


def test_a_step_that_named_everything_reads_unscoped_zero():
    """``unscoped`` is every cell's: a window whose operations all carry
    a scope reads 0 there, not nothing; the named sublayers it lacks
    still read nothing."""
    ops = [o for o in OPS if "copy.4" not in o[0] and "fusion.99" not in o[0]]
    tab = sublayers.reduce_sublayers(_trace(ops, MODULES), MAPS)
    assert None not in tab.ms and not tab.unmatched
    assert tab.per_step(None) == 0.0
    assert tab.per_step("ff.head") is None


# ---------------------------------------------------------------------------
# the recorded stretch


@pytest.fixture(scope="module")
def recorded():
    planes, rest = phases.load_sample(
        os.path.join(HERE, "trace_sample_sublayers.json"))
    return reduce.Trace(planes), rest["scope_maps"], rest["expect"]


def test_the_recorded_stretch(recorded):
    trace, maps, expect = recorded
    tab = sublayers.reduce_sublayers(trace, maps)
    assert tab.steps == expect["steps"] > 1
    assert not tab.unmatched                  # the executable that ran
    got = {s: tab.per_step(scope) for s, scope in sublayers.METRICS.items()}
    assert got == pytest.approx(expect["sub_ms"])
    # the same by the long way round: every operation against every step
    steps = [(s, s + d, n.split("(")[0]) for n, s, d, _ in trace.modules
             if n.startswith("jit_ff_step_") and trace.lo <= s
             and s + d <= trace.hi]
    total, core = 0.0, 0.0
    for name, _, opcode, kernel, s, dur in trace.ops:
        if opcode in reduce.CONTAINERS:
            continue
        for lo, hi, program in steps:
            if lo <= s < hi:
                total += dur / 1e6
                if kernel and name.startswith("ff_ragged_paged_c"):
                    core += dur / 1e6
                    assert maps[program][name] == "ff.attn.core"
    assert sum(v for v in got.values() if v) == pytest.approx(total / len(steps))
    # ff.attn.core holds the kernel's own time, and in a packed step
    # the spread of its queries and the gather of its result (0.2 ms
    # a layer's pair of them at 512 places)
    assert core / len(steps) <= got["attn_core"] <= 1.25 * core / len(steps)
    assert got["unscoped"] < 0.05 * total / len(steps)
    assert got["mixer"] is None and got["attn_select"] is None  # a dense model
