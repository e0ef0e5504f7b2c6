"""The gated delta rule kernel's part of the benchmark (PR 48): the
count of one call by hand, at a small mix and at the Olmo cell's sizes
(the state by its ARITHMETIC, whatever the layout holds), and the reader
that finds the call by NAME on hand-made events: a share where the
named operations exist, nothing (and nothing raised) where they do
not."""
import json
import os
import types

import pytest

from benchmarks.harness import reduce, spec
from benchmarks.harness.loop import Window

CELL = "olmo-hybrid-7b.decode-wide-closed"
METRIC = "kernel.gdn_recur_roofline.decode"
SMALL = dict(
    hidden_size=8, intermediate_size=24, vocab_size=100,
    num_attention_heads=4, num_key_value_heads=4, num_hidden_layers=5,
    linear_num_value_heads=3, linear_key_head_dim=2, linear_value_head_dim=4,
    linear_conv_kernel_dim=4,
    layer_types=["linear_attention"] * 3 + ["full_attention", "linear_attention",
                                            "full_attention"])
MIX = dict(decode_rows=3, decode_ctx=150, prefill_rows=0, prefill_tokens=0,
           prefill_row_ctx=0, prefill_tok_ctx=0)
K = ', custom_call_target="tpu_custom_call"'


def test_one_call_by_hand():
    count = spec.load_module("counts", "gdn_recur_kernel").count
    Hl, dk, dv, rows = 3, 2, 4, 3
    # a row: its state in and out; k and q (Hl dk each); v, the decay, the
    # write strength and k . q on their head's lanes in, o out (Hl dv each)
    assert count(SMALL, MIX) == (
        pytest.approx(7 * rows * Hl * dk * dv),
        pytest.approx(4 * rows * (2 * Hl * dk * dv + 2 * Hl * dk + 5 * Hl * dv)))
    # the cell's sizes, 64 rows: 2 x 141.6 MB of state a call, 0.35 ms at
    # the chip's peak; the vectors are 8.8 MB of it, and memory binds
    flops, nbytes = count(spec.Cell(CELL).config, dict(MIX, decode_rows=64))
    state = 64 * 30 * 96 * 192 * 4
    assert state == pytest.approx(141.6e6, rel=1e-3)
    assert nbytes == 2 * state + 4 * 64 * 30 * (2 * 96 + 5 * 192)
    assert nbytes / 819e9 * 1e3 == pytest.approx(0.3565, abs=1e-3)
    assert flops == 7 * 64 * 30 * 96 * 192 and flops / 197e12 < 0.01 * nbytes / 819e9


def _ctx(trace=reduce.NoTrace(), tracer=None):
    return reduce.Context(
        window=Window(), setup_s=0.0, cfg=SMALL,
        peaks={"bf16_flops_per_s": 1e9, "hbm_bytes_per_s": 1e9}, trace=trace,
        tracer=tracer, log=lambda msg: None,
        engine_serving=types.SimpleNamespace(mixed_chunk=128))


def _tracer():
    """A traced run's notes of two decode turns at three live rows."""
    zero = types.SimpleNamespace(mixed_steps=0, prefill_tokens=0)
    return types.SimpleNamespace(rows=[(3, 150, 0, 0)] * 2, stats_start=zero,
                                 stats_stop=zero)


def _planes(kernel=True):
    recur = ("%ff_gdn_recur_c1.{} = (f32[4,1,3,4]{{3,2,1,0}}, f32[4,4,3,2,4]{{4,3,2,1,0}}) "
             "custom-call(%lay, %cnt, %fr, %cols)" + K)
    fused = "%select_dynamic-update-slice_fusion.{} = f32[4,4,3,2,4]{{4,3,2,1,0}} fusion(%s)"
    attn = "%ff_ragged_paged_c{}.1 = bf16[4,{},4,1,2]{{4,3,2,1,0}} custom-call(%q)" + K
    ops, modules = [], []
    # two decode programs: four recurrent layers (400, 410, ... ns), one attention call
    for run, start in enumerate((1000, 4000)):
        modules.append(("jit_ff_step_c1(3)", start, 2500, {"run_id": run}))
        for layer in range(4):
            at = start + 10 + 500 * layer
            ops.append(((recur if kernel else fused).format(layer), at,
                        400 + 10 * layer + run, {}))
        ops.append((attn.format(1, 1), start + 2100, 30, {}))
    # a mixed program runs XLA's rule, whatever the decode step runs
    modules.append(("jit_ff_step_c128_t256(4)", 7000, 900, {"run_id": 5}))
    ops.append((attn.format(128, 128), 7010, 50, {}))
    ops.append((fused.format(9), 7100, 700, {}))
    return {"/device:TPU:0": {"XLA Ops": ops, "XLA Modules": modules},
            "/host:CPU": {"python3": [("bench.traced", 0, 9000, {})]}}


def test_the_kernel_is_found_by_name_and_the_step_by_its_first_result():
    reader = spec.load_module("per_layer", METRIC)
    t = reduce.Trace(_planes())
    ctx = _ctx(trace=t)
    # eight calls: 400, 401, 410, 411, 420, 421, 430, 431 ns
    assert reader.call_ms(ctx) == pytest.approx((411 + 420) / 2 * 1e-6)
    # o stands first in the call's result, [slots, 1, ...]: the decode
    # program is still keyed 1, though its first kernel is no attention call
    assert sorted(t.programs) == [1, 128] and len(t.programs[1]) == 2
    assert t.program_ms(1) == pytest.approx(2500e-6)
    # the roofline needs a traced run's notes of its turns: nothing here
    assert reader.read(ctx) is None
    flops, nbytes = spec.load_module("counts", "gdn_recur_kernel").count(SMALL, MIX)
    assert reader.read(_ctx(trace=t, tracer=_tracer())) == pytest.approx(
        100 * max(flops, nbytes) / 1e9 / 415.5e-9)


def test_a_program_without_the_kernel_reads_nothing():
    """The parent's programs and ``kernels="xla"``: None, nothing raised."""
    reader = spec.load_module("per_layer", METRIC)
    parent = reduce.Trace(_planes(kernel=False))
    assert sorted(parent.programs) == [1, 128]
    for ctx in (_ctx(trace=parent, tracer=_tracer()), _ctx(trace=parent), _ctx()):
        assert reader.call_ms(ctx) is None and reader.read(ctx) is None


def test_the_olmo_cell_alone_reports_it():
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry, = (m for m in bench["per_layer"] if m["name"] == METRIC)
    assert entry == dict(
        name=METRIC, unit="%", better="higher", source="device_trace",
        layer="kernels", moves="tpot_p90_ms", workloads=[CELL])
    for cell in bench["workloads"]:
        names = {m["name"] for m in spec.Cell(cell["name"]).per_layer}
        assert (METRIC in names) == (cell["name"] == CELL), cell["name"]
