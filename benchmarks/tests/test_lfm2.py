"""The LFM2-MoE configuration's part of the benchmark (PR 34): the
count modules against hand sums at one small mix; the reader that finds
a sparse layer's grouped matmuls by NAME, on hand-made events and on a
recorded slice of ``lfm2-24b-a2b.decode-wide-closed``
(``trace_sample_lfm2.json``, written by ``tools/phases.py --sample``,
the expected values in the file); the counter readers; and the control
for the configuration's tolerance at the rehearsal's size."""
import os
import types

import numpy as np
import pytest

from benchmarks.harness import reduce, spec
from benchmarks.harness.loop import Window
from benchmarks.tools import phases

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "lfm2-24b-a2b.decode-wide-closed"

SMALL = dict(
    hidden_size=8, intermediate_size=24, moe_intermediate_size=4, vocab_size=100,
    num_attention_heads=4, num_key_value_heads=2, num_hidden_layers=4,
    num_dense_layers=1, num_experts=8, num_experts_per_tok=2, conv_L_cache=3,
    layer_types=["conv", "full_attention", "conv", "conv", "full_attention"])
# three decoding rows at 50 keys each, one prefilling row of a 20-token
# prompt, half-way, feeding 10 tokens
MIX = dict(decode_rows=3, decode_ctx=150, prefill_rows=1, prefill_tokens=10,
           prefill_row_ctx=10, prefill_tok_ctx=10 * 10.5)


def test_lfm2_counts_against_hand_sums():
    step = spec.load_module("counts", "lfm2_step").count(SMALL, MIX)
    ffn = spec.load_module("counts", "moe_ffn").count(SMALL, MIX)
    D, F, Fm, V, H, KV, d, E, K = 8, 24, 4, 100, 4, 2, 2, 8, 2
    conv, attn = 4 * D * D, 2 * D * H * d + 2 * D * KV * d
    expert, router = 3 * D * Fm, D * E
    tokens, rows = 13, 4
    # the first 4 of layer_types: 3 conv, 1 attention; 1 dense, 3 sparse
    per_token = 3 * conv + 1 * attn + 1 * 3 * D * F + 3 * router
    hit = E * (1 - (1 - K / E) ** tokens)
    flops = (2 * tokens * per_token + 2 * 3 * tokens * K * expert
             + 4 * H * d * (150 + 105) * 1 + 2 * 3 * D * tokens * 3 + 2 * rows * D * V)
    nbytes = 2 * (per_token + 3 * hit * expert + D * V
                  + 1 * 2 * KV * d * (150 + 10 + tokens)
                  + 2 * rows * 3 * 2 * D + tokens * D)
    assert step == (pytest.approx(flops), pytest.approx(nbytes))
    assert ffn == (pytest.approx(2 * tokens * K * expert),
                   pytest.approx(2 * (hit * expert + 2 * tokens * K * D)))
    # a chip that holds experts 2..5 of 8 computes half the pairs
    half = spec.load_module("counts", "moe_ffn").count(
        dict(SMALL, experts_held=[2, 6]), MIX)
    assert half[0] == pytest.approx(tokens * K * expert)


def _ctx(trace=reduce.NoTrace(), stats=None, cfg=None):
    win = Window()
    if stats:
        win.stats_open, win.stats_close = stats
    return reduce.Context(
        window=win, setup_s=0.0, cfg=cfg or SMALL, peaks=None, trace=trace,
        engine_serving=types.SimpleNamespace(mixed_chunk=128))


def read(name, ctx):
    return spec.load_module("per_layer", name).read(ctx)


def test_expert_counters():
    a = types.SimpleNamespace(moe_pairs=100, moe_experts_hit=10,
                              moe_experts_held=80, moe_load_max=40)
    b = types.SimpleNamespace(moe_pairs=612, moe_experts_hit=136,
                              moe_experts_held=208, moe_load_max=152)
    ctx = _ctx(stats=(a, b))
    assert read("moe.experts_hit_pct", ctx) == pytest.approx(100 * 126 / 128)
    # 112 tokens on the fullest experts over 512 pairs / 8 experts
    assert read("moe.load_max_over_mean", ctx) == pytest.approx(112 * 8 / 512)
    for name in ("moe.experts_hit_pct", "moe.load_max_over_mean"):
        assert read(name, _ctx(stats=(a, a))) is None
        # a program before PR 34 keeps no such counters: nothing, no error
        old = (types.SimpleNamespace(steps=1), types.SimpleNamespace(steps=2))
        assert read(name, _ctx(stats=old)) is None
        # nor does a family with no routed layer, whose counters stay 0
        assert read(name, _ctx(stats=(a, a), cfg={})) is None


def test_grouped_matmuls_are_found_by_name():
    K = ', custom_call_target="tpu_custom_call"'
    attn = "%ff_ragged_paged_c1.{} = bf16[64,1,8,4,64]{{4,3,2,1,0}} custom-call(%q)" + K
    dot = "%ragged-dot-none.{} = f32[256,1536]{{1,0}} custom-call(%x)" + K
    meta = "%ragged-dot-metadata.{} = (s32[257]{{0}}, s32[256]{{0}}) custom-call(%p)" + K
    ops = []
    for start in (1000, 3000):            # two decode programs, 3 sparse layers each
        ops.append((attn.format(start), start + 10, 20, {}))
        for layer in range(3):
            at = start + 100 + 200 * layer
            ops.append((meta.format(layer), at, 5, {}))
            ops += [(dot.format(3 * layer + i), at + 10 + 40 * i, 30 + (start == 3000), {})
                    for i in range(3)]
    ops.append((dot.format(99), 5100, 500, {}))   # in a mixed program: not counted
    modules = [("jit_ff_step_c1(7)", 1000, 900, {"run_id": 1}),
               ("jit_ff_step_c1(7)", 3000, 900, {"run_id": 2}),
               ("jit_ff_step_c128_t2048(9)", 5000, 900, {"run_id": 3})]
    ops.append(("%ff_ragged_paged_c128.1 = bf16[64,128,8,4,64]{4,3,2,1,0} custom-call(%q)" + K,
                5010, 50, {}))
    planes = {"/device:TPU:0": {"XLA Ops": ops, "XLA Modules": modules},
              "/host:CPU": {"python3": [("bench.traced", 0, 7000, {})]}}
    t = reduce.Trace(planes)
    ctx = _ctx(trace=t)
    reader = spec.load_module("per_layer", "moe.ffn_roofline.decode")
    # (3 x (5 + 90) and 3 x (5 + 93)) ns a program over 3 layers: the median
    assert reader.layer_ms(ctx) == pytest.approx((95 + 98) / 2 * 1e-6)
    assert t.program_ms(1) == pytest.approx(900e-6)   # keyed by the FIRST kernel
    # the rooflines need a traced run's notes of its turns: nothing here
    assert reader.read(ctx) is None
    assert read("step.moe_decode_roofline", ctx) is None
    # a program without the routed layer (the parent's): nothing, no error
    plain = reduce.Trace({"/device:TPU:0": {"XLA Ops": ops[:1], "XLA Modules": modules[:1]},
                          "/host:CPU": {"python3": [("bench.traced", 0, 7000, {})]}})
    assert reader.layer_ms(_ctx(trace=plain)) is None
    for name in ("moe.ffn_roofline.decode", "step.moe_decode_roofline"):
        assert read(name, _ctx()) is None               # no trace at all


@pytest.fixture(scope="module")
def recorded():
    path = os.path.join(HERE, "trace_sample_lfm2.json")
    if not os.path.exists(path):
        pytest.skip("no recorded slice of the LFM2 cell yet")
    planes, rest = phases.load_sample(path)
    return reduce.Trace(planes), rest["expect"]


def test_recorded_lfm2_slice(recorded):
    """A stretch of the new cell's own trace: the decode program is
    found by its first kernel (the attention call, ahead of any grouped
    matmul), the grouped matmuls are in it by name, and the readers give
    what they gave on the chip."""
    t, want = recorded
    cell = spec.Cell(CELL)
    ctx = _ctx(trace=t, cfg=cell.config)
    assert t.program_ms(1) == pytest.approx(want["step.decode_ms"], rel=1e-9)
    reader = spec.load_module("per_layer", "moe.ffn_roofline.decode")
    assert reader.layer_ms(ctx) == pytest.approx(want["moe_ffn_layer_ms"], rel=1e-9)
    kernels = {name.split(".")[0] for name, _, _, kernel, *_ in t.ops if kernel}
    assert "ff_ragged_paged_c1" in kernels
    assert any(k.startswith(reader.NAMES) for k in kernels)
    # no all-expert product among the operations of the slice
    assert not [shape for _, shape, *_ in t.ops if ",64,1536]" in shape]


def test_control_fails_and_served_passes_for_lfm2():
    """The configuration's comparison at the rehearsal's size (float32,
    CPU, interpret-mode kernels, 64 experts top-4, two layers: a dense
    conv layer and a sparse attention layer): the served path passes
    ``probe.verdict``, the reference computed in int8 does not."""
    from benchmarks import run as bench_run
    from benchmarks.harness import model, probe

    cell = spec.Cell(CELL)
    assert cell.config["tolerance"]["control"] == "ref_int8"
    bench_run.tiny(cell)
    config = cell.config
    reference = spec.load_module("references", config["reference"])
    quiet = lambda msg: None
    for seed in (1, 2):
        llm, params = model.build_server(config, seed)
        seqs, judged = probe.served_logits(llm.engine, cell.traffic,
                                           np.random.default_rng(seed))
        want = probe.reference_rows(config, params, seqs, judged)
        assert probe.verdict(config, probe.against(config, want, judged), quiet)
        logits = reference.judged_logits(params, config, *want[1], control_bits=8)[0]
        control = [(row, pos, logits[row, j, 0]) for (row, pos, _), j
                   in zip(judged, want[2])]
        assert not probe.verdict(config, probe.against(config, want, control), quiet)
