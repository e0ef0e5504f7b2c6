"""``moe.ffn_roofline.mixed`` (PR 36): the count module over the generic
decoder's keys against a hand sum, and the reader that finds a sparse
layer's grouped matmuls by NAME in the mixed programs of a window, on
hand-made events: the mean over programs of two widths by count, nothing
on a program that computes every expert (the parent's), nothing without
a trace."""
import types

import pytest

from benchmarks.harness import reduce, spec
from benchmarks.harness.loop import Window

SMALL = dict(hidden_size=8, intermediate_size=24, num_attention_heads=4,
             num_key_value_heads=2, num_hidden_layers=2, vocab_size=100,
             num_local_experts=4, num_experts_per_tok=2)
MIX = dict(decode_rows=3, decode_ctx=150, prefill_rows=1, prefill_tokens=10,
           prefill_row_ctx=10, prefill_tok_ctx=105.0)
K = ', custom_call_target="tpu_custom_call"'


def _ctx(trace=reduce.NoTrace(), tracer=None):
    return reduce.Context(
        window=Window(), setup_s=0.0, cfg=SMALL,
        peaks={"bf16_flops_per_s": 1e9, "hbm_bytes_per_s": 1e9}, trace=trace,
        tracer=tracer, log=lambda msg: None,
        engine_serving=types.SimpleNamespace(mixed_chunk=128))


def test_routed_ffn_counts_against_a_hand_sum():
    flops, nbytes = spec.load_module("counts", "routed_ffn").count(SMALL, MIX)
    D, F, E, K_, tokens = 8, 24, 4, 2, 13
    hit = E * (1 - (1 - K_ / E) ** tokens)
    assert flops == pytest.approx(2 * tokens * K_ * 3 * D * F)
    assert nbytes == pytest.approx(2 * (hit * 3 * D * F + 2 * tokens * K_ * D))


def _planes(grouped=True):
    attn = "%ff_ragged_paged_c128.{} = bf16[16,128,8,4,128]{{4,3,2,1,0}} custom-call(%q)" + K
    glu = "%ff_moe_grouped_glu_t128.{} = bf16[2048,24]{{1,0}} custom-call(%x)" + K
    down = "%ff_moe_grouped_down_t128.{} = f32[2048,8]{{1,0}} custom-call(%a)" + K
    einsum = "%fusion.{} = bf16[512,4,24]{{2,1,0}} fusion(%h)"
    ops, modules = [], []
    # two steps at the narrow rung, one at the wide one: 2 layers each
    for run, (start, name, ms) in enumerate([
            (1000, "jit_ff_step_c128_t512(1)", 30),
            (3000, "jit_ff_step_c128_t512(1)", 34),
            (5000, "jit_ff_step_c128_t1024(2)", 50)]):
        modules.append((name, start, 900, {"run_id": run}))
        for layer in range(2):
            at = start + 10 + 400 * layer
            ops.append((attn.format(layer), at, 20, {}))
            if grouped:
                ops.append((glu.format(layer), at + 30, 2 * ms, {}))
                ops.append((down.format(layer), at + 200, ms, {}))
            else:
                ops.append((einsum.format(layer), at + 30, 300, {}))
    # a C=1 program's grouped matmuls are not a mixed step's
    modules.append(("jit_ff_step_c1(3)", 7000, 500, {"run_id": 9}))
    ops.append(("%ff_ragged_paged_c1.1 = bf16[16,1,8,4,128]{4,3,2,1,0} custom-call(%q)" + K,
                7010, 5, {}))
    if grouped:
        ops.append(("%ff_moe_grouped_glu_t16.1 = bf16[160,24]{1,0} custom-call(%x)" + K,
                    7100, 999, {}))
    return {"/device:TPU:0": {"XLA Ops": ops, "XLA Modules": modules},
            "/host:CPU": {"python3": [("bench.traced", 0, 9000, {})]}}


def test_grouped_matmuls_of_the_mixed_programs_are_found_by_name():
    reader = spec.load_module("per_layer", "moe.ffn_roofline.mixed")
    t = reduce.Trace(_planes())
    assert sorted(t.programs) == [1, 128]       # keyed by the attention call
    # (2 x 90, 2 x 102, 2 x 150) ns a program over 2 layers: the mean
    assert reader.layer_ms(_ctx(trace=t)) == pytest.approx(
        (90 + 102 + 150) / 3 * 1e-6)
    # the roofline needs a traced run's notes of its turns: nothing here
    assert reader.read(_ctx(trace=t)) is None
    # with them: the least time at the window's mean mix over that mean
    zero = types.SimpleNamespace(mixed_steps=0, prefill_tokens=0)
    stop = types.SimpleNamespace(mixed_steps=3, prefill_tokens=30)
    tracer = types.SimpleNamespace(
        rows=[(3, 150, 1, 20)] * 3, stats_start=zero, stats_stop=stop)
    flops, nbytes = spec.load_module("counts", "routed_ffn").count(SMALL, MIX)
    assert reader.read(_ctx(trace=t, tracer=tracer)) == pytest.approx(
        100 * max(flops, nbytes) / 1e9 / (114e-9))
    # a program that computes every expert for every token: nothing, no error
    parent = reduce.Trace(_planes(grouped=False))
    assert reader.layer_ms(_ctx(trace=parent)) is None
    assert reader.read(_ctx(trace=parent, tracer=tracer)) is None
    assert reader.read(_ctx()) is None           # no trace at all
