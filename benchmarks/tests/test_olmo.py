"""The Olmo-Hybrid configuration's part of the benchmark (PR 44): the
count modules against hand sums at one small mix and against the
issue's arithmetic at the published widths; the reader that takes a
step's device time under the scope ``ff.mixer`` by PROGRAM, on a
hand-made table; nothing read, and nothing raised, without a trace."""
import json
import os
import types

import pytest

from benchmarks.harness import reduce, spec, sublayers
from benchmarks.harness.loop import Window

SMALL = dict(
    hidden_size=8, intermediate_size=24, vocab_size=100,
    num_attention_heads=4, num_key_value_heads=4, num_hidden_layers=5,
    linear_num_value_heads=3, linear_key_head_dim=2, linear_value_head_dim=4,
    linear_conv_kernel_dim=4,
    layer_types=["linear_attention"] * 3 + ["full_attention", "linear_attention",
                                            "full_attention"])
# three decoding rows at 50 keys each, one prefilling row of a 20-token
# prompt, half-way, feeding 10 tokens
MIX = dict(decode_rows=3, decode_ctx=150, prefill_rows=1, prefill_tokens=10,
           prefill_row_ctx=10, prefill_tok_ctx=10 * 10.5)


def test_counts_against_hand_sums():
    step = spec.load_module("counts", "olmo_hybrid_step").count(SMALL, MIX)
    mixer = spec.load_module("counts", "gdn_mixer").count(SMALL, MIX)
    D, F, V, H, d, Hl, dk, dv, taps = 8, 24, 100, 4, 2, 3, 2, 4, 4
    channels = Hl * (2 * dk + dv)
    gdn = D * channels + D * 2 * Hl + 2 * D * Hl * dv
    attn, ffn = 4 * D * H * d, 3 * D * F
    tokens, rows = 13, 4
    # the first 5 of layer_types: 4 recurrent, 1 attention
    per_token = 4 * gdn + 1 * attn + 5 * ffn
    rule = (Hl * (3 * 7 * dk * dv + 10 * (6 * dk * dv + 2 * 64 * (dk + dv)))
            + tokens * 2 * taps * channels)
    state = 2 * rows * (4 * Hl * dk * dv + 2 * (taps - 1) * channels)
    flops = (2 * tokens * per_token + 4 * H * d * (150 + 105) * 1 + 4 * rule
             + 2 * rows * D * V)
    nbytes = 2 * (per_token + D * V + tokens * D
                  + 1 * 2 * H * d * (150 + 10 + tokens)) + 4 * state
    assert step == (pytest.approx(flops), pytest.approx(nbytes))
    assert mixer == (pytest.approx(4 * (2 * tokens * gdn + rule)),
                     pytest.approx(4 * (2 * (gdn + 2 * tokens * D) + state)))


def test_counts_at_the_published_widths_are_the_issues():
    """64 rows at a mean context of 350: 11.4 ms of memory a step by the
    chip's peak, of which the recurrent mixers (weights and states) are
    5.0 to 5.2 ms."""
    with open(os.path.join(spec.BENCH_DIR, "configs", "olmo-hybrid-7b.json")) as f:
        cfg = json.load(f)
    mix = dict(decode_rows=64, decode_ctx=64 * 350, prefill_rows=0,
               prefill_tokens=0, prefill_row_ctx=0, prefill_tok_ctx=0)
    _, step = spec.load_module("counts", "olmo_hybrid_step").count(cfg, mix)
    _, mixer = spec.load_module("counts", "gdn_mixer").count(cfg, mix)
    assert step / 819e9 * 1e3 == pytest.approx(11.4, abs=0.2)
    assert mixer / 819e9 * 1e3 == pytest.approx(5.1, abs=0.15)


def _ctx(trace=reduce.NoTrace()):
    return reduce.Context(
        window=Window(), setup_s=0.0, cfg=SMALL, peaks=None, trace=trace,
        engine_serving=types.SimpleNamespace(mixed_chunk=128))


def test_scope_time_is_read_by_program():
    decode = spec.load_module("per_layer", "mixer.gdn_roofline.decode")
    table = sublayers.Table(steps=13, ms={}, unscoped={}, unmatched={}, by_program={
        "jit_ff_step_c1": {"steps": 10, "ms": {"ff.mixer": 50.0, "ff.ffn": 20.0}},
        "jit_ff_step_c128_t2048": {"steps": 2, "ms": {"ff.mixer": 80.0}},
        "jit_ff_step_c128": {"steps": 1, "ms": {"ff.mixer": 70.0}},
        "jit_ff_step_c128_logits": {"steps": 1, "ms": {"ff.ffn": 5.0}},
    })
    ctx = _ctx(trace=types.SimpleNamespace(ops=(), sublayers=table))
    assert decode.scope_ms(ctx, 1) == pytest.approx(5.0)       # never the c128 ones
    assert decode.scope_ms(ctx, 128) == pytest.approx(50.0)    # the mean by count
    assert decode.scope_ms(ctx, 64) is None


@pytest.mark.parametrize("name", ["step.gdn_decode_roofline",
                                  "mixer.gdn_roofline.decode",
                                  "mixer.gdn_roofline.mixed"])
def test_nothing_to_read_is_nothing(name):
    assert spec.load_module("per_layer", name).read(_ctx()) is None
