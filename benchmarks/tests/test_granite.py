"""The granite-4.0-h-micro configuration's part of the benchmark (PR
46): the count modules against hand sums at one small mix and against
the issue's arithmetic at the published widths; the configuration file
against the catalog's keys; nothing read, and nothing raised, without a
trace."""
import json
import os
import types

import pytest

from benchmarks.harness import reduce, spec
from benchmarks.harness.loop import Window

SMALL = dict(
    hidden_size=8, shared_intermediate_size=24, vocab_size=100,
    num_attention_heads=4, num_key_value_heads=2, num_hidden_layers=5,
    mamba_n_heads=3, mamba_d_head=2, mamba_d_state=4, mamba_d_conv=4,
    layer_types=["mamba"] * 3 + ["attention", "mamba", "attention"])
# three decoding rows at 50 keys each, one prefilling row of a 20-token
# prompt, half-way, feeding 10 tokens
MIX = dict(decode_rows=3, decode_ctx=150, prefill_rows=1, prefill_tokens=10,
           prefill_row_ctx=10, prefill_tok_ctx=10 * 10.5)


def test_counts_against_hand_sums():
    step = spec.load_module("counts", "granite_hybrid_step").count(SMALL, MIX)
    mixer = spec.load_module("counts", "ssm_mixer").count(SMALL, MIX)
    D, F, V, H, KV, d, Hs, P, N, taps = 8, 24, 100, 4, 2, 2, 3, 2, 4, 4
    inner = Hs * P
    channels = inner + 2 * N
    ssm = D * (inner + channels + Hs) + inner * D
    attn, ffn = 2 * D * H * d + 2 * D * KV * d, 3 * D * F
    tokens, rows = 13, 4
    # the first 5 of layer_types: 4 mamba, 1 attention
    per_token = 4 * ssm + 1 * attn + 5 * ffn
    scan = (Hs * (3 * (5 * P * N + 2 * P) + 10 * (4 * P * N + 128 * (P + 1) + 2 * P))
            + 10 * 128 * N + tokens * 2 * taps * channels)
    state = 2 * rows * (4 * Hs * P * N + 2 * (taps - 1) * channels)
    flops = (2 * tokens * per_token + 4 * H * d * (150 + 105) * 1 + 4 * scan
             + 2 * rows * D * V)
    nbytes = 2 * (per_token + D * V + 1 * 2 * KV * d * (150 + 10 + tokens)) + 4 * state
    assert step == (pytest.approx(flops), pytest.approx(nbytes))
    assert mixer == (pytest.approx(4 * (2 * tokens * ssm + scan)),
                     pytest.approx(4 * (2 * (ssm + 2 * tokens * D) + state)))


def _file():
    with open(os.path.join(spec.BENCH_DIR, "configs", "granite-4.0-h-micro.json")) as f:
        return json.load(f)


def test_counts_at_the_published_widths_are_the_issues():
    """64 rows at a mean context of 350: 20.0 ms of memory a step by the
    chip's peak (7.8 of weights, 11.8 of state), of which the mamba
    mixers (weights and states) are 14.2 ms."""
    mix = dict(decode_rows=64, decode_ctx=64 * 350, prefill_rows=0,
               prefill_tokens=0, prefill_row_ctx=0, prefill_tok_ctx=0)
    _, step = spec.load_module("counts", "granite_hybrid_step").count(_file(), mix)
    _, mixer = spec.load_module("counts", "ssm_mixer").count(_file(), mix)
    assert step / 819e9 * 1e3 == pytest.approx(20.0, abs=0.1)
    assert mixer / 819e9 * 1e3 == pytest.approx(14.2, abs=0.1)


def test_the_file_holds_the_catalogs_keys_and_cuts_nothing():
    cfg = _file()
    assert cfg["reduced"] == {} and cfg["num_hidden_layers"] == 40
    assert len(cfg["layer_types"]) == 40
    assert [i for i, t in enumerate(cfg["layer_types"]) if t == "attention"] == [5, 15, 25, 35]
    tol = cfg["tolerance"]
    assert tol["metric"] == "rms_share" and tol["control"] == "ref_int8"
    for key in ("sound", "control", "why this limit"):
        assert tol["readings"][key]
    bench = json.load(open(os.path.join(spec.ROOT, "BENCHMARK.json")))
    entry = [c for c in bench["configs"] if c["name"] == cfg["name"]][0]
    assert entry["reduced"] == [] and entry["source"] == cfg["source"]


def _ctx(trace=reduce.NoTrace()):
    return reduce.Context(
        window=Window(), setup_s=0.0, cfg=SMALL, peaks=None, trace=trace,
        engine_serving=types.SimpleNamespace(mixed_chunk=128))


@pytest.mark.parametrize("name", ["step.ssm_decode_roofline",
                                  "mixer.ssm_roofline.decode",
                                  "mixer.ssm_roofline.mixed"])
def test_nothing_to_read_is_nothing(name):
    assert spec.load_module("per_layer", name).read(_ctx()) is None
