"""The smallthinker-21b-a3b configuration's part of the benchmark (PR
50): the configuration file against the catalog's keys and the issue's
arithmetic; the count modules on a hand-worked step and at the
published widths; each new reader on hand-made trace events, and
nothing read, nothing raised, where there is nothing to read; the
traffic file's worst case against both classes' tables."""
import json
import os
import types

import pytest

from benchmarks.harness import lengths, reduce, spec
from benchmarks.harness.loop import Window

CELL = "smallthinker-21b-a3b.doc12k-closed"
SMALL = dict(
    hidden_size=8, vocab_size=100, num_attention_heads=4, num_key_value_heads=2,
    head_dim=2, num_hidden_layers=4, sliding_window_layout=[0, 1, 1, 1],
    sliding_window_size=16, moe_ffn_hidden_size=4, moe_num_primary_experts=8,
    moe_num_active_primary_experts=2, serving={"page_size": 4})
# three decoding rows at 50 keys each, one prefilling row of a 60-token
# prompt, half-way (context 30), feeding 10 tokens
MIX = dict(decode_rows=3, decode_ctx=150, prefill_rows=1, prefill_tokens=10,
           prefill_row_ctx=30, prefill_tok_ctx=10 * 30.5)


def _file():
    with open(os.path.join(spec.BENCH_DIR, "configs", "smallthinker-21b-a3b.json")) as f:
        return json.load(f)


def _count(name, cfg, mix, fn="count"):
    return getattr(spec.load_module("counts", name), fn)(cfg, mix)


def test_counts_on_a_hand_worked_step():
    D, V, H, KV, d, E, K, W, page, F = 8, 100, 4, 2, 2, 8, 2, 16, 4, 4
    tokens, rows = 13, 4
    # a window layer's call: a decoding row's query sees 16 of its 50
    # keys, in pages 35 // 4 .. 50 // 4 (5 pages); the prefilling row's
    # token j (context 30 + j) sees 16, its keys lie in lines 15 .. 39:
    # pages 3 .. 9 (7 pages)
    pairs_w = 3 * 16 + 10 * 16
    lines_w = 3 * 5 * page + 7 * page
    # a full layer's: 51 keys a decoding query (pages 0 .. 12), the
    # prefilling tokens 31 .. 40 keys (pages 0 .. 9)
    pairs_f = 3 * 51 + sum(range(31, 41))
    lines_f = 3 * 13 * page + 10 * page
    kv_line = 2 * KV * d
    io = 2 * tokens * 2 * 8 * d   # each K/V head's group of 2 padded to 8
    assert _count("window_kernel", SMALL, MIX) == (
        pytest.approx(4 * H * d * pairs_w), pytest.approx(2 * (kv_line * lines_w + io)))
    assert _count("full_kernel", SMALL, MIX) == (
        pytest.approx(4 * H * d * pairs_f), pytest.approx(2 * (kv_line * lines_f + io)))
    attn, router, expert = 2 * D * H * d + 2 * D * KV * d, D * E, 3 * D * F
    hit = E * (1 - (1 - K / E) ** tokens)
    flops = (2 * tokens * 4 * (attn + router) + 2 * 4 * tokens * K * expert
             + 4 * H * d * (3 * pairs_w + pairs_f) + 2 * rows * D * V)
    nbytes = 2 * (4 * (attn + router) + 4 * hit * expert + D * V
                  + kv_line * (3 * (lines_w + tokens) + lines_f + tokens) + tokens * D)
    assert _count("smallthinker_step", SMALL, MIX) == (
        pytest.approx(flops), pytest.approx(nbytes))


def test_a_row_inside_its_first_window_counts_what_it_sees():
    """A prefilling row at context 4 of a window of 16, feeding 8
    tokens: they see 5 .. 12 keys, as in a full layer."""
    mix = dict(decode_rows=0, decode_ctx=0, prefill_rows=1, prefill_tokens=8,
               prefill_row_ctx=4, prefill_tok_ctx=0)
    window = _count("window_kernel", SMALL, mix)
    assert window == _count("full_kernel", SMALL, mix)
    assert window[0] == pytest.approx(4 * 4 * 2 * sum(range(5, 13)))


def test_counts_at_the_published_widths_are_the_issues():
    """A prompt of 12 800 tokens over 12 layers, chunk by chunk: the
    issue's 3 x 1.17 TFLOP in the full layers and 9 x 0.63 in the
    window layers; the matmuls of 1024 tokens are 1.4 TFLOP a step,
    which reads 10.35 GB of weights (all 11.12 GB less the embedding
    table, of which a step gathers its tokens' rows) and 0.97 GB of
    K/V: 51 pages a row in 3 full layers, 33 in 9 window layers."""
    cfg = _file()
    full = window = 0.0
    for c in range(0, 12800, 128):
        mix = dict(decode_rows=0, decode_ctx=0, prefill_rows=1, prefill_tokens=128,
                   prefill_row_ctx=c, prefill_tok_ctx=0)
        full += _count("full_kernel", cfg, mix)[0]
        window += _count("window_kernel", cfg, mix)[0]
    assert full / 1e12 == pytest.approx(1.17, abs=0.01)
    assert window / 1e12 == pytest.approx(0.63, abs=0.01)
    mix = dict(decode_rows=0, decode_ctx=0, prefill_rows=8, prefill_tokens=1024,
               prefill_row_ctx=8 * 6400, prefill_tok_ctx=0)
    flops, nbytes = _count("smallthinker_step", cfg, mix)
    attention = 9 * _count("window_kernel", cfg, mix)[0] + 3 * _count("full_kernel", cfg, mix)[0]
    assert (flops - attention) / 1e12 == pytest.approx(1024 * 1.35e-3, rel=0.02)
    assert nbytes / 1e9 == pytest.approx(10.35 + 0.97, abs=0.02)


#: the published ``config.json`` (the configuration's ``source``), as the
#: catalog of model configurations quotes it: every key and its value
PUBLISHED = {
    "head_dim": 128, "hidden_size": 2560, "max_position_embeddings": 16384,
    "model_name": "smallthinker_21b_instruct", "moe_ffn_hidden_size": 768,
    "moe_num_active_primary_experts": 6, "moe_num_primary_experts": 64,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "num_attention_heads": 28, "num_hidden_layers": 52,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_layout": [0, 1, 1, 1] * 13, "rope_scaling": None,
    "rope_theta": 1500000, "sliding_window_layout": [0, 1, 1, 1] * 13,
    "sliding_window_size": 4096, "tie_word_embeddings": False,
    "vocab_size": 151936,
}
SOURCE = ("https://huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct/"
          "blob/main/config.json")


def test_the_file_holds_the_catalogs_keys_and_cuts_the_depth_alone():
    cfg = _file()
    published = PUBLISHED
    cut = set(cfg["reduced"])
    assert cut == {"num_hidden_layers", "sliding_window_layout", "rope_layout"}
    for key, value in published.items():
        if key not in cut:
            assert cfg[key] == value, key
    assert cfg["source"] == SOURCE
    assert cfg["num_hidden_layers"] == 12
    assert cfg["sliding_window_layout"] == cfg["rope_layout"] == [0, 1, 1, 1] * 3
    assert published["sliding_window_layout"][:12] == cfg["sliding_window_layout"]
    for key in ("router input", "window", "secondary experts"):
        assert cfg["assumed"][key]
    tol = cfg["tolerance"]
    assert tol["metric"] == "rms_share" and tol["control"] == "ref_int8"
    for key in ("sound", "control", "control without the window", "why this limit"):
        assert tol["readings"][key]
    bench = json.load(open(os.path.join(spec.ROOT, "BENCHMARK.json")))
    entry = [c for c in bench["configs"] if c["name"] == cfg["name"]][0]
    assert set(entry["reduced"]) == cut and entry["source"] == cfg["source"]


def test_sizes_are_the_issues_arithmetic():
    """A layer 398.6 M parameters, 12 layers and the two ends 5.56 G:
    11.12 GB of bf16; the pool by class at 8 slots: 129 pages a slot of
    3 layers, 34 of 9 layers, 2 KB a line and layer."""
    import jax.numpy as jnp

    from benchmarks.harness import model
    from flexflow_tpu.serve.paging import window_table_pages

    cfg = _file()
    family = model.family_of(cfg)
    dc = model.decoder_config(cfg)
    assert family.num_params(dc) * 2 / 1e9 == pytest.approx(11.12, abs=0.01)
    serving = model.serving_config(cfg)
    per = window_table_pages(dc.sliding_window, serving.mixed_chunk, serving.page_size)
    assert (serving.pages_per_slot, per) == (129, 34)
    assert serving.num_pages == 8 * (129 + 34)
    import jax

    cache = jax.eval_shape(lambda: family.init_paged_kv_cache(
        dc, 8 * 129, 128, jnp.bfloat16, class_pages={"full": 8 * 129, "window": 8 * 34}))
    assert cache["k"].shape == (3, 8 * 129 + 1, 128, 512)
    assert cache["k_win"].shape == (9, 8 * 34 + 1, 128, 512)
    pool = sum(a.size * 2 for a in cache.values())
    assert pool / 1e9 == pytest.approx(0.81 + 0.64, abs=0.02)
    # kept whole, the window layers would need 9 x 8 x 129 pages
    assert 9 * 8 * 129 * 128 * 2048 / 1e9 == pytest.approx(2.43, abs=0.01)


def test_the_traffics_worst_case_fits_both_tables():
    cell = spec.Cell(CELL)
    t = cell.traffic
    assert (t["kind"], t["clients"], t["order"], t["warmup_s"]) == ("closed", 8, 50, 15)
    assert t["clients"] == cell.config["serving"]["max_requests_per_batch"]
    longest = lengths.quantile(t["prompt_tokens"], 1 - 1e-9)
    answer = lengths.quantile(t["answer_tokens"], 1 - 1e-9)
    assert (longest, answer) == (15360, 64)
    worst = longest + answer + 5
    assert worst <= cell.config["serving"]["max_sequence_length"]
    assert -(-worst // 128) == 121 <= 129
    # every client at its worst: the full class's pool holds them all
    assert 8 * 121 <= 8 * 129
    assert {m["name"] for m in cell.end_to_end} == {
        "ttft_p50_ms", "out_tokens_per_s", "setup_s"}
    names = {m["name"] for m in cell.per_layer}
    assert {"cache.window_live_pct", "kernel.window_roofline.mixed",
            "kernel.full_roofline.mixed", "step.swa_mixed_ms",
            "step.swa_mixed_roofline", "moe.experts_hit_pct",
            "step.sub_ms.moe_route"} <= names


# --- the readers -------------------------------------------------------------


def _ctx(trace=reduce.NoTrace(), stats=None, cfg=None, tracer=None):
    win = Window()
    if stats:
        win.stats_open, win.stats_close = stats
    return reduce.Context(
        window=win, setup_s=0.0, cfg=cfg or SMALL, trace=trace, tracer=tracer,
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        engine_serving=types.SimpleNamespace(mixed_chunk=128))


def read(name, ctx):
    return spec.load_module("per_layer", name).read(ctx)


READERS = ("cache.window_live_pct", "kernel.window_roofline.mixed",
           "kernel.full_roofline.mixed", "step.swa_mixed_ms",
           "step.swa_mixed_roofline")


def test_window_live_share_reads_the_counters():
    a = types.SimpleNamespace(window_pages_live_peak=0, window_pages_unfreed_peak=0)
    b = types.SimpleNamespace(window_pages_live_peak=272, window_pages_unfreed_peak=800)
    assert read("cache.window_live_pct", _ctx(stats=(a, b))) == pytest.approx(34.0)
    # a family with one class of page, a program before PR 50: nothing
    assert read("cache.window_live_pct", _ctx(stats=(a, a))) is None
    old = (types.SimpleNamespace(steps=1), types.SimpleNamespace(steps=2))
    assert read("cache.window_live_pct", _ctx(stats=old)) is None


def _planes(window_layers=True):
    K = ', custom_call_target="tpu_custom_call"'
    full = "%ff_ragged_paged_c128.{} = bf16[8,128,4,7,128]{{4,3,2,1,0}} custom-call(%q)" + K
    win = "%ff_ragged_paged_c128_win.{} = bf16[8,128,4,7,128]{{4,3,2,1,0}} custom-call(%q)" + K
    glu = "%ff_moe_grouped_glu_t16.{} = bf16[4096,768]{{1,0}} custom-call(%x)" + K
    ops, modules = [], []
    for i, (start, dur) in enumerate([(1000, 9000), (11000, 11000)]):
        modules.append((f"jit_ff_step_c128_t{256 << i}(3)", start, dur, {"run_id": i}))
        ops.append((full.format(i), start + 10, 400 + 100 * i, {}))
        ops.append((glu.format(i), start + 500, 300, {}))
        if window_layers:
            ops += [(win.format(3 * i + j), start + 1000 + 1000 * j, 200 + 20 * i, {})
                    for j in range(3)]
    # a decode program: its calls are another chunk's
    modules.append(("jit_ff_step_c1(5)", 30000, 900, {"run_id": 9}))
    ops.append(("%ff_ragged_paged_c1.1 = bf16[8,1,4,7,128]{4,3,2,1,0} custom-call(%q)" + K,
                30010, 50, {}))
    if window_layers:
        ops.append(("%ff_ragged_paged_c1_win.1 = bf16[8,1,4,7,128]{4,3,2,1,0} custom-call(%q)"
                    + K, 30100, 20, {}))
    return {"/device:TPU:0": {"XLA Ops": ops, "XLA Modules": modules},
            "/host:CPU": {"python3": [("bench.traced", 0, 40000, {})]}}


def test_the_two_calls_are_found_by_name():
    t = reduce.Trace(_planes())
    ctx = _ctx(trace=t)
    window = spec.load_module("per_layer", "kernel.window_roofline.mixed")
    # six window calls of 200, 200, 200, 220, 220, 220 ns; two full of 400, 500
    assert window.call_ms(ctx) == pytest.approx(210e-6)
    assert window.call_ms(ctx, suffix="") == pytest.approx(450e-6)
    assert read("step.swa_mixed_ms", ctx) == pytest.approx(10000e-6)
    assert t.program_ms(128) == pytest.approx(10000e-6)   # keyed by the FIRST kernel
    # the shares need a traced run's notes of its turns: nothing here
    for name in READERS[1:]:
        if name != "step.swa_mixed_ms":
            assert read(name, ctx) is None


def test_the_shares_over_a_traced_runs_notes():
    """With the loop's notes of its turns (8 rows prefilling prompts of
    12 800, 1024 tokens a step) the shares are the counts over the
    times the events give, and none can pass 100."""
    cfg = _file()
    t = reduce.Trace(_planes())
    a = types.SimpleNamespace(mixed_steps=0, prefill_tokens=0)
    b = types.SimpleNamespace(mixed_steps=2, prefill_tokens=2048)
    tracer = types.SimpleNamespace(rows=[(0, 0, 8, 8 * 12800)] * 2,
                                   stats_start=a, stats_stop=b)
    ctx = _ctx(trace=t, cfg=cfg, tracer=tracer)
    mix = dict(decode_rows=0.0, decode_ctx=0.0, prefill_rows=8.0, prefill_tokens=1024.0,
               prefill_row_ctx=8 * 6400.0, prefill_tok_ctx=1024 * 6400.5)
    for name, counter, seconds in (
            ("kernel.window_roofline.mixed", "window_kernel", 210e-9),
            ("kernel.full_roofline.mixed", "full_kernel", 450e-9),
            ("step.swa_mixed_roofline", "smallthinker_step", 10000e-9)):
        flops, nbytes = _count(counter, cfg, mix)
        least = max(flops / 197e12, nbytes / 819e9)
        assert read(name, ctx) == pytest.approx(100 * least / seconds)


def test_a_program_without_window_layers_reads_nothing():
    """The parent's program, or a family with one kind of attention
    layer: the accepted kernel name alone is no full layer of this
    kind."""
    t = reduce.Trace(_planes(window_layers=False))
    for name in READERS[1:]:
        assert read(name, _ctx(trace=t)) is None


@pytest.mark.parametrize("name", READERS)
def test_nothing_to_read_is_nothing(name):
    assert read(name, _ctx()) is None
