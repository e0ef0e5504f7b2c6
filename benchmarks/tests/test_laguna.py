"""The laguna-xs.2 configuration's part of the benchmark (PR 58): the
configuration file against the catalog's keys and the issue's
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

CELL = "laguna-xs.2.agent12k-closed"
SMALL = dict(
    hidden_size=8, vocab_size=100, num_attention_heads=2, num_key_value_heads=2,
    head_dim=2, num_hidden_layers=5, intermediate_size=16,
    layer_types=["full_attention"] + ["sliding_attention"] * 3 + ["full_attention"],
    mlp_layer_types=["dense"] + ["sparse"] * 4,
    num_attention_heads_per_layer=[2, 4, 4, 4, 2], sliding_window=16,
    moe_intermediate_size=4, shared_expert_intermediate_size=4, num_experts=8,
    num_experts_per_tok=2, serving={"page_size": 4})
# three decoding rows at 50 keys each, one prefilling row of a 60-token
# prompt, half-way (context 30), feeding 10 tokens
MIX = dict(decode_rows=3, decode_ctx=150, prefill_rows=1, prefill_tokens=10,
           prefill_row_ctx=30, prefill_tok_ctx=10 * 30.5)


def _file():
    with open(os.path.join(spec.BENCH_DIR, "configs", "laguna-xs.2.json")) as f:
        return json.load(f)


def _count(name, cfg, mix):
    return spec.load_module("counts", name).count(cfg, mix)


def test_counts_on_a_hand_worked_step():
    D, V, KV, d, E, K, page, F, I = 8, 100, 2, 2, 8, 2, 4, 4, 16
    Hf, Hw = 2, 4
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
    # queries and outputs at the layer's REAL heads: 4 on a window
    # layer, 2 on a full one (each call pads a group to 8: no work)
    assert _count("window_kind_kernel", SMALL, MIX) == (
        pytest.approx(4 * Hw * d * pairs_w),
        pytest.approx(2 * (kv_line * lines_w + 2 * tokens * Hw * d)))
    assert _count("full_kind_kernel", SMALL, MIX) == (
        pytest.approx(4 * Hf * d * pairs_f),
        pytest.approx(2 * (kv_line * lines_f + 2 * tokens * Hf * d)))
    attn = lambda H: 2 * D * H * d + 2 * D * KV * d + D * H
    router, expert, shared, dense = D * E, 3 * D * F, 3 * D * F, 3 * D * I
    hit = E * (1 - (1 - K / E) ** tokens)
    assert _count("all_held_ffn", SMALL, MIX) == (
        pytest.approx(2 * tokens * K * expert),
        pytest.approx(2 * (hit * expert + 2 * tokens * K * D)))
    per_token = 2 * attn(Hf) + 3 * attn(Hw) + dense + 4 * (router + shared)
    flops = (2 * tokens * per_token + 2 * 4 * tokens * K * expert
             + 4 * d * (3 * Hw * pairs_w + 2 * Hf * pairs_f) + 2 * rows * D * V)
    nbytes = 2 * (per_token + 4 * hit * expert + D * V
                  + kv_line * (3 * (lines_w + tokens) + 2 * (lines_f + tokens))
                  + tokens * D)
    assert _count("laguna_step", SMALL, MIX) == (
        pytest.approx(flops), pytest.approx(nbytes))


def test_counts_at_the_published_widths_are_the_issues():
    """The issue's mixed step: 13 decode rows at 12.5 k lines beside
    three prefilling rows half-way through prompts of 12 288, 400
    prompt tokens: the four sparse layers' experts read whole (6.44
    GB), the decode rows' K/V on the two full layers 1.3 GB, the other
    weights and the head 0.75 GB: 12-13 ms at 819 GB/s."""
    cfg = _file()
    mix = dict(decode_rows=13, decode_ctx=13 * 12500, prefill_rows=3,
               prefill_tokens=400, prefill_row_ctx=3 * 6144, prefill_tok_ctx=0)
    s = spec.load_module("counts", "laguna_sizes")
    z = s.sizes(cfg)
    assert (z["H_full"], z["H_win"], z["n_full"], z["n_window"], z["n_dense"],
            z["n_sparse"], z["W"]) == (48, 64, 2, 3, 1, 4, 512)
    assert (z["attn_full"], z["attn_window"]) == (29_458_432, 37_879_808)
    assert (z["expert"], z["dense_ffn"]) == (3_145_728, 50_331_648)
    assert s.experts_hit(z, 413) == pytest.approx(256, abs=0.01)   # every expert
    assert s.pairs_held(z, 413) / 256 == pytest.approx(12.9, abs=0.1)
    ffn = _count("all_held_ffn", cfg, mix)
    assert ffn[1] / 1e9 == pytest.approx(6.44 / 4 + 0.03, abs=0.01)
    full, window = _count("full_kind_kernel", cfg, mix), _count("window_kind_kernel", cfg, mix)
    # a full layer's call reads 13 rows x 98 pages and 3 x 49-52 pages
    assert full[1] / 1e9 == pytest.approx((13 * 98 + 3 * 52) * 128 * 4096 / 1e9, rel=0.03)
    # a window layer's: 5 pages a decoding row, 6 a prefilling one, and
    # the step's queries and outputs at 64 heads (a quarter of the call)
    assert window[1] / 1e9 == pytest.approx(
        ((13 * 5 + 3 * 6) * 128 * 4096 + 4 * 413 * 64 * 128) / 1e9, rel=0.05)
    flops, nbytes = _count("laguna_step", cfg, mix)
    other = nbytes - 4 * ffn[1] - 2 * full[1] - 3 * window[1]
    assert 4 * 256 * z["expert"] * 2 / 1e9 == pytest.approx(6.44, abs=0.01)
    assert 2 * full[1] / 1e9 == pytest.approx(1.5, abs=0.1)   # the chunk rows' pages too
    assert other / 1e9 == pytest.approx(0.75, abs=0.03)
    assert nbytes / 819e9 * 1e3 == pytest.approx(11.3, abs=0.5)
    assert flops / 197e12 < nbytes / 819e9                       # memory binds


SOURCE = "https://huggingface.co/poolside/Laguna-XS.2/blob/main/config.json"
#: the published ``config.json`` (the configuration's ``source``), as the
#: catalog of model configurations quotes it: every key and its value
PUBLISHED = {
    "model_type": "laguna", "vocab_size": 100352, "hidden_size": 2048,
    "intermediate_size": 8192, "num_hidden_layers": 40,
    "num_attention_heads": 48, "num_key_value_heads": 8, "head_dim": 128,
    "max_position_embeddings": 262144, "attention_bias": False,
    "rms_norm_eps": 1e-06, "num_experts": 256, "num_experts_per_tok": 8,
    "moe_intermediate_size": 512, "shared_expert_intermediate_size": 512,
    "tie_word_embeddings": False, "gating": True, "sliding_window": 512,
    "rope_parameters": {
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
            "original_max_position_embeddings": 4096, "beta_slow": 1,
            "beta_fast": 64, "attention_factor": 1.4158883083359672,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1},
        "original_max_position_embeddings": 4096},
    "layer_types": ["full_attention", "sliding_attention", "sliding_attention",
                    "sliding_attention"] * 10,
    "moe_apply_router_weight_on_input": False, "partial_rotary_factor": 0.5,
    "mlp_layer_types": ["dense"] + ["sparse"] * 39,
    "moe_routed_scaling_factor": 2.5,
    "num_attention_heads_per_layer": [48, 64, 64, 64] * 10,
}


def test_the_file_holds_the_catalogs_keys_and_cuts_the_depth_alone():
    cfg = _file()
    cut = set(cfg["reduced"])
    assert cut == {"num_hidden_layers", "layer_types", "mlp_layer_types",
                   "num_attention_heads_per_layer"}
    for key, value in PUBLISHED.items():
        if key in cut:
            assert cfg[key] == (5 if key == "num_hidden_layers" else value[:5]), key
        else:
            assert cfg[key] == value, key
    assert cfg["source"] == SOURCE and cfg["family"] == cfg["reference"] == "laguna"
    for key in ("gate function", "q and k norm", "router", "shared expert",
                "activation", "attention_factor", "window", "weights"):
        assert cfg["assumed"][key]
    assert "eight" in cfg["stands_for"] and "Every expert is here" in cfg["stands_for"]
    tol = cfg["tolerance"]
    assert tol["metric"] == "rms_share" and tol["control"] == "ref_int8"
    for key in ("sound", "control", "control without the window",
                "control with plain rope", "why this limit"):
        assert tol["readings"][key]
    bench = json.load(open(os.path.join(spec.ROOT, "BENCHMARK.json")))
    entry = [c for c in bench["configs"] if c["name"] == cfg["name"]][0]
    assert set(entry["reduced"]) == cut and entry["source"] == cfg["source"]


def test_sizes_are_the_issues_arithmetic():
    """3869.8 M parameters, 7.74 GB of bf16; the pool by class at 16
    slots: 133 pages a slot of 2 layers, 6 of 3 layers, 4 KB a line and
    layer: 2.23 + 0.15 GB; 10.12 GB in all, over a quarter of the chip."""
    import jax
    import jax.numpy as jnp

    from benchmarks.harness import model
    from flexflow_tpu.serve.paging import window_table_pages

    cfg = _file()
    family = model.family_of(cfg)
    dc = model.decoder_config(cfg)
    weights = family.num_params(dc) * 2
    assert weights / 1e9 == pytest.approx(7.74, abs=0.01)
    serving = model.serving_config(cfg)
    per = window_table_pages(dc.sliding_window, serving.mixed_chunk, serving.page_size)
    assert (serving.pages_per_slot, per) == (133, 6)
    assert serving.num_pages == 16 * (133 + 6) == 284672 // 128
    cache = jax.eval_shape(lambda: family.init_paged_kv_cache(
        dc, 16 * 133, 128, jnp.bfloat16, class_pages={"full": 16 * 133, "window": 16 * 6}))
    assert cache["k"].shape == (2, 16 * 133 + 1, 128, 1024)
    assert cache["k_win"].shape == (3, 16 * 6 + 1, 128, 1024)
    pool = sum(a.size * 2 for a in cache.values())
    assert pool / 1e9 == pytest.approx(2.23 + 0.15, abs=0.01)
    assert (weights + pool) / 1e9 == pytest.approx(10.12, abs=0.01)
    assert (weights + pool) / (16 * 2 ** 30) > 0.25
    # kept whole, the window layers would need 3 x 16 x 133 pages
    assert 3 * 16 * 133 * 128 * 4096 / 1e9 == pytest.approx(3.35, abs=0.01)


def test_the_traffics_worst_case_fits_both_tables():
    cell = spec.Cell(CELL)
    t = cell.traffic
    assert (t["kind"], t["clients"], t["order"], t["warmup_s"]) == ("closed", 16, 58, 15)
    assert t["clients"] == cell.config["serving"]["max_requests_per_batch"]
    longest = lengths.quantile(t["prompt_tokens"], 1 - 1e-9)
    answer = lengths.quantile(t["answer_tokens"], 1 - 1e-9)
    assert (lengths.quantile(t["prompt_tokens"], 1e-9), longest, answer) == (8192, 16384, 512)
    worst = longest + answer + 5
    assert worst <= cell.config["serving"]["max_sequence_length"] == 16928
    assert -(-worst // 128) == 133
    assert cell.chips == 1
    assert {m["name"] for m in cell.end_to_end} == {
        "tpot_p90_ms", "out_tokens_per_s", "setup_s"}
    names = {m["name"] for m in cell.per_layer}
    assert {"cache.window_live_pct", "kernel.window_kind_roofline.mixed",
            "kernel.full_kind_roofline.mixed", "step.swa_gated_mixed_ms",
            "step.swa_gated_mixed_roofline", "moe.all_held_ffn_roofline.mixed",
            "attn.decode_ctx_lines", "moe.experts_hit_pct", "moe.tiles_per_expert",
            "moe.load_max_over_mean", "step.sub_ms.moe_route"} <= names
    # SmallThinker's readers count one head count for both kinds: not this cell's
    assert not {"kernel.window_roofline.mixed", "step.swa_mixed_roofline"} & names


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


READERS = ("attn.decode_ctx_lines", "kernel.window_kind_roofline.mixed",
           "kernel.full_kind_roofline.mixed", "step.swa_gated_mixed_ms",
           "step.swa_gated_mixed_roofline", "moe.all_held_ffn_roofline.mixed")


def test_decode_context_reads_the_counters():
    a = types.SimpleNamespace(decode_context_lines=1000, decode_tokens=10)
    b = types.SimpleNamespace(decode_context_lines=126000, decode_tokens=20)
    assert read("attn.decode_ctx_lines", _ctx(stats=(a, b))) == pytest.approx(12500.0)
    assert read("attn.decode_ctx_lines", _ctx(stats=(a, a))) is None   # no row decoded
    old = (types.SimpleNamespace(decode_tokens=1), types.SimpleNamespace(decode_tokens=2))
    assert read("attn.decode_ctx_lines", _ctx(stats=old)) is None      # before PR 58


def _planes(window_layers=True):
    K = ', custom_call_target="tpu_custom_call"'
    full = "%ff_ragged_paged_c128.{} = bf16[16,128,8,8,128]{{4,3,2,1,0}} custom-call(%q)" + K
    win = "%ff_ragged_paged_c128_win.{} = bf16[16,128,8,8,128]{{4,3,2,1,0}} custom-call(%q)" + K
    glu = "%ff_moe_grouped_glu_t16.{} = bf16[8192,512]{{1,0}} custom-call(%x)" + K
    down = "%ff_moe_grouped_down_t16.{} = f32[8192,2048]{{1,0}} custom-call(%x)" + K
    ops, modules = [], []
    for i, (start, dur) in enumerate([(1000, 9000), (11000, 11000)]):
        modules.append((f"jit_ff_step_c128_t{512 << i}(3)", start, dur, {"run_id": i}))
        ops.append((full.format(2 * i), start + 10, 400 + 100 * i, {}))
        ops.append((full.format(2 * i + 1), start + 8000, 400 + 100 * i, {}))
        for j in range(4):   # the four sparse layers' two calls
            ops.append((glu.format(4 * i + j), start + 500 + 1500 * j, 300, {}))
            ops.append((down.format(4 * i + j), start + 900 + 1500 * j, 100 + 40 * i, {}))
        if window_layers:
            ops += [(win.format(3 * i + j), start + 1000 + 1000 * j, 200 + 20 * i, {})
                    for j in range(3)]
    modules.append(("jit_ff_step_c1(5)", 30000, 900, {"run_id": 9}))
    ops.append(("%ff_ragged_paged_c1.1 = bf16[16,1,8,8,128]{4,3,2,1,0} custom-call(%q)" + K,
                30010, 50, {}))
    return {"/device:TPU:0": {"XLA Ops": ops, "XLA Modules": modules},
            "/host:CPU": {"python3": [("bench.traced", 0, 40000, {})]}}


def test_the_shares_over_a_traced_runs_notes():
    """With the loop's notes of its turns (13 rows decoding at 12 500
    lines, 3 prefilling prompts of 12 288, 400 tokens a step) the
    shares are the counts over the times the events give."""
    cfg = _file()
    t = reduce.Trace(_planes())
    a = types.SimpleNamespace(mixed_steps=0, prefill_tokens=0)
    b = types.SimpleNamespace(mixed_steps=2, prefill_tokens=800)
    tracer = types.SimpleNamespace(rows=[(13, 13 * 12500, 3, 3 * 12288)] * 2,
                                   stats_start=a, stats_stop=b)
    ctx = _ctx(trace=t, cfg=cfg, tracer=tracer)
    mix = dict(decode_rows=13.0, decode_ctx=13 * 12500.0, prefill_rows=3.0,
               prefill_tokens=400.0, prefill_row_ctx=3 * 6144.0,
               prefill_tok_ctx=400 * 6144.5)
    assert read("step.swa_gated_mixed_ms", ctx) == pytest.approx(10000e-6)
    for name, counter, seconds in (
            ("kernel.window_kind_roofline.mixed", "window_kind_kernel", 210e-9),
            ("kernel.full_kind_roofline.mixed", "full_kind_kernel", 450e-9),
            # a layer: (4 x 300 + 4 x 100, 4 x 300 + 4 x 140) / 4, the mean
            ("moe.all_held_ffn_roofline.mixed", "all_held_ffn", 420e-9),
            ("step.swa_gated_mixed_roofline", "laguna_step", 10000e-9)):
        flops, nbytes = _count(counter, cfg, mix)
        least = max(flops / 197e12, nbytes / 819e9)
        assert read(name, ctx) == pytest.approx(100 * least / seconds), name


def test_a_program_without_window_layers_reads_nothing():
    """The parent's program, or a family with one kind of attention
    layer: the accepted kernel name alone is no full layer of this
    kind."""
    t = reduce.Trace(_planes(window_layers=False))
    for name in READERS[1:5]:
        assert read(name, _ctx(trace=t)) is None


@pytest.mark.parametrize("name", READERS)
def test_nothing_to_read_is_nothing(name):
    assert read(name, _ctx()) is None
