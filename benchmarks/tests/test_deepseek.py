"""The DeepSeek-V3 configuration's part of the benchmark (PR 40): the
count modules against hand sums at one small mix; the readers that find
the latent kernel and the programs that hold it by NAME, on hand-made
events; what the cell reports; and the configuration file against the
catalog's published keys."""
import json
import os
import types

import pytest

from benchmarks.harness import reduce, spec
from benchmarks.harness.loop import Window

CELL = "deepseek-v3.doc8k-closed"

SMALL = dict(
    hidden_size=8, intermediate_size=24, moe_intermediate_size=4, vocab_size=100,
    num_attention_heads=4, num_hidden_layers=3, first_k_dense_replace=1,
    q_lora_rank=6, kv_lora_rank=10, qk_nope_head_dim=3, qk_rope_head_dim=2,
    v_head_dim=5, n_routed_experts=4, router_outputs=16, experts_held=[4, 8],
    n_shared_experts=1, num_experts_per_tok=2)
# three decoding rows at 50 lines each, one prefilling row of a 20-token
# prompt, half-way, feeding 10 tokens
MIX = dict(decode_rows=3, decode_ctx=150, prefill_rows=1, prefill_tokens=10,
           prefill_row_ctx=10, prefill_tok_ctx=10 * 10.5)


def test_deepseek_counts_against_hand_sums():
    step = spec.load_module("counts", "deepseek_step").count(SMALL, MIX)
    kernel = spec.load_module("counts", "mla_kernel").count(SMALL, MIX)
    D, F, Fm, V, H, E, K, held = 8, 24, 4, 100, 4, 16, 2, 4
    ql, rank, nope, dr, dv = 6, 10, 3, 2, 5
    line = rank + dr
    mla = D * ql + ql * H * (nope + dr) + D * line + rank * H * (nope + dv) + H * dv * D
    absorb = H * rank * (nope + dv)
    expert = 3 * D * Fm
    tokens, rows, pairs = 13, 4, 150 + 105
    per_token = 3 * mla + 1 * 3 * D * F + 2 * (D * E + expert)       # + router, shared
    hit = held * (1 - (1 - K / E) ** tokens)
    flops = (2 * tokens * (per_token + 3 * absorb)
             + 2 * 2 * (tokens * K * held / E) * expert
             + 2 * H * (line + rank) * pairs * 3 + 2 * rows * D * V)
    nbytes = 2 * (per_token + 2 * hit * expert + D * V
                  + 3 * line * (150 + 10 + tokens) + tokens * D)
    assert step == (pytest.approx(flops), pytest.approx(nbytes))
    # one call: every (query, line) pair on the line's width and its c;
    # the rows' lines once, the absorbed queries in and the outputs out
    assert kernel == (pytest.approx(2 * H * (line + rank) * pairs),
                      pytest.approx(2 * (line * 160 + tokens * H * (line + rank))))
    # at the published widths a pair costs 2 x 128 x (576 + 512) = 278.5 k
    # and a line 1152 B: a decode call sits on the v5e's ridge (ISSUE 40)
    cfg = spec.Cell(CELL).config
    f, b = spec.load_module("counts", "mla_kernel").count(
        cfg, dict(MIX, decode_rows=1, decode_ctx=8192, prefill_rows=0,
                  prefill_tokens=0, prefill_row_ctx=0, prefill_tok_ctx=0))
    assert f == 278528 * 8192 and 230 < f / b < 242      # 197e12 / 819e9 = 240.5


def _ctx(trace=reduce.NoTrace(), cfg=None):
    return reduce.Context(
        window=Window(), setup_s=0.0, cfg=cfg or SMALL, peaks=None, trace=trace,
        engine_serving=types.SimpleNamespace(mixed_chunk=128))


def read(name, ctx):
    return spec.load_module("per_layer", name).read(ctx)


def _planes():
    K = ', custom_call_target="tpu_custom_call"'
    mla = "%ff_mla_paged_c128.{} = bf16[4,128,128,512]{{3,2,1,0}} custom-call(%q)" + K
    glu = "%ff_moe_grouped_glu_t128.{} = bf16[6016,2048]{{1,0}} custom-call(%x)" + K
    dec = "%ff_mla_paged_c1.{} = bf16[4,1,128,512]{{3,2,1,0}} custom-call(%q)" + K
    ops, modules = [], []
    # two packed mixed programs and one padded, five layers each; a decode program
    for run, (start, name, call) in enumerate((
            (1000, "jit_ff_step_c128_t256(5)", 40), (3000, "jit_ff_step_c128(6)", 60),
            (5000, "jit_ff_step_c128_t256(5)", 44))):
        modules.append((name, start, 1000 + 100 * run, {"run_id": run}))
        for layer in range(5):
            ops.append((mla.format(layer), start + 10 + 150 * layer, call + layer, {}))
            ops.append((glu.format(layer), start + 90 + 150 * layer, 20, {}))
    modules.append(("jit_ff_step_c1(7)", 7000, 300, {"run_id": 9}))
    ops.append((dec.format(0), 7010, 5, {}))
    return {"/device:TPU:0": {"XLA Ops": ops, "XLA Modules": modules},
            "/host:CPU": {"python3": [("bench.traced", 0, 8000, {})]}}


def test_the_latent_kernel_and_its_programs_are_found_by_name():
    t = reduce.Trace(_planes())
    ctx = _ctx(trace=t)
    kernel = spec.load_module("per_layer", "kernel.mla_roofline.mixed")
    # 15 calls: 40..44, 60..64, 44..48 ns; the median is the 8th smallest
    assert kernel.call_ms(ctx) == pytest.approx(46e-6)
    step = spec.load_module("per_layer", "step.mla_mixed_ms")
    # the mean over the executed widths, by count: not the median
    assert step.step_ms(ctx) == pytest.approx((1000 + 1100 + 1200) / 3 * 1e-6)
    assert read("step.mla_mixed_ms", ctx) == step.step_ms(ctx)
    # the harness keys a program by its FIRST kernel: the latent call
    assert sorted(t.programs) == [1, 128] and len(t.programs[128]) == 3
    assert t.program_ms(1) == pytest.approx(300e-6)
    # the rooflines need a traced run's notes of its turns: nothing here
    assert read("kernel.mla_roofline.mixed", ctx) is None
    assert read("step.mla_mixed_roofline", ctx) is None


def test_a_program_without_the_latent_kernel_reads_nothing():
    """The parent's programs, and every other family's: the readers
    return None and do not raise (the result line leaves them out)."""
    K = ', custom_call_target="tpu_custom_call"'
    ops = [("%ff_ragged_paged_c128.1 = bf16[16,128,8,4,128]{4,3,2,1,0} custom-call(%q)" + K,
            1010, 50, {})]
    modules = [("jit_ff_step_c128(3)", 1000, 900, {"run_id": 1})]
    other = reduce.Trace({"/device:TPU:0": {"XLA Ops": ops, "XLA Modules": modules},
                          "/host:CPU": {"python3": [("bench.traced", 0, 3000, {})]}})
    for ctx in (_ctx(trace=other), _ctx()):
        kernel = spec.load_module("per_layer", "kernel.mla_roofline.mixed")
        assert kernel.call_ms(ctx) is None
        assert spec.load_module("per_layer", "step.mla_mixed_ms").step_ms(ctx) is None
        for name in ("kernel.mla_roofline.mixed", "step.mla_mixed_ms",
                     "step.mla_mixed_roofline"):
            assert read(name, ctx) is None


def test_what_the_cell_reports():
    cell = spec.Cell(CELL)
    assert cell.chips == 1 and cell.config["family"] == "deepseek_v3"
    assert [m["name"] for m in cell.end_to_end] == [
        "ttft_p50_ms", "out_tokens_per_s", "setup_s"]
    names = {m["name"] for m in cell.per_layer}
    assert {"kernel.mla_roofline.mixed", "step.mla_mixed_ms", "step.mla_mixed_roofline",
            "moe.experts_hit_pct", "device.idle_pct.tput", "step.mixed_mean_ms",
            "cache.pages_peak_pct"} <= names
    assert not names & {"step.decode_ms", "kernel.ragged_roofline.mixed",
                        "step.hybrid_mixed_ms", "moe.ffn_roofline.mixed"}
    t = cell.traffic
    assert (t["kind"], t["clients"], t["order"]) == ("closed", 4, 40)
    serving = cell.config["serving"]
    # the longest request: prompt, answer and five lines of slack, in pages
    worst = -(-(t["prompt_tokens"]["hi"] + t["answer_tokens"]["hi"] + 5) // serving["page_size"])
    assert worst == 81 and worst * 4 * serving["page_size"] == serving["max_cached_tokens"]
    # the new metrics read this cell only
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for m in bench["per_layer"]:
        if "mla" in m["name"]:
            assert m["workloads"] == [CELL]


def test_the_configuration_keeps_every_published_width():
    """``reduced`` names every key that differs from the catalog row's
    ``config`` (copied here: the published file), and no width."""
    published = {
        "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 3,
        "hidden_act": "silu", "hidden_size": 7168, "intermediate_size": 18432,
        "kv_lora_rank": 512, "max_position_embeddings": 163840,
        "model_type": "deepseek_v3", "moe_intermediate_size": 2048,
        "moe_layer_freq": 1, "n_group": 8, "n_routed_experts": 256,
        "n_shared_experts": 1, "norm_topk_prob": True, "num_attention_heads": 128,
        "num_experts_per_tok": 8, "num_hidden_layers": 61,
        "num_key_value_heads": 128, "num_nextn_predict_layers": 1,
        "q_lora_rank": 1536, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-06,
        "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1,
                         "mscale_all_dim": 1,
                         "original_max_position_embeddings": 4096, "type": "yarn"},
        "rope_theta": 10000, "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
        "tie_word_embeddings": False, "topk_group": 4, "topk_method": "noaux_tc",
        "v_head_dim": 128, "vocab_size": 129280}
    config = spec.Cell(CELL).config
    differs = {k for k, v in published.items() if config.get(k) != v}
    assert differs == set(config["reduced"]) == {
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts", "vocab_size"}
    for key, entry in config["reduced"].items():
        assert entry["published"] == published[key] and entry["here"] == config[key]
    assert (config["router_outputs"], config["experts_held"]) == (256, [0, 16])
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        entry = [c for c in json.load(f)["configs"] if c["name"] == "deepseek-v3"][0]
    assert set(entry["reduced"]) == differs
    tol = config["tolerance"]
    assert tol["control"] == "ref_int8" and tol["metric"] == "rms_share"


def test_recorded_slice_of_the_cell():
    """A stretch of the cell's own trace (``trace_sample_deepseek.json``,
    written by ``tools/phases.py --sample``, the expected values in the
    file): the mixed programs are found by their first kernel, the
    latent call, ahead of the grouped expert matmuls; the readers give
    what they gave on the chip; no K/V pool and no all-expert product
    among the operations."""
    from benchmarks.tools import phases

    planes, rest = phases.load_sample(
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "trace_sample_deepseek.json"))
    t, want = reduce.Trace(planes), rest["expect"]
    ctx = _ctx(trace=t, cfg=spec.Cell(CELL).config)
    assert sorted(t.programs) == [128] and len(t.programs[128]) == want["programs"]
    kernel = spec.load_module("per_layer", "kernel.mla_roofline.mixed")
    assert kernel.call_ms(ctx) == pytest.approx(want["kernel_call_ms"], rel=1e-9)
    assert read("step.mla_mixed_ms", ctx) == pytest.approx(want["step.mla_mixed_ms"], rel=1e-9)
    kernels = {name.split(".")[0] for name, _, _, k, *_ in t.ops if k}
    assert kernels == {"ff_mla_paged_c128", "ff_moe_grouped_glu_t128",
                       "ff_moe_grouped_down_t128"}
    # five latent calls a program, and each program's FIRST kernel
    for s, e, *_ in t.programs[128]:
        inside = sorted((st, n.split(".")[0]) for n, _, _, k, st, _ in t.ops
                        if k and s <= st < e)
        assert inside[0][1] == "ff_mla_paged_c128"
        assert sum(n == "ff_mla_paged_c128" for _, n in inside) == 5
    shapes = [shape for _, shape, *_ in t.ops]
    assert not [x for x in shapes if ",256,2048]" in x]        # no all-expert product
    assert not [x for x in shapes if ",8,128]" in x]           # no K/V heads of 128
