"""The longcat-flash-chat configuration's part of the benchmark (PR 60):
the configuration file against the catalog's keys and the issue's
arithmetic; the count modules on a hand-worked step and at the
published widths; each new reader on hand-made trace events and
counters, and nothing read, nothing raised, where there is nothing to
read; the traffic file's worst case against the slot's pages."""
import json
import os
import types

import pytest

from benchmarks.harness import lengths, reduce, spec
from benchmarks.harness.loop import Window

CELL = "longcat-flash-chat.agent12k-closed"
SMALL = dict(
    hidden_size=8, vocab_size=100, num_attention_heads=2, num_layers=3,
    q_lora_rank=6, kv_lora_rank=4, qk_nope_head_dim=4, qk_rope_head_dim=2,
    v_head_dim=4, ffn_hidden_size=16, expert_ffn_hidden_size=4,
    n_routed_experts=2, router_outputs=12, zero_expert_num=4,
    experts_held=[2, 4], moe_topk=3)
# three decoding rows at 50 lines each, one prefilling row of a 60-token
# prompt, half-way (context 30), feeding 10 tokens
MIX = dict(decode_rows=3, decode_ctx=150, prefill_rows=1, prefill_tokens=10,
           prefill_row_ctx=30, prefill_tok_ctx=10 * 30.5)
SOURCE = "https://huggingface.co/meituan-longcat/LongCat-Flash-Chat/blob/main/config.json"
# the catalog row's ``config``, every key and its value
PUBLISHED = {
    "attention_bias": False, "vocab_size": 131072, "hidden_size": 6144,
    "ffn_hidden_size": 12288, "expert_ffn_hidden_size": 2048, "num_layers": 28,
    "num_attention_heads": 64, "kv_lora_rank": 512, "q_lora_rank": 1536,
    "qk_rope_head_dim": 64, "v_head_dim": 128, "qk_nope_head_dim": 128,
    "mla_scale_q_lora": True, "mla_scale_kv_lora": True,
    "routed_scaling_factor": 6, "n_routed_experts": 512,
    "max_position_embeddings": 131072, "rms_norm_eps": 1e-05,
    "rope_theta": 10000000, "attention_method": "MLA", "zero_expert_num": 256,
    "zero_expert_type": "identity", "moe_topk": 12}


def _file():
    with open(os.path.join(spec.BENCH_DIR, "configs", "longcat-flash-chat.json")) as f:
        return json.load(f)


def _count(name, cfg, mix):
    return spec.load_module("counts", name).count(cfg, mix)


def test_counts_on_a_hand_worked_step():
    D, V, H, ql, rank, dr, nope, dv = 8, 100, 2, 6, 4, 2, 4, 4
    layers, E, held, K, F, I = 3, 12, 2, 3, 4, 16
    tokens, rows = 13, 4
    pairs = 150 + 10 * 30.5
    line = rank + dr
    assert _count("longcat_mla_kernel", SMALL, MIX) == (
        pytest.approx(2 * H * (line + rank) * pairs),
        pytest.approx(2 * (line * (150 + 30) + tokens * H * (line + rank))))
    mla = D * ql + ql * H * (nope + dr) + D * line + rank * H * (nope + dv) + H * dv * D
    absorb = H * rank * (nope + dv)
    dense, expert, router = 3 * D * I, 3 * D * F, D * E
    per_token = layers * (2 * (mla + dense) + router)
    hit = held * (1 - (1 - K / E) ** tokens)
    flops = (2 * tokens * (per_token + 2 * layers * absorb)
             + 2 * layers * (tokens * K * held / E) * expert
             + 2 * H * (line + rank) * pairs * 2 * layers + 2 * rows * D * V)
    nbytes = 2 * (per_token + layers * hit * expert + D * V
                  + 2 * layers * line * (150 + 30 + tokens) + tokens * D)
    assert _count("longcat_step", SMALL, MIX) == (
        pytest.approx(flops), pytest.approx(nbytes))


def test_counts_at_the_published_widths_are_the_issues():
    """The issue's mixed step: 13 decode rows at 12.5 k lines beside
    three prefilling rows half-way through prompts of 12 288, 400
    prompt tokens. A prompt token at a mean prefix of 6 k costs 6.85 G
    operations of attention against 5.11 G in every matmul of the four
    layers (experts and absorbed products apart); the 13 decoding rows
    read 1.5 GB of lines a step; every held expert is hit, so the step
    reads its 10.35 GB of weights whole."""
    cfg = _file()
    s = spec.load_module("counts", "longcat_sizes")
    z = s.sizes(cfg)
    assert (z["H"], z["E"], z["zero"], z["held"], z["K"], z["layers"]) == (
        64, 768, 256, 16, 12, 4)
    assert (z["mla"], z["dense_ffn"], z["expert"], z["router"]) == (
        90_570_752, 226_492_416, 37_748_736, 4_718_592)
    assert (z["line"], z["rank"]) == (576, 512)
    assert 2 * 64 * (576 + 512) * 6144 * 8 / 1e9 == pytest.approx(6.85, abs=0.01)
    assert 2 * 4 * (2 * (z["mla"] + z["dense_ffn"]) + z["router"]) / 1e9 == pytest.approx(
        5.11, abs=0.01)
    assert s.pairs_held(z, 413) == pytest.approx(103.25)          # 6 rows an expert
    assert s.experts_hit(z, 413) == pytest.approx(16, abs=0.03)   # every one held
    mix = dict(decode_rows=13, decode_ctx=13 * 12500, prefill_rows=3,
               prefill_tokens=400, prefill_row_ctx=3 * 6144,
               prefill_tok_ctx=400 * 6144.5)
    kernel = _count("longcat_mla_kernel", cfg, mix)
    # a call reads 13 x 12.5 k + 3 x 6144 lines of 1152 B and the queries
    assert kernel[1] / 1e9 == pytest.approx(
        (13 * 12500 + 3 * 6144) * 1152 / 1e9 + 413 * 64 * 1088 * 2 / 1e9)
    assert 8 * 13 * 12500 * 1152 / 1e9 == pytest.approx(1.5, abs=0.01)
    assert kernel[0] / 197e12 > kernel[1] / 819e9                 # compute binds a call
    flops, nbytes = _count("longcat_step", cfg, mix)
    weights = 4 * (2 * (z["mla"] + z["dense_ffn"]) + z["router"] + 16 * z["expert"]) \
        + 6144 * 16384
    assert weights * 2 / 1e9 == pytest.approx(10.14, abs=0.01)    # less the embedding
    assert nbytes / 1e9 == pytest.approx(
        weights * 2 / 1e9 + 8 * kernel[1] / 1e9 - 8 * 413 * 64 * 1088 * 2 / 1e9
        + 8 * 413 * 1152 / 1e9 + 413 * 6144 * 2 / 1e9, rel=2e-3)
    # attention is some half of the step's operations
    assert 8 * kernel[0] / flops == pytest.approx(0.5, abs=0.08)
    assert flops / 197e12 > nbytes / 819e9                        # compute binds the step
    assert flops / 197e12 * 1e3 == pytest.approx(27, abs=3)       # ms at the peak


def test_the_file_holds_the_catalogs_keys_and_cuts_three():
    cfg = _file()
    cut = set(cfg["reduced"])
    assert cut == {"num_layers", "n_routed_experts", "vocab_size"}
    for key, value in PUBLISHED.items():
        if key not in cut:
            assert cfg[key] == value, key
    assert (cfg["num_layers"], cfg["n_routed_experts"], cfg["vocab_size"]) == (4, 16, 16384)
    for key, here in (("num_layers", 4), ("n_routed_experts", 16), ("vocab_size", 16384)):
        assert cfg["reduced"][key]["published"] == PUBLISHED[key]
        assert cfg["reduced"][key]["here"] == here and cfg["reduced"][key]["why"]
    assert (cfg["router_outputs"], cfg["experts_held"]) == (768, [0, 16])
    assert "n_group" not in cfg and "num_hidden_layers" not in cfg
    assert cfg["source"] == SOURCE and cfg["family"] == cfg["reference"] == "longcat_flash"
    for key in ("hidden_act", "norm_topk_prob", "router bias", "e_score_correction_bias", "dtype",
                "weights", "mtp", "rope pairing", "latent line"):
        assert cfg["assumed"][key]
    assert "seven" in cfg["stands_for"] and "32-chip" in cfg["stands_for"]
    tol = cfg["tolerance"]
    assert tol["metric"] == "rms_share" and tol["control"] == "ref_int8"
    for key in ("number", "sound", "control", "why this limit"):
        assert tol["readings"][key]
    bench = json.load(open(os.path.join(spec.ROOT, "BENCHMARK.json")))
    entry = [c for c in bench["configs"] if c["name"] == cfg["name"]][0]
    assert set(entry["reduced"]) == cut and entry["source"] == cfg["source"]


def test_sizes_are_the_issues_arithmetic():
    """5172.6 M parameters, 10.35 GB of bf16; the pool at 16 slots of
    133 pages, two lines of 1152 B a token and layer over four layers:
    2.51 GB; 12.86 GB in all, over a quarter of the chip."""
    import jax
    import jax.numpy as jnp

    from benchmarks.harness import model

    cfg = _file()
    family = model.family_of(cfg)
    dc = model.decoder_config(cfg)
    weights = family.num_params(dc) * 2
    assert weights / 1e9 == pytest.approx(10.35, abs=0.01)
    serving = model.serving_config(cfg)
    assert (serving.pages_per_slot, serving.num_pages) == (133, 2128)
    cache = jax.eval_shape(lambda: family.init_paged_kv_cache(
        dc, serving.num_pages, 128, jnp.bfloat16))
    assert cache["latent"].shape == (8, 2129, 128, 512)
    assert cache["latent_rope"].shape == (8, 2129, 64, 128)
    pool = sum(a.size * 2 for a in cache.values())
    assert pool / 1e9 == pytest.approx(2.51, abs=0.01)
    assert (weights + pool) / 1e9 == pytest.approx(12.86, abs=0.01)
    assert (weights + pool) / (16 * 2 ** 30) > 0.25


def test_the_traffics_worst_case_fits_a_slot():
    cell = spec.Cell(CELL)
    t = cell.traffic
    assert (t["kind"], t["clients"], t["order"], t["warmup_s"]) == ("closed", 16, 58, 15)
    assert t["clients"] == cell.config["serving"]["max_requests_per_batch"]
    longest = lengths.quantile(t["prompt_tokens"], 1 - 1e-9)
    answer = lengths.quantile(t["answer_tokens"], 1 - 1e-9)
    worst = longest + answer + 5
    assert worst <= cell.config["serving"]["max_sequence_length"] == 16928
    assert -(-worst // 128) == 133 and cell.chips == 1
    assert {m["name"] for m in cell.end_to_end} == {
        "tpot_p90_ms", "out_tokens_per_s", "setup_s"}
    names = {m["name"] for m in cell.per_layer}
    assert {"step.scmoe_mixed_ms", "step.scmoe_mixed_roofline",
            "kernel.latent_roofline.mixed", "moe.zero_pairs_pct",
            "attn.decode_ctx_lines", "moe.experts_hit_pct", "moe.tiles_per_expert",
            "moe.load_max_over_mean", "step.sub_ms.moe_route"} <= names
    # DeepSeek's readers count 128 heads and one attention a layer: not this cell's
    assert not {"kernel.mla_roofline.mixed", "step.mla_mixed_roofline"} & names


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


READERS = ("step.scmoe_mixed_ms", "step.scmoe_mixed_roofline",
           "kernel.latent_roofline.mixed", "moe.zero_pairs_pct")


def test_zero_pairs_reads_the_counters():
    a = types.SimpleNamespace(moe_zero_pairs=100, moe_routed_pairs=1000)
    b = types.SimpleNamespace(moe_zero_pairs=1700, moe_routed_pairs=5800)
    assert read("moe.zero_pairs_pct", _ctx(stats=(a, b))) == pytest.approx(100 / 3)
    assert read("moe.zero_pairs_pct", _ctx(stats=(a, a))) is None    # nothing routed
    old = (types.SimpleNamespace(moe_pairs=1), types.SimpleNamespace(moe_pairs=2))
    assert read("moe.zero_pairs_pct", _ctx(stats=old)) is None       # before PR 60


def _planes(latent=True):
    K = ', custom_call_target="tpu_custom_call"'
    mla = "%ff_mla_paged_c128.{} = bf16[16,128,64,512]{{3,2,1,0}} custom-call(%q)" + K
    other = "%ff_ragged_paged_c128.{} = bf16[16,128,8,8,128]{{4,3,2,1,0}} custom-call(%q)" + K
    glu = "%ff_moe_grouped_glu_t128.{} = bf16[8192,2048]{{1,0}} custom-call(%x)" + K
    ops, modules = [], []
    for i, (start, dur) in enumerate([(1000, 40000), (50000, 44000)]):
        modules.append((f"jit_ff_step_c128_t{512 << i}(3)", start, dur, {"run_id": i}))
        for j in range(8):   # four layers' two attention calls
            ops.append(((mla if latent else other).format(8 * i + j),
                        start + 10 + 4000 * j, 2000 + 200 * i, {}))
        ops += [(glu.format(4 * i + j), start + 3000 + 8000 * j, 300, {})
                for j in range(4)]
    modules.append(("jit_ff_step_c1(5)", 100000, 900, {"run_id": 9}))
    ops.append(("%ff_mla_paged_c1.1 = bf16[16,1,64,512]{3,2,1,0} custom-call(%q)" + K,
                100010, 50, {}))
    return {"/device:TPU:0": {"XLA Ops": ops, "XLA Modules": modules},
            "/host:CPU": {"python3": [("bench.traced", 0, 110000, {})]}}


def test_the_shares_over_a_traced_runs_notes():
    """With the loop's notes of its turns (13 rows decoding at 12 500
    lines, 3 prefilling prompts of 12 288, 400 tokens a step) the
    shares are the counts over the times the events give: the step's
    mean over both widths, the kernel's median call."""
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
    assert read("step.scmoe_mixed_ms", ctx) == pytest.approx(42000e-6)
    for name, counter, seconds in (
            ("kernel.latent_roofline.mixed", "longcat_mla_kernel", 2100e-9),
            ("step.scmoe_mixed_roofline", "longcat_step", 42000e-9)):
        flops, nbytes = _count(counter, cfg, mix)
        least = max(flops / 197e12, nbytes / 819e9)
        assert read(name, ctx) == pytest.approx(100 * least / seconds), name


def test_a_program_without_the_latent_kernel_reads_nothing():
    t = reduce.Trace(_planes(latent=False))
    for name in READERS[:3]:
        assert read(name, _ctx(trace=t)) is None


@pytest.mark.parametrize("name", READERS)
def test_nothing_to_read_is_nothing(name):
    assert read(name, _ctx()) is None
