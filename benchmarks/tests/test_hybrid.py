"""The hybrid configuration's part of the benchmark (PR 29): the count
modules against hand sums at one small mix; the readers that find a
step program and a kernel by NAME on hand-made events and on a recorded
slice of ``minicpm-sala.longdoc-closed`` (``trace_sample_hybrid.json``,
written by ``tools/phases.py --sample``, the expected values in the
file); the counter reader; and the control for the configuration's
tolerance at a size a test run can hold."""
import os
import types

import numpy as np
import pytest

from benchmarks.harness import reduce, spec
from benchmarks.harness.loop import Window
from benchmarks.tools import phases

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "minicpm-sala.longdoc-closed"

SMALL = dict(
    hidden_size=8, intermediate_size=16, vocab_size=100,
    num_attention_heads=4, num_key_value_heads=2, head_dim=4,
    lightning_nh=2, lightning_head_dim=4, num_hidden_layers=3,
    mixer_types=["minicpm4", "lightning-attn", "lightning-attn", "minicpm4"],
    sparse_config=dict(kernel_size=8, kernel_stride=4, block_size=16, topk=2,
                       window_size=16, init_blocks=1, dense_len=40))
# one decoding row at 100 keys (above dense_len: attends 2 x 16), one
# prefilling row of a 60-token prompt, half-way, feeding 10 tokens
MIX = dict(decode_rows=1, decode_ctx=100, prefill_rows=1, prefill_tokens=10,
           prefill_row_ctx=30, prefill_tok_ctx=10 * 30.5)


def test_hybrid_counts_against_hand_sums():
    step = spec.load_module("counts", "hybrid_step").count(SMALL, MIX)
    kernel = spec.load_module("counts", "sparse_kernel").count(SMALL, MIX)
    D, F, V, H, KV, d, LH, ld = 8, 16, 100, 4, 2, 4, 2, 4
    ffn, lmix, smix = 3 * D * F, 5 * D * LH * ld, 3 * D * H * d + 2 * D * KV * d
    matmul = 2 * lmix + 1 * smix + 3 * ffn          # the first 3 of mixer_types
    tokens, rows = 11, 2
    # a prompt of 60: positions 1..40 attend all, the last 20 attend 32
    mean_prefill = (40 * 41 / 2 + 20 * 32) / 60
    keys = 1 * 32 + 10 * mean_prefill
    scored = (100 + 10 * (20 / 60) * (60 + 40) / 2) / 4
    flops = (2 * tokens * matmul + 2 * rows * D * V + 4 * tokens * 2 * LH * ld * ld
             + 1 * H * d * (4 * keys + 2 * scored))
    lines = 1 * 32 + 30 + tokens                    # the prefilling row is at 30 keys: dense
    nbytes = (2 * (matmul + D * V + tokens * D) + 2 * 4 * rows * 2 * LH * ld * ld
              + 1 * (2 * 2 * KV * d * lines + 4 * KV * d * 100 / 4))
    assert step == (pytest.approx(flops), pytest.approx(nbytes))
    assert kernel == (pytest.approx(4 * H * d * keys),
                      pytest.approx(2 * (2 * KV * d * (32 + 30) + 2 * tokens * H * d)))


def _ctx(trace=reduce.NoTrace(), stats=None, chunk=128):
    win = Window()
    if stats:
        win.stats_open, win.stats_close = stats
    return reduce.Context(
        window=win, setup_s=0.0, cfg={}, peaks=None, trace=trace,
        engine_serving=types.SimpleNamespace(mixed_chunk=chunk))


def read(name, ctx):
    return spec.load_module("per_layer", name).read(ctx)


def test_sparse_rows_counter():
    stats = (types.SimpleNamespace(real_rows=100, sparse_rows=10),
             types.SimpleNamespace(real_rows=300, sparse_rows=138))
    assert read("attn.sparse_rows_pct", _ctx(stats=stats)) == pytest.approx(64.0)
    assert read("attn.sparse_rows_pct", _ctx(stats=(stats[0], stats[0]))) is None
    # a program before PR 29 keeps no such counters: nothing, no error
    old = (types.SimpleNamespace(steps=1), types.SimpleNamespace(steps=2))
    assert read("attn.sparse_rows_pct", _ctx(stats=old)) is None


def test_step_and_kernel_are_found_by_name():
    K = ', custom_call_target="tpu_custom_call"'
    ops = [("%ff_sparse_paged_c128.7 = bf16[4,128,2,16,128]{4,3,2,1,0} custom-call(%q)" + K, 1100, 300, {}),
           ("%ff_ragged_paged_c128.3 = bf16[4,128,2,16,128]{4,3,2,1,0} custom-call(%q)" + K, 1500, 100, {}),
           ("%ff_sparse_paged_c128.9 = bf16[4,128,2,16,128]{4,3,2,1,0} custom-call(%q)" + K, 2100, 500, {}),
           ("%ff_sparse_paged_c1.2 = bf16[4,1,2,16,128]{4,3,2,1,0} custom-call(%q)" + K, 3100, 50, {})]
    modules = [("jit_ff_step_c128(11)", 1000, 800, {"run_id": 1}),
               ("jit_ff_step_c128(11)", 2000, 900, {"run_id": 2}),
               ("jit_ff_step_c128_logits(12)", 2950, 40, {"run_id": 3}),
               ("jit_ff_step_c1(13)", 3000, 100, {"run_id": 4}),
               ("jit_ff_step_c128(11)", 3900, 800, {"run_id": 5})]   # cut by the window
    host = [("bench.traced", 0, 4000, {})]
    planes = {"/device:TPU:0": {"XLA Ops": ops, "XLA Modules": modules},
              "/host:CPU": {"python3": host}}
    ctx = _ctx(trace=reduce.Trace(planes))
    assert read("step.hybrid_mixed_ms", ctx) == pytest.approx(850e-6)
    # the rooflines need a traced run's notes of its turns: nothing here
    assert read("step.hybrid_mixed_roofline", ctx) is None
    assert read("kernel.sparse_roofline.mixed", ctx) is None
    for name in ("step.hybrid_mixed_ms", "step.hybrid_mixed_roofline",
                 "kernel.sparse_roofline.mixed"):
        assert read(name, _ctx()) is None               # no trace at all


@pytest.fixture(scope="module")
def recorded():
    path = os.path.join(HERE, "trace_sample_hybrid.json")
    if not os.path.exists(path):
        pytest.skip("no recorded slice of the hybrid cell yet")
    planes, rest = phases.load_sample(path)
    return reduce.Trace(planes), rest["expect"]


def test_recorded_hybrid_slice(recorded):
    """A stretch of the new cell's own trace: the step program is found
    by name, both attention kernels are in it by name, and the readers
    give what they gave on the chip."""
    t, want = recorded
    ctx = _ctx(trace=t)
    assert read("step.hybrid_mixed_ms", ctx) == pytest.approx(
        want["step.hybrid_mixed_ms"], rel=1e-9)
    kernels = {name.split(".")[0] for name, _, _, kernel, *_ in t.ops if kernel}
    assert kernels <= {"ff_sparse_paged_c128", "ff_ragged_paged_c128",
                       "ff_sparse_paged_c1", "ff_ragged_paged_c1"}
    assert "ff_sparse_paged_c128" in kernels
    calls = [dur for n, _, _, kernel, s, dur in t.ops
             if kernel and n.split(".")[0] == "ff_sparse_paged_c128"]
    assert len(calls) == want["sparse_kernel_calls"]
    # what PR 26's reduction makes of a program with several kernels: it
    # still keys it by the first kernel's chunk extent
    assert t.program_ms(128) == pytest.approx(want["program_ms_128"], rel=1e-9)


def test_control_fails_and_served_passes_for_the_hybrid():
    """The configuration's comparison at a test's size (float32, CPU,
    interpret-mode kernels): the served path passes ``probe.verdict``,
    the reference computed in int8 does not. ``run.py``'s tiny override
    keeps 160 positions, which this cell's prompts do not fit, so the
    sizes are cut here: prompts of 100 to 150 over a ``dense_len`` of 96."""
    from benchmarks import run as bench_run
    from benchmarks.harness import model, probe

    cell = spec.Cell(CELL)
    assert cell.config["tolerance"]["control"] == "ref_int8"
    bench_run.tiny(cell)
    config = dict(
        cell.config, head_dim=16, lightning_nh=4, lightning_nkv=4,
        lightning_head_dim=16, num_key_value_heads=2, dim_model_base=32,
        sparse_config=dict(kernel_size=8, kernel_stride=4, block_size=16,
                           topk=6, window_size=32, init_blocks=1, dense_len=96))
    import jax.numpy as jnp

    # a float32 pool: from bf16 keys two nearly level block scores of a
    # 154-token context can change places (seen once in 130 rows), and
    # one block of ten is 4e-4 of a row there; at 18k tokens it is one
    # block of 64 among 290
    config["serving"] = dict(config["serving"], max_sequence_length=256,
                             max_cached_tokens=4 * 256, cache_dtype=jnp.float32)
    traffic = dict(cell.traffic, prompt_tokens=dict(dist="uniform", lo=100, hi=150))
    # float32 at two layers: sound rows read under 1e-5, the int8
    # control 6e-4 to 1.2e-3 (my CPU readings, PR 29); the rehearsal's
    # 1.5e-3 was set for the decoder families, whose residual branches
    # are not scaled down by scale_depth / sqrt(32)
    config["tolerance"] = dict(config["tolerance"], limit=2e-4)
    reference = spec.load_module("references", config["reference"])
    quiet = lambda msg: None
    for seed in (1, 2):
        llm, params = model.build_server(config, seed)
        seqs, judged = probe.served_logits(llm.engine, traffic,
                                           np.random.default_rng(seed))
        want = probe.reference_rows(config, params, seqs, judged)
        assert probe.verdict(config, probe.against(config, want, judged), quiet)
        logits = reference.judged_logits(params, config, *want[1], control_bits=8)[0]
        control = [(row, pos, logits[row, j, 0]) for (row, pos, _), j
                   in zip(judged, want[2])]
        assert not probe.verdict(config, probe.against(config, want, control), quiet)
