"""Chip-compiler tests, the kernels alone: each Pallas kernel of the serving
paths at a cell's shapes, and the routed layer around its two calls,
compiled for a TPU v5e that is DESCRIBED, not attached (the TPU compiler
ships with the installation; nothing here executes). The topology is
conftest.py's module-scoped ``topo`` fixture; shapes and helpers are
tests/chip_compile.py's. Published widths; only depth is cut. A compile that
passes is not a chip run: ``chip_smoke.py`` is the run.
"""
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.models import mistral
from flexflow_tpu.serve import kernels

from chip_compile import *  # noqa: F401,F403 (shapes, helpers)


@pytest.mark.parametrize("C", [1, 128])
def test_ragged_paged_attention_bf16_compiles(chip, C):
    cfg = mistral.mistral_7b(dtype=jnp.bfloat16)
    _, text = _compile(
        kernels.ragged_paged_attention, *_attention_args(chip, C, cfg)
    )
    assert "tpu_custom_call" in text
    # the kernel's name= is its HLO instruction's name: what an xplane's
    # XLA Ops event shows of it (PERF.md section 3)
    assert f"%ff_ragged_paged_c{C}" in text


@pytest.mark.parametrize("C", [1, 128])
def test_ragged_paged_attention_with_query_lengths_compiles(
    chip, C, monkeypatch
):
    """The kernel as a step calls it, told each row's real queries
    (``q_len``: the padding-blind guard, at C=128 the narrow body beside
    the chunk-wide one) and the table entries they may see (``work``,
    made from the positions as the step makes it), at Mistral-7B
    widths, under the ``vmem_limit_bytes`` the kernel states without
    them: the limit may not rise. ONE ``tpu_custom_call`` either way,
    whose grid is one axis as long as the work list
    (``kernels.ragged_work``: a value of the step, not of its
    shapes)."""
    cfg = mistral.mistral_7b(dtype=jnp.bfloat16)
    limits, grids = [], []
    stated, spec = kernels._ragged_vmem_limit, kernels.pltpu.PrefetchScalarGridSpec
    monkeypatch.setattr(
        kernels, "_ragged_vmem_limit",
        lambda *a: limits.append(stated(*a)) or limits[-1],
    )
    monkeypatch.setattr(
        kernels.pltpu, "PrefetchScalarGridSpec",
        lambda **kw: grids.append(kw["grid"]) or spec(**kw))
    kernels._ragged_call.cache_clear()  # the grid is read as the call traces
    args = _attention_args(chip, C, cfg) + (chip((R, C), jnp.int32),)

    def fn(q, kp, vp, pt, mask, positions, use):
        told = {}
        if use:
            q_len = kernels.real_query_lengths(positions, CACHE_LEN)
            told = dict(q_len=q_len, work=kernels.step_work(
                positions, q_len, PAGE, PAGES_PER_SLOT))
        return kernels.ragged_paged_attention(q, kp, vp, pt, mask, **told)

    for use in (False, True):
        _, text = _compile(functools.partial(fn, use=use), *args)
        assert text.count("tpu_custom_call") == 1
        assert f"%ff_ragged_paged_c{C}" in text
        (steps,) = grids[-1]
        assert not isinstance(steps, int) and steps.shape == ()
    assert limits[1] <= limits[0]


def test_sparse_paged_attention_with_query_lengths_compiles(chip):
    """``ff_sparse_paged_c128`` with ``q_len`` at MiniCPM-SALA's widths
    (2 KV heads of 16 query heads, a mask a group, the cell's 146 pages
    a slot, the layer's row offset)."""
    slots, pages, KV, G, dk = 4, 146, 2, 16, 128
    pool = chip((3 * (slots * pages + 1), PAGE, KV, dk), jnp.bfloat16)

    def fn(q, kp, vp, pt, mask, q_len):
        return kernels.sparse_paged_attention(
            q, kp, vp, pt, mask, row_offset=slots * pages + 1, q_len=q_len)

    _, text = _compile(
        fn, chip((slots, 128, KV * G, dk), jnp.bfloat16), pool, pool,
        chip((slots, pages), jnp.int32),
        chip((slots, KV, 128, pages * PAGE), jnp.bool_),
        chip((slots,), jnp.int32),
    )
    assert text.count("tpu_custom_call") == 1
    assert "%ff_sparse_paged_c128" in text


@pytest.mark.parametrize("C", [1, 128])
@pytest.mark.parametrize("kv_quant, dk_pool", [("int8", 128), ("int4", 64)])
def test_ragged_paged_attention_quantized_pool_compiles(
    chip, C, kv_quant, dk_pool
):
    """Per-page scale blocks: (1, KV) of a (P+1, KV) array was refused at
    lowering; the (P+1, 1, KV) view's block is legal."""
    from flexflow_tpu.serve.kv_quant import resolve_spec

    cfg = mistral.mistral_7b(dtype=jnp.bfloat16)
    args = _attention_args(chip, C, cfg, resolve_spec(kv_quant).dtype, dk_pool)
    scale = chip((NUM_PAGES + 1, cfg.num_key_value_heads), jnp.float32)

    def fn(q, kp, vp, pt, mask, ks, vs):
        return kernels.ragged_paged_attention(
            q, kp, vp, pt, mask, k_scale=ks, v_scale=vs
        )

    _, text = _compile(fn, *args, scale, scale)
    assert "tpu_custom_call" in text


def test_flash_attention_forward_and_backward_compile(chip):
    """ops/flash_attention.py (training): the row statistics travel as
    (N, S, 1) columns — (1, bq) blocks of an (N, S) array were refused."""
    from flexflow_tpu.ops import flash_attention as fa

    q = chip((1, 2048, 32, 128), jnp.bfloat16)

    def loss(q, k, v):
        return fa.flash_attention(q, k, v).astype(jnp.float32).sum()

    _, text = _compile(jax.grad(loss, argnums=(0, 1, 2)), q, q, q)
    assert text.count("tpu_custom_call") == 3  # forward, dK/dV, dQ


@pytest.mark.parametrize("layout, padded", [
    ((64, 15, 96, 384), False), ((64, 30, 96, 192), True)],
    ids=["lane-packed", "heads-apart"])
def test_olmo_recurrent_state_takes_its_arithmetic_on_the_device(chip, layout, padded):
    """The finding of ISSUE 48, pinned: the device tiles a float32
    array's two minor axes at (8, 128), so one layer's states with the
    heads apart (rows of 192) take 188.7 MB as an argument where their
    values are 141.6, and two heads to a row of 384 lanes take their
    arithmetic. ``init_paged_kv_cache`` lays the state out the second
    way; a layout that pads its lanes fails here, before any chip
    call."""
    from flexflow_tpu.models import olmo_hybrid as fam

    cfg = fam.config(num_hidden_layers=4, dtype=jnp.bfloat16)
    state = jax.eval_shape(functools.partial(
        fam.init_paged_kv_cache, cfg, 64, PAGE, jnp.bfloat16, num_slots=64))["state"]
    assert state.shape == (3, 64, 15, 96, 384)
    assert fam.lane_pack(30, 192) == 2
    compiled, _ = _compile(lambda s: s + 1.0, chip((1,) + layout, jnp.float32),
                           donate=(0,))
    held = compiled.memory_analysis().argument_size_in_bytes
    arithmetic = int(np.prod(layout)) * 4
    assert arithmetic == 141_557_760
    assert held == (arithmetic * 4 // 3 if padded else arithmetic), held


def test_gdn_recurrence_kernel_compiles_at_the_cells_shapes(chip):
    """Mosaic takes ``ff_gdn_recur_c1`` at the Olmo cell's shapes: 64
    rows, a row's 15 pairs of heads a block (96 x 384 float32 a pair,
    2.2 MB a row in and out), the nine-layer stack aliased through the
    call, nothing copied beside it."""
    from flexflow_tpu.models import olmo_hybrid as fam

    R, H, dk, dv = 64, 30, 96, 192
    f32 = lambda *shape: chip(shape, jnp.float32)
    stack = f32(9, R, 15, dk, 384)
    compiled, text = _compile(
        fam.recurrence_c1, f32(R, H, dk), f32(R, H, dk), f32(R, H, dv),
        f32(R, H), f32(R, H), stack, chip((), jnp.int32),
        chip((R,), jnp.int32), chip((R,), jnp.bool_), donate=(5,))
    call, = re.findall(r"= (\S+ \S+) custom-call\(.*tpu_custom_call", text)
    assert call.startswith("(f32[64,1,15,384]") and "f32[9,64,15,96,384]" in call
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 9 * 141_557_760
    assert mem.temp_size_in_bytes < 16 << 20, mem.temp_size_in_bytes


def test_ragged_kernel_compiles_at_head_size_256(chip):
    """Mosaic takes ``ff_ragged_paged_c128`` at Qwen3-Next's full layer:
    64 rows of 128 queries, 16 query heads on 2 K/V heads of 256, a
    pool line of 512 merged on the minor axis, the cell's 8 pages a
    row, told each row's real queries; both K/V heads one grid step."""
    slots, pages, C = 64, 8, 128
    pool = chip((3 * (slots * pages + 1), PAGE, 2 * 256), jnp.bfloat16)
    compiled, text = _compile(
        lambda q, k, v, table, mask, at, n: kernels.ragged_paged_attention(
            q, k, v, table, mask, row_offset=at, q_len=n),
        chip((slots, C, 16, 256), jnp.bfloat16), pool, pool,
        chip((slots, pages), jnp.int32), chip((slots, C, pages * PAGE), jnp.bool_),
        chip((), jnp.int32), chip((slots,), jnp.int32))
    call, = re.findall(r"%(\w+?)(?:\.\d+)* = (\S+) custom-call\(.*tpu_custom_call", text)
    assert call[0] == "ff_ragged_paged_c128" and call[1].startswith("bf16[64,128,2,8,256]")


@pytest.mark.parametrize("tm", [16, 128])
def test_grouped_expert_matmuls_compile_at_mixtral_widths(chip, tm,
                                                          monkeypatch):
    """``grouped_glu`` / ``grouped_down`` alone at one row tile an
    expert of Mixtral's widths, (8 tm, 4096) x (8, 4096, 14336), under
    the VMEM limit the calls state: the up-projections in 14 column
    blocks of 1024 (two (4096, 1024) weight blocks, double-buffered,
    are 32 MB), the down-projection in 8 of 512 (a (14336, 512) block
    is 14.7 MB). LFM2's matrices fit the 32 MB whole (PR 51). The two
    weight blocks' slots are the kernel's own scratch since PR 52
    (``_grouped_call``), the same bytes."""
    assert kernels.grouped_block(14336, 4096, 2, 2) == 1024
    assert kernels.grouped_block(4096, 14336, 1, 2) == 512
    assert kernels.grouped_block(1536, 2048, 2, 2) == 1536
    assert kernels.grouped_block(2048, 1536, 1, 2) == 2048
    limits = []
    params = kernels.pltpu.CompilerParams
    monkeypatch.setattr(
        kernels.pltpu, "CompilerParams",
        lambda **kw: limits.append(kw["vmem_limit_bytes"]) or params(**kw))
    kernels._grouped_call.cache_clear()   # a trace kept reads no patch
    E, D, F = 8, 4096, 14336
    up, down = chip((E, D, F), jnp.bfloat16), chip((E, F, D), jnp.bfloat16)
    tiles = chip((E,), jnp.int32)

    def fn(rows, w_gate, w_up, w_down, tile_group, n_active):
        act = kernels.grouped_glu(rows, w_gate, w_up, tile_group, n_active,
                                  tm=tm)
        return kernels.grouped_down(act, w_down, tile_group, n_active, tm=tm)

    _, text = _compile(fn, chip((E * tm, D), jnp.bfloat16), up, up, down,
                       tiles, chip((), jnp.int32))
    assert text.count("tpu_custom_call") == 2
    assert f"%ff_moe_grouped_glu_t{tm}" in text
    assert f"%ff_moe_grouped_down_t{tm}" in text
    assert limits == [48 << 20, 48 << 20]
    # the weights reach the calls as they are held: the stacks stay in
    # HBM whole and the kernels copy the blocks they read themselves (a
    # copy, a relayout or a slice of a stack on the way would be the
    # experts' bytes once more a call)
    assert not re.findall(
        r"= \w+\[(?:\d+,)?(?:4096,14336|14336,4096)\]\S* "
        r"(?:copy|slice|dynamic-slice|bitcast-convert|transpose)\(", text)
    # and are the program's own parameters, by name, at the calls
    calls = re.findall(r"custom-call\(([^)]*)\), custom_call_target="
                       r"\"tpu_custom_call\"", text)
    assert [re.findall(r"%(w_[a-z]+)(?:\.\d+)?(?=,|$)", call)
            for call in calls] == [["w_gate", "w_up"], ["w_down"]], calls


def _entry(text):
    """[(result shape less its layout, opcode)] of the instructions of a
    compiled module's ENTRY computation: what the device runs one by
    one (a fusion is one; what is fused into it is not listed)."""
    from flexflow_tpu.obs.sublayers import parse_instructions

    entry = re.search(r"^ENTRY %?([\w.\-]+) ", text, re.M).group(1)
    return [(i.shape, i.opcode) for i in parse_instructions(text).values()
            if i.computation == entry]


@pytest.mark.parametrize("case, T, k, n, routed, D, F", [
    # SmallThinker's padded step: 6144 pairs over 64 experts, 32-row tiles
    ("smallthinker", 1024, 6, 64, 64, 2560, 768),
    # Qwen3-Next's widest rung: 20480 pairs, 128 held of 512
    ("qwen3_next_2048", 2048, 10, 128, 512, 2048, 512),
    # Mixtral's 1024 rung: 2048 pairs over 8 experts, k = 2
    ("mixtral_1024", 1024, 2, 8, 8, 4096, 14336),
])
def test_routed_layer_moves_each_row_once_in_and_once_out(chip, case, T, k, n,
                                                          routed, D, F):
    """``routed_experts_ffn(kernels="pallas")`` alone, compiled: the
    layout is COUNTED (no ``sort``, no ``while``: ``searchsorted``'s
    loop; at most one ``scatter``, of the pairs' tokens), the aligned
    rows are produced ONCE outside the kernels (the gather of ``h``; a
    pass that zeroed the rows no pair has was the whole array read and
    written again), the experts' results gathered once (the pairs'
    rows, (P, D) float32: what only a kernel's edge can take), and the
    weight fetches' scalars reckoned once for the two calls
    (``grouped_fetches``' running sum and minimum are the program's
    only ``reduce-window``s beside the layout's own). The results are
    gathered CHOICE-MAJOR and summed over the leading axis (PR 59): the
    program makes no (T, k, D) array (where k is no multiple of the 8
    sublanes that view is a copy, padded, and the sum reads the
    padding), and the way out's temporaries, compiled alone, stay under
    five quarters of the pairs' results (the token-major form: the
    gather and the padded copy, 146.9 MB against 62.9 at SmallThinker's
    step). The guard that keeps the routed layer's bytes
    from coming back (PR 57)."""
    from flexflow_tpu.models import transformer

    P, tm = T * k, transformer.routed_tile(T, k, (0, n), routed)
    rows = _pair_rows(P, n, routed)
    stack = lambda *shape: chip((2, n) + shape, jnp.bfloat16)

    def fn(h, real, experts, weights, w_gate, w_up, w_down):
        return transformer.routed_experts_ffn(
            h, real, experts, weights, w_gate, w_up, w_down,
            experts_held=(0, n), routed=routed, layer=jnp.int32(1),
            kernels="pallas")

    _, text = _compile(
        fn, chip((T, D), jnp.bfloat16), chip((T,), jnp.bool_),
        chip((T, k), jnp.int32), chip((T, k), jnp.float32),
        stack(D, F), stack(D, F), stack(F, D))
    assert f"%ff_moe_grouped_glu_t{tm}" in text
    assert f"%ff_moe_grouped_down_t{tm}" in text
    assert not re.findall(r" (?:sort|while)\(", text)
    assert len(re.findall(r" scatter\(", text)) <= 1
    entry = _entry(text)
    made = lambda shape: [op for s, op in entry if s == shape
                          and op not in ("bitcast", "parameter")]
    assert made(f"bf16[{rows},{D}]") == ["fusion"]      # h's rows, gathered
    assert made(f"f32[{rows},{D}]") == ["custom-call"]  # grouped_down's
    assert made(f"f32[{P},{D}]") == ["fusion"]          # gathered back, once
    assert made(f"f32[{T},{k},{D}]") == []              # no token-major copy
    # the way out alone (inside the layer the kernels' own arrays set
    # the peak wherever F or the aligned rows are large)
    way_out, _ = _compile(
        transformer.pairs_to_tokens, chip((rows, D), jnp.float32),
        chip((T, k), jnp.int32), chip((T, k), jnp.bool_),
        chip((T, k), jnp.float32))
    assert way_out.memory_analysis().temp_size_in_bytes < 1.25 * P * D * 4
    # the running sums: the layout's two and the fetches' own, once
    windows = lambda fn, *args: sum(
        op == "reduce-window" for _, op in _entry(_compile(fn, *args)[1]))
    tiles = chip((rows // tm,), jnp.int32)
    assert sum(op == "reduce-window" for _, op in entry) == (
        windows(lambda g: transformer.pair_layout(g, n, tm, k),
                chip((P,), jnp.int32))
        + windows(kernels.grouped_fetches, tiles, chip((), jnp.int32)))


@pytest.mark.parametrize("C", [1, 128])
def test_mla_paged_kernel_compiles(chip, C, monkeypatch):
    """serve/kernels.mla_paged_attention at the published widths (128
    heads on one line of 512 + 64 a token) and the benchmark cell's
    shapes (4 slots of 82 logical pages of 128, five layers' pool as
    one view with a row offset): Mosaic takes the paired rope keys'
    lane halves, the 2048-row tile's accumulators and the page index
    maps that stop at a tile's last real query. ONE call, whose grid is
    one axis as long as the step's work list (``kernels.mla_work``: a
    value of the step, not of its shapes) and whose stated VMEM is
    under the scope's ceiling."""
    slots, pages, layers = 4, 82, 5
    rows = layers * (slots * 81 + 1)
    tc, kb = kernels.mla_block(C, pages, 128, PAGE)
    assert (tc, kb) == {1: (1, 8), 128: (32, 2)}[C]
    stated = {}
    params, spec = kernels.pltpu.CompilerParams, kernels.pltpu.PrefetchScalarGridSpec
    monkeypatch.setattr(
        kernels.pltpu, "CompilerParams",
        lambda **kw: stated.update(vmem=kw["vmem_limit_bytes"]) or params(**kw))
    monkeypatch.setattr(
        kernels.pltpu, "PrefetchScalarGridSpec",
        lambda **kw: stated.update(grid=kw["grid"]) or spec(**kw))
    fn = functools.partial(kernels.mla_paged_attention, scale=0.1,
                           row_offset=jnp.int32(325))
    _, text = _compile(
        fn, chip((slots, C, 128, 512), jnp.bfloat16),
        chip((slots, C, 128, 64), jnp.bfloat16),
        chip((rows, PAGE, 512), jnp.bfloat16),
        chip((rows, PAGE // 2, 128), jnp.bfloat16),
        chip((slots, pages), jnp.int32),
        chip((slots,), jnp.int32), chip((slots,), jnp.int32))
    assert f"%ff_mla_paged_c{C}" in text
    assert text.count("tpu_custom_call") == 1
    (steps,) = stated["grid"]
    assert not isinstance(steps, int) and steps.shape == ()
    assert stated["vmem"] <= kernels._VMEM_SCOPE_CEILING
