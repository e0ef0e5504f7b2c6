"""Chip-compiler tests, the latent pools and the two classes of page:
DeepSeek-V3 and LongCat-Flash, SmallThinker and Laguna at their cells' depth,
compiled for a TPU v5e that is DESCRIBED, not attached (the TPU compiler
ships with the installation; nothing here executes). The topology is
conftest.py's module-scoped ``topo`` fixture; shapes and helpers are
tests/chip_compile.py's. Published widths; only depth is cut. A compile that
passes is not a chip run: ``chip_smoke.py`` is the run.
"""
import functools
import math
import re

import jax
import jax.numpy as jnp
import pytest

from flexflow_tpu.serve import kernels

from chip_compile import *  # noqa: F401,F403 (shapes, helpers)


@pytest.mark.parametrize("C, pack", [(1, None), (128, 128), (128, None)])
def test_deepseek_v3_step_compiles_in_place(chip, C, pack):
    """models/deepseek_v3.py at published widths, the benchmark
    configuration's cut (a dense layer and two of its sparse layers, 16
    of 256 experts held, an eighth of the vocabulary), the cell's 4
    slots: the latent kernel is in the program by name at the chunk's
    width and is its FIRST kernel call, the grouped expert matmuls
    follow, and the latent pool is the loop's carry in place: no copy
    of either of its arrays (one array of 576 values a line is re-laid
    with the page on its lanes for the line write and back for the
    kernel), of an expert stack or of a layer of one."""
    from flexflow_tpu.models import deepseek_v3 as fam

    cfg = fam.config(num_hidden_layers=3, first_k_dense_replace=1,
                     experts_held=(0, 16), vocab_size=16160,
                     dtype=jnp.bfloat16)
    slots, pages, cache_len = 4, 82, 10432
    params = _on(jax.eval_shape(
        functools.partial(fam.init_params, cfg=cfg), jax.random.PRNGKey(0)),
        chip)
    cache = _on(jax.eval_shape(functools.partial(
        fam.init_paged_kv_cache, cfg, slots * 81, PAGE, jnp.bfloat16)), chip)

    def step(params, cache, tokens, positions, logits_idx, page_table):
        return fam.serve_step_paged(
            params, cache, tokens, positions, logits_idx, None, None,
            page_table, cfg=cfg, cache_len=cache_len, kernels="pallas",
            pack=pack)

    compiled, text = _compile(
        step, params, cache, chip((slots, C), jnp.int32),
        chip((slots, C), jnp.int32), chip((slots,), jnp.int32),
        chip((slots, pages), jnp.int32), donate=(1,))
    entry = text[text.index("\nENTRY "):]
    calls = re.findall(r"= (\S+) custom-call\(.*tpu_custom_call", entry)
    assert f"%ff_mla_paged_c{C}" in text
    assert f"[{slots},{C},128,512]" in calls[0], calls[:2]
    tokens = pack or slots * C
    tm = kernels.grouped_tile(8 * tokens, 16, 256)
    assert tm == (128 if tokens > 128 else 16)   # as before ISSUE 51
    rows = _pair_rows(8 * tokens, 16, 256)
    assert re.findall(rf"%ff_moe_grouped_glu_t{tm}\S* = bf16\[{rows},2048\]", text)
    assert re.findall(rf"%ff_moe_grouped_down_t{tm}\S* = f32\[{rows},7168\]", text)
    experts = params["sparse"]["w_gate"]
    for a in (cache["latent"], cache["latent_rope"], experts,
              jax.ShapeDtypeStruct(experts.shape[1:], experts.dtype)):
        dims = ",".join(map(str, a.shape))
        assert not re.findall(rf"= \w+\[{dims}\]\S* copy\(", text), dims
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 1.5e9, temp


_SMALLTHINKER_STEPS = {}


def _smallthinker_step(chip, layers, C, pack):
    """:func:`_lower_smallthinker_step`, each program lowered once: the
    rungs are held to the padded step's bytes."""
    key = (layers, C, pack)
    if key not in _SMALLTHINKER_STEPS:
        _SMALLTHINKER_STEPS[key] = _lower_smallthinker_step(chip, *key)
    return _SMALLTHINKER_STEPS[key]


def _lower_smallthinker_step(chip, layers, C, pack, slots=8, max_seq=16384):
    """models/smallthinker.py's step at published widths and the
    benchmark cell's serving sizes, lowered with a table a class of
    page as the engine hands them: (compiled, text, params, cache,
    window table pages)."""
    from flexflow_tpu.models import smallthinker as fam
    from flexflow_tpu.serve.paging import window_table_pages

    cfg = fam.config(num_hidden_layers=layers, dtype=jnp.bfloat16)
    pages = -(-(max_seq + 65) // PAGE)
    win = window_table_pages(cfg.sliding_window, 128, PAGE)
    params = _on(jax.eval_shape(
        functools.partial(fam.init_params, cfg=cfg), jax.random.PRNGKey(0)),
        chip)
    cache = _on(jax.eval_shape(functools.partial(
        fam.init_paged_kv_cache, cfg, slots * pages, PAGE, jnp.bfloat16,
        class_pages={"full": slots * pages, "window": slots * win})), chip)
    table = {"full": chip((slots, pages), jnp.int32),
             "window": chip((slots, win), jnp.int32),
             "window_start": chip((slots,), jnp.int32)}

    def step(params, cache, tokens, positions, logits_idx, page_table):
        return fam.serve_step_paged(
            params, cache, tokens, positions, logits_idx, None, None,
            page_table, cfg=cfg, cache_len=max_seq + 64, kernels="pallas",
            pack=pack)

    compiled, text = _compile(
        step, params, cache, chip((slots, C), jnp.int32),
        chip((slots, C), jnp.int32), chip((slots,), jnp.int32), table,
        donate=(1,))
    return compiled, text, params, cache, win


@pytest.mark.parametrize("C, pack", [(1, None), (128, 256), (128, 512),
                                     (128, None)])
def test_smallthinker_step_compiles_in_place(chip, C, pack):
    """models/smallthinker.py at published widths (28 query heads to 4
    K/V heads of 128: a group of SEVEN, handed to the merged-head body
    padded to eight, which costs it a fifth of the time on the chip
    (``smallthinker._pad_groups``); 64 ReGLU experts of 768; the whole
    vocabulary), four layers (a full
    layer, then a run of three window layers), 8 slots of a 16 384
    context: the full layers' call walks the context's 129 pages under
    the accepted name, the window layers' the window class's 34 under
    a name of its own, the full layer's is the program's FIRST kernel
    call (the trace reduction keys the step by its result), the grouped
    expert matmuls follow at the row tile of the program's static
    pairs (the C=1 step's 48 over 64 experts: 16; every rung of the
    mixed step, 24, 48 and 96 rows an expert: 32, ISSUE 51) with an
    expert's whole matrix a weight block, no rung needs more of the
    device than the padded step, and both classes' pools are the
    loop's carry: no copy of a pool or of a layer's experts."""
    compiled, text, params, cache, win = _smallthinker_step(chip, 4, C, pack)
    slots = 8
    assert win == 34
    entry = text[text.index("\nENTRY "):]
    calls = re.findall(r"= (\S+) custom-call\(.*tpu_custom_call", entry)
    assert f"[{slots},{C},4,8,128]" in calls[0], calls[:2]
    names = set(re.findall(r"%(ff_ragged_paged_c\d+\w*?)(?:\.\d+)* = ", text))
    assert names == {f"ff_ragged_paged_c{C}", f"ff_ragged_paged_c{C}_win"}, names
    tokens = pack or slots * C
    tm, rows = kernels.grouped_tile(6 * tokens, 64), _pair_rows(6 * tokens, 64)
    assert tm == (16 if C == 1 else 32)
    assert re.findall(rf"%ff_moe_grouped_glu_t{tm}\S* = bf16\[{rows},768\]", text)
    assert re.findall(rf"%ff_moe_grouped_down_t{tm}\S* = f32\[{rows},2560\]", text)
    if pack:
        assert _need(compiled) <= _need(_smallthinker_step(chip, 4, C, None)[0])
    experts = params["sparse"]["w_gate"]
    for a in (cache["k"], cache["k_win"], experts,
              jax.ShapeDtypeStruct(experts.shape[1:], experts.dtype)):
        dims = ",".join(map(str, a.shape))
        assert not re.findall(rf"= \w+\[{dims}\]\S* copy\(", text), dims
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 3 * experts.size // experts.shape[0] * experts.dtype.itemsize


def test_smallthinker_padded_step_fits_at_the_cells_depth(chip):
    """The benchmark cell's twelve layers, padded C=128 step: the bytes
    the program holds stay under the 15.49 GB a program may use with
    the room the cell's runtime needs beside it (the probe's reference:
    16.1 GB of 16.9 at the peak, PERF.md section 4). 12.73 GB at the
    16-row tile, 12.84 at a 64-row one and 12.88 at 128; the 32-row
    tile's 1024 more aligned rows (35 MB of a layer's temporaries) stay
    under the step's peak elsewhere: 12.73 still (ISSUE 51)."""
    compiled = _smallthinker_step(chip, 12, 128, None)[0]
    assert _need(compiled) / 1e9 == pytest.approx(12.73, abs=0.02)


@pytest.mark.parametrize("C", [1, 128])
def test_laguna_step_compiles_at_the_cells_depth(chip, C):
    """models/laguna.py at published widths and the benchmark cell's
    depth and serving sizes (five layers: [F, S, S, S, F], the first
    dense; 16 slots of 133 pages; the window class six pages a slot):
    the full layers' call at 48 query heads handed over as 8 groups of
    8 (6 real) under the accepted name and FIRST in the program, the
    window layers' at 64 under ``_win``, the grouped expert matmuls at
    256 groups, both classes' pools and the experts carried in place,
    and the weight and pool argument bytes equal to the configuration's
    arithmetic (3869.9 M parameters; 2.23 + 0.15 GB of pool)."""
    from flexflow_tpu.models import laguna as fam
    from flexflow_tpu.serve.paging import window_table_pages

    slots, max_seq = 16, 16928
    cfg = fam.config(num_hidden_layers=5, dtype=jnp.bfloat16)
    pages = -(-(max_seq + 65) // PAGE)
    win = window_table_pages(cfg.sliding_window, 128, PAGE)
    assert (pages, win) == (133, 6)
    params = _on(jax.eval_shape(
        functools.partial(fam.init_params, cfg=cfg), jax.random.PRNGKey(0)),
        chip)
    cache = _on(jax.eval_shape(functools.partial(
        fam.init_paged_kv_cache, cfg, slots * pages, PAGE, jnp.bfloat16,
        class_pages={"full": slots * pages, "window": slots * win})), chip)
    table = {"full": chip((slots, pages), jnp.int32),
             "window": chip((slots, win), jnp.int32),
             "window_start": chip((slots,), jnp.int32)}

    def step(params, cache, tokens, positions, logits_idx, page_table):
        return fam.serve_step_paged(
            params, cache, tokens, positions, logits_idx, None, None,
            page_table, cfg=cfg, cache_len=max_seq + 64, kernels="pallas",
            pack=512 if C > 1 else None)

    compiled, text = _compile(
        step, params, cache, chip((slots, C), jnp.int32),
        chip((slots, C), jnp.int32), chip((slots,), jnp.int32), table,
        donate=(1,))

    def nbytes(tree):
        return sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(tree))

    matmul = (2 * 29_458_432 + 3 * 37_879_808 + 50_331_648 + 4 * 808_976_384
              + 2 * 100352 * 2048)
    assert matmul == 3_869_835_264
    assert nbytes(params) == 2 * matmul + 2 * (11 * 2048 + 10 * 128) + 4 * 4 * 256
    assert nbytes(cache) == 4096 * 128 * (2 * (slots * pages + 1) + 3 * (slots * win + 1))
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes - nbytes(params) - nbytes(cache) < 1 << 20
    entry = text[text.index("\nENTRY "):]
    calls = re.findall(r"= (\S+) custom-call\(.*tpu_custom_call", entry)
    assert f"[{slots},{C},8,8,128]" in calls[0], calls[:2]
    names = set(re.findall(r"%(ff_ragged_paged_c\d+\w*?)(?:\.\d+)* = ", text))
    assert names == {f"ff_ragged_paged_c{C}", f"ff_ragged_paged_c{C}_win"}, names
    tokens = 512 if C > 1 else slots
    tm, rows = kernels.grouped_tile(8 * tokens, 256), _pair_rows(8 * tokens, 256)
    assert re.findall(rf"%ff_moe_grouped_glu_t{tm}\S* = bf16\[{rows},512\]", text)
    assert re.findall(rf"%ff_moe_grouped_down_t{tm}\S* = f32\[{rows},2048\]", text)
    experts = params["sparse"]["w_gate"]
    for a in (cache["k"], cache["k_win"], experts,
              jax.ShapeDtypeStruct(experts.shape[1:], experts.dtype)):
        dims = ",".join(map(str, a.shape))
        assert not re.findall(rf"= \w+\[{dims}\]\S* copy\(", text), dims
    assert _need(compiled) / 1e9 < 12.0
    print(f"laguna C={C}: need {_need(compiled) / 1e9:.2f} GB, temp "
          f"{mem.temp_size_in_bytes / 1e9:.2f} GB, tile {tm}")


@pytest.mark.parametrize("C", [1, 128])
def test_longcat_flash_step_compiles_at_the_cells_depth(chip, C):
    """models/longcat_flash.py at published widths and the benchmark
    cell's depth and serving sizes (four layers of two latent attentions,
    two dense FFNs and the routed block; 16 of 512 experts held under a
    router of 768 outputs; an eighth of the vocabulary; 16 slots of 133
    pages, TWO lines a token and layer): the latent kernel at 64 heads
    under its accepted name and FIRST in the program, the grouped expert
    matmuls at 16 groups, the pool (eight lines deep) and the experts
    carried in place, and the weight and pool argument bytes equal to
    the configuration's arithmetic (5172.6 M parameters; 2.51 GB of
    pool)."""
    from flexflow_tpu.models import longcat_flash as fam

    slots, max_seq = 16, 16928
    cfg = fam.config(num_hidden_layers=4, experts_held=(0, 16),
                     vocab_size=16384, dtype=jnp.bfloat16)
    pages = -(-(max_seq + 65) // PAGE)
    assert pages == 133
    params = _on(jax.eval_shape(
        functools.partial(fam.init_params, cfg=cfg), jax.random.PRNGKey(0)),
        chip)
    cache = _on(jax.eval_shape(functools.partial(
        fam.init_paged_kv_cache, cfg, slots * pages, PAGE, jnp.bfloat16)), chip)

    def step(params, cache, tokens, positions, logits_idx, page_table):
        return fam.serve_step_paged(
            params, cache, tokens, positions, logits_idx, None, None,
            page_table, cfg=cfg, cache_len=max_seq + 64, kernels="pallas",
            pack=512 if C > 1 else None)

    compiled, text = _compile(
        step, params, cache, chip((slots, C), jnp.int32),
        chip((slots, C), jnp.int32), chip((slots,), jnp.int32),
        chip((slots, pages), jnp.int32), donate=(1,))

    def nbytes(tree):
        return sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(tree))

    attention = (6144 * 1536 + 1536 * 64 * 192 + 6144 * 576
                 + 512 * 64 * 256 + 64 * 128 * 6144)
    layer = (2 * attention + 2 * 3 * 6144 * 12288 + 6144 * 768
             + 16 * 3 * 6144 * 2048)
    matmul = 4 * layer + 2 * 16384 * 6144
    assert (attention, layer, matmul) == (90_570_752, 1_242_824_704, 5_172_625_408)
    scales = 4 * (2 * (6144 + 1536 + 512) + 2 * 6144) + 6144
    assert nbytes(params) == 2 * (matmul + scales) + 4 * 4 * 768
    assert nbytes(cache) == 8 * (slots * pages + 1) * 128 * 1152
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes - nbytes(params) - nbytes(cache) < 1 << 20
    # one kind of layer: every kernel call lies in the loop's body, the
    # first attention's first (the benchmark keys a step program by it)
    body, = (c for c in text.split("\n\n") if f"%ff_mla_paged_c{C}" in c)
    calls = re.findall(r"%(ff_\w+?)(?:\.\d+)* = (\S+) custom-call\(.*tpu_custom_call", body)
    # (where in the body the compiler puts the routed block's two calls
    # is its own: the shortcut leaves it free up to the layer's last sum;
    # at C=1 they follow the SECOND attention)
    tile = 16 if C == 1 else 128
    assert sorted(name for name, _ in calls) == [
        f"ff_mla_paged_c{C}", f"ff_mla_paged_c{C}",
        f"ff_moe_grouped_down_t{tile}", f"ff_moe_grouped_glu_t{tile}"], calls
    assert calls[0][0] == f"ff_mla_paged_c{C}"
    assert f"[{slots},{C},64,512]" in calls[0][1], calls[0]
    tokens = 512 if C > 1 else slots
    tm = kernels.grouped_tile(12 * tokens, 16, 768)
    rows = _pair_rows(12 * tokens, 16, 768)
    assert re.findall(rf"%ff_moe_grouped_glu_t{tm}\S* = bf16\[{rows},2048\]", text)
    assert re.findall(rf"%ff_moe_grouped_down_t{tm}\S* = f32\[{rows},6144\]", text)
    experts = params["sparse"]["w_gate"]
    for a in (cache["latent"], cache["latent_rope"], experts,
              jax.ShapeDtypeStruct(experts.shape[1:], experts.dtype)):
        dims = ",".join(map(str, a.shape))
        assert not re.findall(rf"= \w+\[{dims}\]\S* copy\(", text), dims
    # nothing runs over a whole pool flattened (a scatter of half lane
    # tiles does: a 279 MB operand, one update a token), and the
    # kernel's (slots, C) result is not laid out anew on its way back to
    # the token axis (1.2 ms a call where (H, c) were folded first)
    for a in (cache["latent"], cache["latent_rope"]):
        assert f"[{a.size}]" not in text
    if C > 1:
        for dims in re.findall(r"= \w+\[([\d,]+)\]\S* copy\(", text):
            assert math.prod(map(int, dims.split(","))) < slots * C * 64 * 512, dims
    # ONE loop carries both of the pool's arrays whole
    loops = [carry for carry in re.findall(r"= \((.*?)\) while\(", text)
             if "bf16[8,2129,128,512]" in carry]
    assert len(loops) == 1 and "bf16[8,2129,64,128]" in loops[0]
    assert _need(compiled) / 1e9 < 14.0
    print(f"longcat_flash C={C}: need {_need(compiled) / 1e9:.2f} GB, temp "
          f"{mem.temp_size_in_bytes / 1e9:.2f} GB, tile {tm}")
