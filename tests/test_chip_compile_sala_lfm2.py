"""Chip-compiler tests, MiniCPM-SALA's hybrid step and LFM2-MoE's (a state or a
convolution's tail a slot beside the page pool),
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
import pytest

from flexflow_tpu.serve import kernels

from chip_compile import *  # noqa: F401,F403 (shapes, helpers)


@pytest.mark.parametrize("C", [1, 128])
def test_minicpm_sala_hybrid_step_compiles_in_place(chip, C):
    """models/minicpm_sala.py at published widths, five layers (sparse,
    two lightning, two sparse: every kind of run and transition), the
    benchmark cell's 4 slots of 146 pages: both attention kernels are in
    the program by name, and the loop's carry is updated in place: no
    copy of a K/V pool, of the lightning states or of the compressed
    keys, and temporaries under one pool (a conditional that took the
    compressed keys as an operand copied all of them, twice a step)."""
    from flexflow_tpu.models import minicpm_sala as sala

    S, L = sala.SPARSE, sala.LIGHTNING
    cfg = sala.config(num_hidden_layers=5, mixer_types=(S, L, L, S, S),
                      dtype=jnp.bfloat16)
    slots, pages, cache_len = 4, 146, 18624
    params = _on(jax.eval_shape(
        functools.partial(sala.init_params, cfg=cfg), jax.random.PRNGKey(0)),
        chip)
    cache = _on(jax.eval_shape(functools.partial(
        sala.init_paged_kv_cache, cfg, slots * pages, PAGE, jnp.bfloat16,
        num_slots=slots, cache_len=cache_len)), chip)

    def step(params, cache, tokens, positions, logits_idx, page_table):
        return sala.serve_step_paged(
            params, cache, tokens, positions, logits_idx, None, None,
            page_table, cfg=cfg, cache_len=cache_len, kernels="pallas")

    compiled, text = _compile(
        step, params, cache, chip((slots, C), jnp.int32),
        chip((slots, C), jnp.int32), chip((slots,), jnp.int32),
        chip((slots, pages), jnp.int32), donate=(1,))
    assert f"%ff_ragged_paged_c{C}" in text    # no row above dense_len
    assert f"%ff_sparse_paged_c{C}" in text    # some row above it
    # one call of each in the sparse layers' loop body, per run of them
    # (two runs in this order), nothing else made into a kernel
    assert text.count("tpu_custom_call") == 4
    for name in ("k", "v", "state", "kbar"):
        dims = ",".join(map(str, cache[name].shape))
        assert not re.findall(rf"= \w+\[{dims}\]\S* copy\(", text), name
    pool = cache["k"]
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < pool.size * pool.dtype.itemsize


@pytest.mark.parametrize("C, pack", [(1, None), (128, None), (128, 2048),
                                     (128, 256)])
def test_lfm2_moe_step_compiles_in_place(chip, C, pack):
    """models/lfm2_moe.py at published widths (head size 64, 64 experts
    of 1536, the whole vocabulary), five layers (a dense conv layer,
    then attention, two conv, attention: every kind of run), the
    benchmark cell's 64 slots of 8 pages, padded, on a packed rung and
    on the admission rung (ISSUE 45: 256 places, whose 1024 pairs are
    16 an expert, so the grouped calls are the decode step's ``_t16``):
    the ragged paged kernel is in the program by name at head size 64
    and is its FIRST kernel call (the trace reduction finds the step by
    it), the grouped expert matmuls (``ff_moe_grouped_*``) follow, there
    is no all-expert product, and the loop's carry is updated in place: no copy of a K/V
    pool, of the conv states or of a layer's expert weights, temporaries
    under one layer's experts (a relayout of the pool or a layer's
    experts sliced out of their stack would each be more)."""
    from flexflow_tpu.models import lfm2_moe as fam

    A, V = fam.ATTENTION, fam.CONV
    cfg = fam.config(num_hidden_layers=5, num_dense_layers=1,
                     layer_types=(V, A, V, V, A), dtype=jnp.bfloat16)
    slots, pages, cache_len = 64, 8, 1024
    params = _on(jax.eval_shape(
        functools.partial(fam.init_params, cfg=cfg), jax.random.PRNGKey(0)),
        chip)
    cache = _on(jax.eval_shape(functools.partial(
        fam.init_paged_kv_cache, cfg, slots * pages, PAGE, jnp.bfloat16,
        num_slots=slots, cache_len=cache_len)), chip)

    def step(params, cache, tokens, positions, logits_idx, page_table):
        return fam.serve_step_paged(
            params, cache, tokens, positions, logits_idx, None, None,
            page_table, cfg=cfg, cache_len=cache_len, kernels="pallas",
            pack=pack)

    compiled, text = _compile(
        step, params, cache, chip((slots, C), jnp.int32),
        chip((slots, C), jnp.int32), chip((slots,), jnp.int32),
        chip((slots, pages), jnp.int32), donate=(1,))
    # the scheduled ENTRY computation holds layer 1 (the first attention
    # layer and its sparse FFN: a run of one, unrolled) in program order
    entry = text[text.index("\nENTRY "):]
    calls = re.findall(r"= (\S+) custom-call\(.*tpu_custom_call", entry)
    kernel = f"[{slots},{C},8,4,64]"
    assert f"%ff_ragged_paged_c{C}" in text and kernel in calls[0], calls[:2]
    tokens = pack or slots * C
    assert not re.findall(rf"\[{tokens},64,1536\]", text)   # no all-expert product
    # the grouped expert matmuls, by name, over the routed pairs' rows
    # (each expert's rows aligned to the row tile)
    tm, rows = kernels.grouped_tile(4 * tokens, 64), _pair_rows(4 * tokens, 64)
    assert re.findall(rf"%ff_moe_grouped_glu_t{tm}\S* = bf16\[{rows},1536\]", text)
    assert re.findall(rf"%ff_moe_grouped_down_t{tm}\S* = f32\[{rows},2048\]", text)
    experts = params["sparse"]["w_gate"]
    for a in (cache["k"], cache["v"], cache["conv"], experts,
              jax.ShapeDtypeStruct(experts.shape[1:], experts.dtype)):
        dims = ",".join(map(str, a.shape))
        assert not re.findall(rf"= \w+\[{dims}\]\S* copy\(", text), dims
    # activations only (the padded step's 32768 pair rows of float32 are
    # 0.65 GB): under one sparse layer's experts, 1.2 GB
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 3 * experts.size // experts.shape[0] * experts.dtype.itemsize
