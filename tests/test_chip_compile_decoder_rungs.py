"""Chip-compiler tests, the generic decoder's packed rungs: Mistral's and
Mixtral's mixed step at every width of the ladder and Mixtral's routed step,
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

from flexflow_tpu.models import mistral
from flexflow_tpu.serve import kernels

from chip_compile import *  # noqa: F401,F403 (shapes, helpers)


@pytest.mark.parametrize("family", ["mistral", "mixtral"])
def test_packed_rungs_compile_with_the_pool_in_place(chip, family):
    """Every rung of the (16, 128) ladder (ISSUE 32; the admission
    rung's 256 places under them, ISSUE 45) at published widths, two
    layers: the kernel's call keeps its (slots, chunk) shape and its
    name (the benchmark finds the step program by them), the pool stays
    the loop's carry updated in place, the matmuls run at the rung's
    width (a sparse model's over the rung's routed pairs: at 256 places
    they are 64 an expert, so the 32-row tile, ISSUE 51), and no rung
    needs more
    of the device than the padded step, which is what
    ``benchmarks/tools/fit.py`` sizes a depth by."""
    from flexflow_tpu.models import mixtral
    from flexflow_tpu.serve.engine import pack_widths

    mod = mistral if family == "mistral" else mixtral
    cfg = (mistral.mistral_7b if family == "mistral" else
           mixtral.mixtral_8x7b)(dtype=jnp.bfloat16, num_hidden_layers=2)
    args = _step_args(chip, cfg, 128)
    if family == "mixtral":
        args = (_on(jax.eval_shape(functools.partial(
            mixtral.init_params, cfg=cfg), jax.random.PRNGKey(0)), chip),
        ) + args[1:]
    *rungs, top = pack_widths(R, 128)
    assert (rungs, top) == ([256, 512, 1024], 2048)

    def step(pack):
        def fn(params, cache, tokens, positions, logits_idx, page_table):
            return mod.serve_step_paged(
                params, cache, tokens, positions, logits_idx, None, None,
                page_table, cfg=cfg, cache_len=CACHE_LEN, kernels="pallas",
                pack=pack)
        return fn

    padded, _ = _compile(step(None), *args, donate=(1,))
    for width in rungs:
        compiled, text = _compile(step(width), *args, donate=(1,))
        # the attention call, and a sparse model's two grouped matmuls
        assert text.count("tpu_custom_call") == (
            1 if family == "mistral" else 3)
        kernel, = re.findall(r"%ff_ragged_paged_c128\S* = (\S+) custom-call",
                             text)
        assert kernel.startswith(f"bf16[{R},128,")   # reduce.kernel_chunk
        _assert_pool_carried(text, args[1]["k"])
        if family == "mistral":  # the pairs' rows hold more than a pool
            _assert_pool_in_place(compiled, text, args[1]["k"])
        # the FFN runs over the rung, not over slots x chunk: a dense one
        # at the rung's width, a sparse one over the rung's routed pairs
        rows = width if family == "mistral" else _pair_rows(2 * width, 8)
        assert re.search(rf"bf16\[(1,)?{rows},14336\]", text)
        assert not re.search(r"bf16\[(16,128|\d+,8),14336\]", text)
        assert family == "mixtral" or "bf16[2048,14336]" not in text
        assert _need(compiled) <= _need(padded)


@pytest.mark.parametrize("family, layers, gigabytes", [
    ("mistral", 20, 10.66), ("mixtral", 4, 12.57)])
def test_widest_rung_needs_what_the_padded_step_did(chip, family, layers,
                                                    gigabytes):
    """The widest rung is the padded step itself: at the benchmark's
    depths and pool (128 pages, 17 a slot) it needs what
    ``benchmarks/tools/fit.py`` counted before the ladder (PERF.md
    section 4), so a depth that fitted still fits. (Mixtral: 12.90 GB
    with the all-expert einsums' 0.49 GB of temporaries, 12.57 since
    its tokens are routed, ISSUE 36.)"""
    from flexflow_tpu.models import mixtral

    mod = mistral if family == "mistral" else mixtral
    cfg = (mistral.mistral_7b if family == "mistral" else
           mixtral.mixtral_8x7b)(dtype=jnp.bfloat16, num_hidden_layers=layers)
    params = _on(jax.eval_shape(functools.partial(
        mod.init_params, cfg=cfg), jax.random.PRNGKey(0)), chip)
    cache = _on(jax.eval_shape(functools.partial(
        mod.init_paged_kv_cache, cfg, 128, PAGE, jnp.bfloat16)), chip)

    def step(params, cache, tokens, positions, logits_idx, page_table):
        return mod.serve_step_paged(
            params, cache, tokens, positions, logits_idx, None, None,
            page_table, cfg=cfg, cache_len=CACHE_LEN, kernels="pallas")

    compiled, _ = _compile(
        step, params, cache, chip((R, 128), jnp.int32),
        chip((R, 128), jnp.int32), chip((R,), jnp.int32),
        chip((R, 17), jnp.int32), donate=(1,))
    assert _need(compiled) / 1e9 == pytest.approx(gigabytes, abs=0.02)


@pytest.mark.parametrize("C, pack", [(1, None), (128, 256), (128, 512),
                                     (128, 1024), (128, None)])
def test_mixtral_routed_step_compiles_in_place(chip, C, pack):
    """models/mixtral.py at published widths (4096 / 14336, 8 experts,
    top-2), two layers, the benchmark cell's 16 slots. Every packed
    rung and the padded step send their real tokens' pairs through the
    grouped expert matmuls (``ff_moe_grouped_*_t128``: from the 512
    rung on the static pairs are a 128-row tile an expert; the
    admission rung's 512 pairs are 64 an expert and take ``_t32``, two
    grid steps an expert where ``_t16`` was four, ISSUE 51), the
    attention call stays the program's FIRST kernel call (the trace
    reduction finds the step by it) and there is no all-expert product;
    the C=1 step (32 pairs: under a tile an expert) keeps the einsum.
    In all four the pool is the loop's carry in place and the scheduled
    program copies no expert stack and no layer of one (a layer sliced
    out to feed a kernel call would be 0.94 GB a projection a layer a
    step)."""
    from flexflow_tpu.models import mixtral

    cfg = mixtral.mixtral_8x7b(dtype=jnp.bfloat16, num_hidden_layers=2)
    args = (_on(jax.eval_shape(functools.partial(
        mixtral.init_params, cfg=cfg), jax.random.PRNGKey(0)), chip),
    ) + _step_args(chip, cfg, C)[1:]

    def step(params, cache, tokens, positions, logits_idx, page_table):
        return mixtral.serve_step_paged(
            params, cache, tokens, positions, logits_idx, None, None,
            page_table, cfg=cfg, cache_len=CACHE_LEN, kernels="pallas",
            pack=pack)

    compiled, text = _compile(step, *args, donate=(1,))
    calls = re.findall(
        r"%(\w+)(?:\.\d+)* = \S+ custom-call\(.*tpu_custom_call", text)
    tokens = pack or R * C
    if C == 1:
        assert calls == ["ff_ragged_paged_c1"], calls
        assert re.findall(rf"\[{tokens},8,14336\]", text)
    else:
        tm = kernels.grouped_tile(2 * tokens, 8)
        assert tm == (32 if tokens == 256 else 128)
        assert calls == [f"ff_ragged_paged_c{C}", f"ff_moe_grouped_glu_t{tm}",
                         f"ff_moe_grouped_down_t{tm}"], calls
        rows = _pair_rows(2 * tokens, 8)
        assert re.findall(
            rf"%ff_moe_grouped_glu_t{tm}\S* = bf16\[{rows},14336\]", text)
        assert re.findall(
            rf"%ff_moe_grouped_down_t{tm}\S* = f32\[{rows},4096\]", text)
        assert not re.findall(r"\[\d+,8,14336\]", text)  # no all-expert product
    _assert_pool_carried(text, args[1]["k"])
    for name in ("w_gate", "w_up", "w_down"):
        stack = args[0]["layers"][name]
        for shape in (stack.shape, stack.shape[1:]):
            dims = ",".join(map(str, shape))
            assert not re.findall(rf"= \w+\[{dims}\]\S* copy\(", text), dims
    # activations only: under one projection of one layer's experts
    temp = compiled.memory_analysis().temp_size_in_bytes
    stack = args[0]["layers"]["w_up"]
    assert temp < stack.size // stack.shape[0] * stack.dtype.itemsize
    assert set(compiled.output_shardings[1]) >= {"k", "v", "moe_counts"}
