"""Chip-compiler tests: the serving main path's kernels and step program
compiled for a TPU v5e that is DESCRIBED, not attached (the TPU compiler
ships with the installation; nothing here executes).

This is the one file that may describe the topology. The description
loads the TPU library, which one process at a time may hold, so it
happens inside a module-scoped fixture — never at import, never in a
skipif/parametrize argument — and every compile runs in the test's own
process. Shapes are Mistral-7B's published widths (models/mistral.py);
only depth is cut. A compile that passes is not a chip run:
``chip_smoke.py`` is the run.
"""
import functools
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from flexflow_tpu.models import llama, mistral
from flexflow_tpu.serve import kernels

R, PAGE, PAGES_PER_SLOT = 16, 128, 16          # slots, tokens/page, NP
NUM_PAGES = R * PAGES_PER_SLOT                 # worst-case pool
CACHE_LEN = PAGE * PAGES_PER_SLOT              # 2048


@pytest.fixture(scope="module")
def topo():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def chip(one_chip, monkeypatch):
    """Steer the kernels to Mosaic (the default backend here is the CPU,
    whose branch is interpret mode) and keep the persistent compile
    cache off: a described-device executable is written to it but can
    never be read back without a chip."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    from flexflow_tpu.ops import flash_attention

    monkeypatch.setattr(kernels, "_interpret", lambda: False)
    monkeypatch.setattr(flash_attention, "_interpret", lambda: False)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield functools.partial(_shape, sharding=one_chip)
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _shape(shape, dtype, *, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _on(tree, sds):
    return jax.tree.map(lambda a: sds(a.shape, a.dtype), tree)


def _compile(fn, *args, donate=()):
    compiled = jax.jit(fn, donate_argnums=donate).lower(*args).compile()
    return compiled, compiled.as_text()


def _attention_args(sds, C, cfg, pool_dtype=jnp.bfloat16, dk_pool=None):
    H, KV, dk = (cfg.num_attention_heads, cfg.num_key_value_heads,
                 cfg.head_dim)
    pool = sds((NUM_PAGES + 1, PAGE, KV, dk_pool or dk), pool_dtype)
    return (
        sds((R, C, H, dk), jnp.bfloat16), pool, pool,
        sds((R, PAGES_PER_SLOT), jnp.int32),
        sds((R, C, CACHE_LEN), jnp.bool_),
    )


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
    """The kernel told each row's real queries (``q_len``: a third
    prefetched scalar, the padding-blind guard, at C=128 the narrow
    body beside the chunk-wide one) at Mistral-7B widths, under the
    ``vmem_limit_bytes`` the kernel states without it: the limit may
    not rise."""
    cfg = mistral.mistral_7b(dtype=jnp.bfloat16)
    limits = []
    stated = kernels._ragged_vmem_limit
    monkeypatch.setattr(
        kernels, "_ragged_vmem_limit",
        lambda *a: limits.append(stated(*a)) or limits[-1],
    )
    args = _attention_args(chip, C, cfg) + (chip((R,), jnp.int32),)

    def fn(q, kp, vp, pt, mask, q_len, use):
        return kernels.ragged_paged_attention(
            q, kp, vp, pt, mask, q_len=q_len if use else None)

    _compile(functools.partial(fn, use=False), *args)
    _, text = _compile(functools.partial(fn, use=True), *args)
    assert text.count("tpu_custom_call") == 1
    assert f"%ff_ragged_paged_c{C}" in text
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


def _step_args(sds, cfg, C, kv_quant=None, family=mistral):
    params = _on(
        jax.eval_shape(
            functools.partial(family.init_params, cfg=cfg),
            jax.random.PRNGKey(0),
        ),
        sds,
    )
    cache = _on(
        jax.eval_shape(
            functools.partial(
                family.init_paged_kv_cache, cfg, NUM_PAGES, PAGE,
                jnp.bfloat16, kv_quant=kv_quant,
            )
        ),
        sds,
    )
    return (
        params, cache,
        sds((R, C), jnp.int32), sds((R, C), jnp.int32), sds((R,), jnp.int32),
        sds((R, PAGES_PER_SLOT), jnp.int32),
    )


def _step(cfg, family=mistral, **kw):
    def step(params, cache, tokens, positions, logits_idx, page_table):
        return family.serve_step_paged(
            params, cache, tokens, positions, logits_idx, None, None,
            page_table, cfg=cfg, cache_len=CACHE_LEN, **kw,
        )

    return step


def _assert_pool_in_place(compiled, text, pool):
    """The donated pool is the layer loop's carry, updated in place: the
    program copies no whole pool and its temporaries are less than one.
    (As scanned inputs and outputs the pools were two buffers: two
    ``copy`` of a whole pool a step, a slice and a write-back a layer,
    a second pool among the temporaries; PERF.md, PR 28.)"""
    dims = ",".join(map(str, pool.shape))
    assert not re.findall(rf"= \w+\[{dims}\]\S* copy\(", text)
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < pool.size * pool.dtype.itemsize


def _assert_pool_carried(text, pool):
    """The same, read off the program itself and not off its
    temporaries (a sparse model's expert einsums hold more than a pool):
    both stacked pools are parameters the program aliases to its
    outputs, the layer loop's ``while`` carries them whole, nothing
    yields ONE layer of a pool (a per-layer slice or write-back), and a
    whole pool comes only from the line write's scatter, in place."""
    whole = rf"\w+\[{','.join(map(str, pool.shape))}\]"
    layer = rf"\w+\[(1,)?{','.join(map(str, pool.shape[1:]))}\]"
    params = {int(n) for n in re.findall(
        rf"= {whole}\S* parameter\((\d+)\), sharding", text)}
    alias, = re.findall(r"input_output_alias={(.*?) }, entry", text)
    aliased = {int(n) for n in re.findall(r"\((\d+), {}, \S+?\)", alias)}
    assert len(params) == 2 and params <= aliased
    # (a routed expert layer's grouping has small loops of its own)
    loop, = (carry for carry in re.findall(r"= \((.*?)\) while\(", text)
             if re.search(whole, carry))
    assert len(re.findall(whole, loop)) == 2
    assert not re.findall(rf"= {layer}\S* [\w-]+\(", text)
    makers = set(re.findall(rf"= {whole}\S* ([\w-]+)\(", text))
    assert makers <= {"parameter", "get-tuple-element", "fusion", "scatter",
                      "bitcast"}, makers


@pytest.mark.parametrize("C", [1, 128])
def test_mistral_paged_pallas_step_compiles(chip, C):
    """The step program chip_smoke.py runs: published widths, 2 layers,
    decode (C=1) and the mixed step (C=128), the pool donated as the
    engine donates it."""
    cfg = mistral.mistral_7b(dtype=jnp.bfloat16, num_hidden_layers=2)
    args = _step_args(chip, cfg, C)
    compiled, text = _compile(_step(cfg, kernels="pallas"), *args, donate=(1,))
    assert "tpu_custom_call" in text
    assert f"%ff_ragged_paged_c{C}" in text  # inside the layer scan too
    # ONE kernel call a layer (the scan's body holds it once), whatever
    # the rows' query lengths: the narrow body is a branch inside it
    assert text.count("tpu_custom_call") == 1
    # weights + pool + temporaries of this cut fit one 16 GB chip
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9
    _assert_pool_in_place(compiled, text, args[1]["k"])
    _assert_pool_carried(text, args[1]["k"])


def test_llama_paged_pallas_decode_step_keeps_pool_in_place(chip):
    """The llama family's step is the decoder's (ISSUE 49): at
    ``llama_7b`` widths (32 K/V heads: four times Mistral's pool a
    token) its compiled C=1 step holds the donated pool once."""
    cfg = llama.LLaMAConfig.llama_7b(dtype=jnp.bfloat16, num_hidden_layers=2)
    args = _step_args(chip, cfg, 1, family=llama)
    compiled, text = _compile(
        _step(cfg, family=llama, kernels="pallas"), *args, donate=(1,))
    assert text.count("tpu_custom_call") == 1 and "%ff_ragged_paged_c1" in text
    _assert_pool_in_place(compiled, text, args[1]["k"])
    _assert_pool_carried(text, args[1]["k"])


@pytest.mark.parametrize("C", [1, 128])
@pytest.mark.parametrize("arm", [
    {"kv_quant": "int8"}, {"fused_rope": True}, {"num_layers": 2},
], ids=lambda arm: next(iter(arm)))
def test_mistral_paged_step_arms_keep_pool_in_place(chip, C, arm):
    """The arms no benchmark cell runs address their layer inside the
    same carry: quantized pool, in-kernel RoPE and KV write through the
    aliased pool outputs, early-exit draft (3 layers)."""
    cfg = mistral.mistral_7b(dtype=jnp.bfloat16, num_hidden_layers=3)
    args = _step_args(chip, cfg, C, arm.get("kv_quant"))
    compiled, text = _compile(
        _step(cfg, kernels="pallas", **arm), *args, donate=(1,)
    )
    _assert_pool_in_place(compiled, text, args[1]["k"])


def _need(compiled):
    """Bytes the program holds on the device, as benchmarks/tools/fit.py
    counts them."""
    mem = compiled.memory_analysis()
    return (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)


def _pair_rows(pairs, experts, routed=None):
    """Rows of the grouped expert matmuls at ``pairs`` static (token,
    expert) pairs: every expert's rows aligned to the row tile."""
    tm = kernels.grouped_tile(pairs, experts, routed)
    return -(-(pairs + experts * (tm - 1)) // tm) * tm


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


# --- kernels repaired in PR 23 (refused by the chip's compiler before) ---


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


@pytest.mark.parametrize("C", [1, 128])
@pytest.mark.parametrize("kv_quant", [None, "int8", "int4"])
def test_mistral_fused_rope_step_compiles(chip, C, kv_quant):
    """fused_decode=("rope_kv_write",): RoPE + the (quantizing) KV write
    inside the ragged paged kernel. On a bf16 pool the C=128 mixed step
    was refused for 17.1 MB of scoped VMEM until the kernel stated its
    limit; on quantized pools the in-kernel commit reshaped an i1 vector
    and moved the per-page scale from lanes to a leading dim, both
    refused ("unsupported shape cast")."""
    cfg = mistral.mistral_7b(dtype=jnp.bfloat16, num_hidden_layers=2)
    kw = dict(kernels="pallas", fused_rope=True)
    if kv_quant:
        kw["kv_quant"] = kv_quant
    _, text = _compile(_step(cfg, **kw), *_step_args(chip, cfg, C, kv_quant))
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


# --- the hybrid family: two kinds of layer, per-slot state beside the pool ---


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


# --- conv layers beside attention layers, routed experts (LFM2-MoE) ---------


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


def _assert_kernel_calls(text, want, slots, C):
    """The program's Pallas calls are ``want`` ((name, what its result
    starts with) pairs, sorted by name), each result [slots, C, ...]:
    the trace reduction keys the step by its FIRST kernel's result,
    whichever call that is."""
    calls = re.findall(
        r"%(\w+?)(?:\.\d+)* = (.+?) custom-call\(.*tpu_custom_call", text)
    assert sorted(name for name, _ in calls) == [name for name, _ in want], calls
    for name, shape in calls:
        assert dict(want)[name] in shape, calls
        assert re.search(r"\[(\d+),(\d+),", shape).groups() == (str(slots), str(C))


# --- Gated DeltaNet layers beside full attention (Olmo-Hybrid) ---------------


@pytest.mark.parametrize("C, pack", [(1, None), (128, 2048), (128, 256)])
def test_olmo_hybrid_step_compiles_in_place(chip, C, pack):
    """models/olmo_hybrid.py at published widths (30 heads of 128 with
    as many K/V heads, 30 recurrent heads of 96 x 192, the whole
    vocabulary), five layers (three recurrent, attention, one
    recurrent: both kinds of run), the benchmark cell's 64 slots of 8
    pages, the decode step, a packed rung and the admission rung (ISSUE
    45: 256 places): in the two mixed programs the ragged paged kernel
    is the ONLY kind of kernel call (at C = 128 thirty heads of one
    query a group pass the fast memory at once, and the call takes them
    in blocks under one name); the decode step also calls the delta
    rule's kernel, once a run of recurrent layers (``ff_gdn_recur_c1``,
    on the state stack in place, ``o`` its first result); either way
    the program's FIRST kernel result is [slots, chunk, ...] (the trace
    reduction keys the step by it), and the loop's carry is updated in
    place: no copy of the K/V pools, of the recurrent state stack (0.57
    GB here, 1.27 GB at the cell's nine layers) or of the convolution
    states, no relayout of a pool or of a layer's states (the state is
    kept two heads to a row of 384 lanes and every program reads and
    writes it so), temporaries (a packed rung's activations: 2048
    tokens' q, k and v in float32 are 94 MB) under two layers' states,
    where a second state stack would be four (the admission rung: no
    more than the padded step's). And the state's bytes on the device
    are its arithmetic: at (.., 30, 96, 192) the device pads each row
    of 192 to 256 lanes, a third more to hold and to move (ISSUE 48)."""
    from flexflow_tpu.models import olmo_hybrid as fam

    L, A = fam.LINEAR, fam.ATTENTION
    cfg = fam.config(num_hidden_layers=5, layer_types=(L, L, L, A, L),
                     dtype=jnp.bfloat16)
    slots, pages, cache_len = 64, 8, 1024
    params = _on(jax.eval_shape(
        functools.partial(fam.init_params, cfg=cfg), jax.random.PRNGKey(0)),
        chip)
    cache = _on(jax.eval_shape(functools.partial(
        fam.init_paged_kv_cache, cfg, slots * pages, PAGE, jnp.bfloat16,
        num_slots=slots, cache_len=cache_len)), chip)
    assert cache["state"].shape == (4, 64, 15, 96, 384)
    assert cache["state"].dtype == jnp.float32
    assert cache["conv"].shape == (4, 3, 64, 11520)

    def compile_at(pack):
        def step(params, cache, tokens, positions, logits_idx, page_table):
            return fam.serve_step_paged(
                params, cache, tokens, positions, logits_idx, None, None,
                page_table, cfg=cfg, cache_len=cache_len, kernels="pallas",
                pack=pack)

        return _compile(
            step, params, cache, chip((slots, C), jnp.int32),
            chip((slots, C), jnp.int32), chip((slots,), jnp.int32),
            chip((slots, pages), jnp.int32), donate=(1,))

    compiled, text = compile_at(pack)
    attn = (f"ff_ragged_paged_c{C}", f"[{slots},{C},30,1,128]")
    # the C=1 program: a recurrence call a run of recurrent layers (two
    # runs here: each run's loop body is one computation of the text), o
    # ahead of the stack in its result, so the step is keyed 1 though
    # its first call is a recurrent layer's (as in the cell's program)
    recur = ("ff_gdn_recur_c1", f"(f32[{slots},1,15,384]")
    _assert_kernel_calls(text, [attn] if C > 1 else [recur, recur, attn], slots, C)
    layer = cache["state"].shape[1:]
    apart = (slots, 30, 96, 192)
    for a in (cache["k"], cache["v"], cache["state"], cache["conv"],
              jax.ShapeDtypeStruct(layer, jnp.float32),
              jax.ShapeDtypeStruct(apart, jnp.float32),
              jax.ShapeDtypeStruct((1,) + apart, jnp.float32)):
        dims = ",".join(map(str, a.shape))
        assert not re.findall(rf"= \w+\[{dims}\]\S* copy\(", text), dims
    # no layer's states with the heads apart anywhere: nothing re-lays a layer
    assert f"[{','.join(map(str, apart))}]" not in text
    temp = compiled.memory_analysis().temp_size_in_bytes
    if pack == 256:
        # the admission rung is held to what its issue asks: no more
        # than the padded step's (292 MB against 1.37 GB, compiled here,
        # PR 45; two layers' states are 283 MB)
        padded, _ = compile_at(None)
        assert temp <= padded.memory_analysis().temp_size_in_bytes, temp
    else:
        assert temp < 2 * np.prod(layer) * 4, temp


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


# --- Gated DeltaNet at 2 value heads a key head, gated attention at head
# --- size 256 and routed experts behind them (Qwen3-Next) ------------------


def test_qwen3_next_decode_step_compiles_in_place(chip):
    """models/qwen3_next.py at published widths (16 key and 32 value
    heads of 128 x 128, 16 / 2 softmax heads of 256, experts of 512
    chosen 10 of the router's 512, of which this chip holds 128, a
    quarter of the vocabulary), one period of four layers, the
    benchmark cell's 64 slots of 8 pages, the decode step: the delta
    rule's kernel is the program's FIRST kernel call, once for the run
    of three recurrent layers, on the state stack in place with ``o``
    its first result ([slots, 1, ...]: the trace reduction keys the
    step by it); the ragged paged kernel takes a pool line of 2 heads x
    256 merged; the grouped expert matmuls run at the 16-row tile over
    640 pairs' rows (1.25 rows an expert); nothing copies the K/V
    pools, the state stack, the convolution states or a layer's
    experts, and the temporaries are a few MB. The state's bytes as an
    argument are its arithmetic (ROADMAP B's rule for a per-slot
    float32 state: a minor extent of 128 is whole lane tiles, so
    ``lane_pack`` is 1): 3 layers x 64 slots x 32 x 128 x 128 x 4 here,
    9 layers' 1.21 GB at the cell's depth."""
    from flexflow_tpu.models import qwen3_next as fam

    cfg = fam.config(num_hidden_layers=4, experts_held=(0, 128),
                     vocab_size=37984, dtype=jnp.bfloat16)
    slots, pages, cache_len = 64, 8, 1024
    params = _on(jax.eval_shape(
        functools.partial(fam.init_params, cfg=cfg), jax.random.PRNGKey(0)),
        chip)
    cache = _on(jax.eval_shape(functools.partial(
        fam.init_paged_kv_cache, cfg, slots * pages, PAGE, jnp.bfloat16,
        num_slots=slots, cache_len=cache_len)), chip)
    assert cache["state"].shape == (3, 64, 32, 128, 128)
    assert cache["state"].dtype == jnp.float32 and fam.lane_pack(32, 128) == 1
    assert cache["conv"].shape == (3, 3, 64, 8192)
    assert cache["k"].shape == (1, slots * pages + 1, PAGE, 2 * 256)

    def step(params, cache, tokens, positions, logits_idx, page_table):
        return fam.serve_step_paged(
            params, cache, tokens, positions, logits_idx, None, None,
            page_table, cfg=cfg, cache_len=cache_len, kernels="pallas")

    compiled, text = _compile(
        step, params, cache, chip((slots, 1), jnp.int32),
        chip((slots, 1), jnp.int32), chip((slots,), jnp.int32),
        chip((slots, pages), jnp.int32), donate=(1,))
    calls = re.findall(
        r"%(\w+?)(?:\.\d+)* = (.+?) custom-call\(.*tpu_custom_call", text)
    tm, rows = kernels.grouped_tile(640, 128, 512), _pair_rows(640, 128, 512)
    assert (tm, rows) == (16, 2560)
    glu = (f"ff_moe_grouped_glu_t{tm}", f"bf16[{rows},512]")
    down = (f"ff_moe_grouped_down_t{tm}", f"f32[{rows},2048]")
    want = [("ff_gdn_recur_c1", "(f32[64,1,32,128]"), glu, down,
            ("ff_ragged_paged_c1", "bf16[64,1,2,8,256]"), glu, down]
    assert [name for name, _ in calls] == [name for name, _ in want], calls
    for (_, shape), (_, starts) in zip(calls, want):
        assert shape.startswith(starts), calls
    assert "f32[3,64,32,128,128]" in calls[0][1]   # the stack through the call
    experts = params["sparse"]["w_gate"]
    for a in (cache["k"], cache["v"], cache["state"], cache["conv"], experts,
              jax.ShapeDtypeStruct(cache["state"].shape[1:], jnp.float32),
              jax.ShapeDtypeStruct(experts.shape[1:], experts.dtype)):
        dims = ",".join(map(str, a.shape))
        assert not re.findall(rf"= \w+\[{dims}\]\S* copy\(", text), dims
    assert compiled.memory_analysis().temp_size_in_bytes < 16 << 20
    held, _ = _compile(lambda s: s + 1.0, chip(cache["state"].shape, jnp.float32),
                       donate=(0,))
    assert (held.memory_analysis().argument_size_in_bytes
            == 3 * 64 * 32 * 128 * 128 * 4 == 402_653_184)


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


# --- Mamba-2 layers beside attention (Granite 4.0-H) --------------------------


@pytest.mark.parametrize("C, pack", [(1, None), (128, 2048), (128, 256)])
def test_granite_hybrid_step_compiles_in_place(chip, C, pack):
    """models/granite_hybrid.py at published widths (64 state-space
    heads of 64 over a state of 128, GQA 32/8 at head size 64, the
    whole vocabulary, tied), five layers (two mamba, attention, two
    mamba: both kinds of run), the benchmark cell's 64 slots of 8
    pages, the decode step, a packed rung and the admission rung: in
    the two mixed programs the ragged paged kernel is the ONLY kind of
    kernel call; the decode step also calls the recurrence kernel, once
    a run of mamba layers (``ff_ssm_recur_c1``, on the state stack in
    place, ``y`` its first result); either way the program's FIRST
    kernel result is [slots, chunk, ...] (the trace reduction keys the
    step by it), and the loop's carry is updated in place: no copy of
    the state stack (0.54 GB here, 4.83 GB at the cell's 36 layers,
    where a second one does not fit the chip beside 6.38 GB of
    weights), of a layer's states, of the convolution states or of the
    K/V pools, temporaries (a packed rung's activations: 2048 tokens'
    convolved channels in float32 are 36 MB) under two layers' states,
    where a second state stack would be four."""
    from flexflow_tpu.models import granite_hybrid as fam

    M, A = fam.MAMBA, fam.ATTENTION
    cfg = fam.config(num_hidden_layers=5, layer_types=(M, M, A, M, M),
                     dtype=jnp.bfloat16)
    slots, pages, cache_len = 64, 8, 1024
    params = _on(jax.eval_shape(
        functools.partial(fam.init_params, cfg=cfg), jax.random.PRNGKey(0)),
        chip)
    cache = _on(jax.eval_shape(functools.partial(
        fam.init_paged_kv_cache, cfg, slots * pages, PAGE, jnp.bfloat16,
        num_slots=slots, cache_len=cache_len)), chip)
    assert cache["state"].shape == (4, 64, 64, 64, 128)
    assert cache["state"].dtype == jnp.float32
    assert cache["conv"].shape == (4, 3, 64, 4352)
    assert cache["k"].shape == (1, slots * pages + 1, PAGE, 512)

    def step(params, cache, tokens, positions, logits_idx, page_table):
        return fam.serve_step_paged(
            params, cache, tokens, positions, logits_idx, None, None,
            page_table, cfg=cfg, cache_len=cache_len, kernels="pallas",
            pack=pack)

    compiled, text = _compile(
        step, params, cache, chip((slots, C), jnp.int32),
        chip((slots, C), jnp.int32), chip((slots,), jnp.int32),
        chip((slots, pages), jnp.int32), donate=(1,))
    attn = (f"ff_ragged_paged_c{C}", f"[{slots},{C},8,4,64]")
    # the C=1 program: a recurrence call a run of mamba layers (two runs
    # here: each run's loop body is one computation of the text), y ahead
    # of the stack in its result, so whichever call runs first (a mamba
    # layer's here, as in the cell's program) the step is keyed 1
    recur = ("ff_ssm_recur_c1", f"(f32[{slots},1,64,64]")
    _assert_kernel_calls(text, [attn] if C > 1 else [attn, recur, recur], slots, C)
    layer = cache["state"].shape[1:]
    for a in (cache["k"], cache["v"], cache["state"], cache["conv"],
              jax.ShapeDtypeStruct(layer, jnp.float32)):
        dims = ",".join(map(str, a.shape))
        assert not re.findall(rf"= \w+\[{dims}\]\S* copy\(", text), dims
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 2 * np.prod(layer) * 4, temp


# --- the generic decoder's sparse layer with its tokens routed (Mixtral) ----


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


# --- latent attention over a compressed paged line (DeepSeek-V3) ------------


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


# --- the engine's own decode program: the head its batch chose (PR 41) ------


def _engine_decode_program(fam, cfg, slots, max_seq, head):
    """``InferenceEngine._get_mixed_step(1, ...)`` of an engine that
    holds no array: the program ``run_decode`` dispatches for a batch
    whose decode-head arrays chose ``head``, from the engine's own
    code (an engine that is built allocates its pool)."""
    from flexflow_tpu.core.mesh import MachineSpec
    from flexflow_tpu.obs import NULL_TRACER, BuildLog
    from flexflow_tpu.serve.engine import InferenceEngine, ServingConfig

    eng = object.__new__(InferenceEngine)
    eng.model, eng.cfg = fam, cfg
    eng.serving = ServingConfig(
        max_requests_per_batch=slots, max_sequence_length=max_seq,
        max_spec_tree_tokens=0, kv_layout="paged", page_size=PAGE,
        kernels="pallas")
    eng.mesh = MachineSpec().make_mesh(jax.devices()[:1])
    eng.paged, eng.cp_ring, eng.retrace_guard = True, False, None
    eng._step_counts = getattr(fam, "step_counts", lambda cfg: {})(cfg)
    eng._steps, eng._traced = {}, {}
    eng.build_log, eng.tracer = BuildLog(), NULL_TRACER  # _jit's wrapper's
    return eng._get_mixed_step(1, False, *head)


@pytest.mark.parametrize("family", ["mistral", "lfm2_moe"])
def test_greedy_decode_program_has_no_sort(chip, family):
    """``ff_step_c1`` as the engine compiles it for an all-greedy batch,
    at published widths (Mistral: 16 rows of 32000 logits; LFM2: the
    benchmark cell's 64 rows of 65536): no ``sort`` over a vocabulary
    in the program, while the full head's program of the same engine
    sorts its (rows, vocabulary) logits (4 ms of a 19 ms LFM2 step on
    the chip; ledger, PR 40)."""
    from flexflow_tpu.serve.sampling import choose_sample_mode

    if family == "mistral":
        fam, slots, pages = mistral, R, PAGES_PER_SLOT
        cfg = mistral.mistral_7b(dtype=jnp.bfloat16, num_hidden_layers=2)
        params, cache = _step_args(chip, cfg, 1)[:2]
    else:
        from flexflow_tpu.models import lfm2_moe as fam

        A, V = fam.ATTENTION, fam.CONV
        cfg = fam.config(num_hidden_layers=3, num_dense_layers=1,
                         layer_types=(V, A, V), dtype=jnp.bfloat16)
        slots, pages = 64, 8
        params = _on(jax.eval_shape(functools.partial(
            fam.init_params, cfg=cfg), jax.random.PRNGKey(0)), chip)
        cache = _on(jax.eval_shape(functools.partial(
            fam.init_paged_kv_cache, cfg, slots * pages, PAGE, jnp.bfloat16,
            num_slots=slots, cache_len=pages * PAGE)), chip)
    rows = lambda dtype: chip((slots,), dtype)
    args = (params, cache, rows(jnp.int32), chip((slots, 1), jnp.int32),
            rows(jnp.bool_), chip((slots, 1), jnp.int32), rows(jnp.int32),
            chip((2,), jnp.uint32), rows(jnp.bool_), rows(jnp.float32),
            rows(jnp.float32), rows(jnp.int32))
    table = chip((slots, pages), jnp.int32)
    logits = rf"f32\[{slots},{cfg.vocab_size}\]"
    greedy = choose_sample_mode(
        np.ones(slots, bool), np.full(slots, 2.0), np.zeros(slots), cfg.vocab_size)
    assert greedy == ("greedy", 0)
    for head, sorts in ((greedy, False), (("full", 0), True)):
        step = _engine_decode_program(fam, cfg, slots, pages * PAGE - 1, head)
        text = step.lower(*args, page_table=table).compile().as_text()
        name = "jit_ff_step_c1" + ("_full" if sorts else "")
        assert f"HloModule {name}," in text
        assert "%ff_ragged_paged_c1" in text
        # (the chip sorts values and places as a pair; a routed layer
        # sorts its pairs by expert: no such sort holds a vocabulary)
        vocab_sorts = [line for line in re.findall(r"= (.*) sort\(", text)
                       if f",{cfg.vocab_size}]" in line]
        assert bool(vocab_sorts) == sorts
        assert all(re.match(rf"\({logits}", s) for s in vocab_sorts)
        # nor is the temperature's divide over the logits there
        assert sorts == bool(re.findall(
            rf"%\S*divide\S* = \(?{logits}", text))


# --- full and window layers over two classes of page (SmallThinker) ----------


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


# --- heads by kind over two classes of page, every expert held (Laguna) ------


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
