"""Chip-compiler tests, the generic decoder's step programs: Mistral-7B's and
Llama's paged steps (the program ``chip_smoke.py`` runs), their arms, the
fused RoPE prologue and the greedy head's decode program,
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

from flexflow_tpu.models import llama, mistral

from chip_compile import *  # noqa: F401,F403 (shapes, helpers)


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
