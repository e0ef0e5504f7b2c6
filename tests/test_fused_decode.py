"""Fused decode step — fused-vs-unfused BITWISE parity.

The ``ServingConfig.fused_decode`` fusion must be bit-for-bit the
unfused step on the same backend, and so must the head every step
samples with on the device be the host-side head:

* "rope_kv_write" (serve/kernels.fused_rope_paged_attention): in-kernel
  RoPE + (optionally int8-quantizing) KV page write vs the unfused
  ``apply_rope → scatter/quant_line_write → ragged_paged_attention``
  composition — identical logits AND identical non-scratch pool bytes
  (the shared scratch page is written with padding garbage by both
  paths and read by neither);
* the mode-specialized heads (serve/sampling.py; no flag: the batch
  chooses): greedy-only / temperature-only / bucketed-top-k heads vs
  the full-sort reference head, and the steps that sample on the
  device (pipelined, and the one-dispatch ``engine.run_sampled`` sync
  step) vs step-then-host-sample (``RequestManager._sample``).

Covered pools: dense, paged, paged+int8; greedy plus per-row top-k
batches; the mixed prefill+decode step (continuous batching); TP2.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.core.mesh import MachineSpec
from flexflow_tpu.models import llama, transformer
from flexflow_tpu.serve import (
    InferenceEngine,
    RequestManager,
    ServingConfig,
)
from flexflow_tpu.serve.batch_config import GenerationConfig
from flexflow_tpu.serve.sampling import choose_sample_mode, sample_tokens


@pytest.fixture(scope="module")
def tiny(llama_recorded_params):
    cfg = llama.LLaMAConfig.tiny(dtype=jnp.float32)
    params = llama_recorded_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _sc(fused, *, kernels="xla", layout="paged", kv_quant=None, slots=4):
    return ServingConfig(
        max_requests_per_batch=slots,
        max_sequence_length=48,
        prefill_chunk=8,
        max_spec_tree_tokens=8,
        cache_dtype=jnp.float32,
        kv_layout=layout,
        page_size=8,
        kernels=kernels,
        kv_quant=kv_quant,
        fused_decode=fused,
        sanitizers=("retrace",),
    )


PROMPTS = [[(i * 7 + j * 3 + 1) % 256 for j in range(5 + i)] for i in range(4)]
# greedy and per-row top-k rows in one batch — the decode-head mix the
# mode-specialized sampling epilogue must serve bitwise-identically
# (topp=2.0 disables nucleus filtering so these land on the bucketed
# top-k head; the full-sort head is covered by the int8 test's default
# topp and by the sampling-level unit tests)
GENS = [
    GenerationConfig(),
    GenerationConfig(do_sample=True, topk=5, temperature=0.8, topp=2.0),
    GenerationConfig(),
    GenerationConfig(do_sample=True, topk=17, temperature=1.2, topp=2.0),
]
# a nucleus row forces the full-sort reference head — the int8+pallas
# end-to-end test runs on this mix so "full" mode is engine-covered too
GENS_TOPP = [
    GenerationConfig(),
    GenerationConfig(do_sample=True, topk=5, temperature=0.8, topp=0.9),
]


def _generate(rm, n_new=6, gens=GENS):
    rids = [rm.submit(p, g, max_new_tokens=n_new)
            for p, g in zip(PROMPTS, gens)]
    while rm.step():
        pass
    rm.drain()
    return [list(rm.requests[r].output_tokens) for r in rids]


# ---------------------------------------------------------------------------
# mode-specialized heads vs the full reference head


def test_sample_mode_heads_bitwise_match_full():
    rng = np.random.RandomState(3)
    R, V = 8, 256
    logits = jnp.asarray(rng.randn(R, V).astype(np.float32) * 4)
    key = jax.random.PRNGKey(11)

    def full(greedy, temp, topp, topk):
        return sample_tokens(
            logits, key, greedy=greedy, temperature=temp, topp=topp,
            topk_arr=topk,
        )

    def head(mode, cap, greedy, temp, topp, topk):
        return sample_tokens(
            logits, key, greedy=greedy, temperature=temp, topp=topp,
            topk_arr=topk, mode=mode, topk_cap=cap,
        )

    temp = jnp.asarray(rng.rand(R).astype(np.float32) + 0.5)
    off_p = jnp.full((R,), 2.0, jnp.float32)
    off_k = jnp.zeros((R,), jnp.int32)

    # greedy-only batch: no sort, no RNG — same argmax tokens
    g = jnp.ones((R,), bool)
    assert bool(jnp.all(full(g, temp, off_p, off_k)
                        == head("greedy", 0, g, temp, off_p, off_k)))
    # temperature-only sampling
    g0 = jnp.zeros((R,), bool)
    assert bool(jnp.all(full(g0, temp, off_p, off_k)
                        == head("sample", 0, g0, temp, off_p, off_k)))
    # mixed greedy + per-row top-k through the bucketed head
    gm = jnp.asarray(rng.rand(R) < 0.4)
    tk = jnp.where(gm, 0, jnp.asarray(rng.randint(1, 50, R))).astype(jnp.int32)
    mode, cap = choose_sample_mode(
        np.asarray(gm), np.full(R, 2.0, np.float32), np.asarray(tk), V
    )
    assert mode == "topk" and cap >= int(np.asarray(tk).max())
    assert bool(jnp.all(full(gm, temp, off_p, tk)
                        == head(mode, cap, gm, temp, off_p, tk)))


def test_choose_sample_mode():
    V = 256
    ones, zeros = np.ones(4, bool), np.zeros(4, bool)
    no_p, no_k = np.full(4, 2.0, np.float32), np.zeros(4, np.int32)
    assert choose_sample_mode(ones, no_p, no_k, V) == ("greedy", 0)
    assert choose_sample_mode(zeros, no_p, no_k, V) == ("sample", 0)
    mode, cap = choose_sample_mode(zeros, no_p, np.full(4, 20), V)
    assert (mode, cap) == ("topk", 32)
    # top-p or huge k fall back to the full-sort reference head
    assert choose_sample_mode(zeros, np.full(4, 0.9), no_k, V) == ("full", 0)
    assert choose_sample_mode(zeros, no_p, np.full(4, 300), V) == ("full", 0)
    # greedy rows' (disabled) params must not drag a greedy batch off
    # the cheap head
    assert choose_sample_mode(ones, np.full(4, 0.9), np.full(4, 5), V)[0] \
        == "greedy"


# ---------------------------------------------------------------------------
# rope_kv_write prologue: step-level parity, Pallas (interpret) path


def _paged_step_pair(model, cfg, params, kv_quant, C=2):
    """One serve_step_paged dispatch, fused vs unfused, kernels=pallas.
    Returns (logits, cache) pairs plus the scratch page index."""
    ps, NP, P = 8, 4, 6
    cache = model.init_paged_kv_cache(cfg, P, ps, kv_quant=kv_quant)
    R = 2
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, cfg.vocab_size, (R, C)), jnp.int32)
    positions = jnp.asarray(
        [[3 + c for c in range(C)], [6 + c for c in range(C)]], jnp.int32
    )
    lidx = jnp.full((R,), C - 1, jnp.int32)
    pt = jnp.asarray([[0, 1, P, P], [2, 3, P, P]], jnp.int32)
    step = functools.partial(
        model.serve_step_paged, cfg=cfg, cache_len=NP * ps - 1,
        kernels="pallas", kv_quant=kv_quant,
    )
    outs = []
    for fused in (False, True):
        f = jax.jit(functools.partial(step, fused_rope=fused))
        outs.append(f(params, cache, tokens, positions, lidx, None, None, pt))
    return outs, P


@pytest.mark.parametrize("kv_quant", [None, "int8"])
def test_step_fused_rope_parity_llama(tiny, kv_quant):
    cfg, params = tiny
    (unf, fus), scratch = _paged_step_pair(llama, cfg, params, kv_quant)
    assert bool(jnp.all(unf[0] == fus[0])), "logits diverge"
    for name in unf[1]:
        a, b = unf[1][name], fus[1][name]
        if name == "k" and kv_quant is None:
            # The committed K is RoPE's ``x*cos + rot*sin``. The unfused
            # step compiles it in the step's XLA:CPU fusion, the fused
            # one inside the interpreted kernel body, and under jax 0.9
            # LLVM contracts the multiply-add into an FMA in one and not
            # the other: one rounding where the other has two (about 1%
            # of the values, exactly 1 ulp; with
            # XLA_FLAGS=--xla_cpu_max_isa=AVX, which has no FMA, the
            # bytes are identical). Rounding order only, so: 1 ulp.
            np.testing.assert_array_max_ulp(
                np.asarray(a[:, :scratch]), np.asarray(b[:, :scratch]),
                maxulp=1,
            )
            continue
        assert bool(jnp.all(a[:, :scratch] == b[:, :scratch])), (
            f"cache[{name}] non-scratch bytes diverge"
        )


def test_step_fused_rope_parity_generic_decoder():
    """The generic decoder's fused prologue (partial-rotary RoPE path)
    stays bitwise too — the 11 family re-exports all ride on this."""
    cfg = transformer.DecoderConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        norm_type="rmsnorm", norm_bias=False, activation="silu", glu=True,
        rotary_pct=0.5, tie_word_embeddings=True, dtype=jnp.float32,
    )
    params = transformer.init_params(jax.random.PRNGKey(1), cfg)
    (unf, fus), scratch = _paged_step_pair(transformer, cfg, params, None)
    assert bool(jnp.all(unf[0] == fus[0]))
    for name in unf[1]:
        assert bool(jnp.all(unf[1][name][:, :scratch]
                            == fus[1][name][:, :scratch]))


# ---------------------------------------------------------------------------
# engine/scheduler parity: every step that samples on the device (the
# pipelined steps, the one-dispatch sync step), with and without the
# fusion, generates the tokens of the HOST-SIDE head (the two-dispatch
# sync step: engine.run, then RequestManager._sample) through the
# continuous-batching scheduler (mixed prefill+decode steps, greedy +
# per-row top-k rows) with zero steady-state recompiles


class HostHeadManager(RequestManager):
    """The reference: every step goes through the blocking sync path
    and samples with the host-side head (what a manager that keeps a
    second engine in step, SpecInfer, runs)."""

    supports_fast_decode = False
    supports_fused_sampling = False


def _host_head(cfg, params, sc, **kw):
    rm = HostHeadManager(InferenceEngine(llama, cfg, params, sc))
    return _generate(rm, **kw), rm


def test_generation_parity_paged_fusions(tiny):
    cfg, params = tiny
    want, ref = _host_head(cfg, params, _sc(()))
    assert ref.stats.sync_steps == ref.stats.steps  # host-side head only
    for fused in ((), ("rope_kv_write",)):
        rm = RequestManager(
            InferenceEngine(llama, cfg, params, _sc(fused))
        )
        assert _generate(rm) == want, fused
        assert rm.stats.sync_steps == 0 and rm.stats.head_steps > 0
        assert rm.engine.retrace_guard.retraces == 0, fused


@pytest.mark.slow  # interpret-mode Pallas e2e (~8s); the step-level
# int8 fused parity stays in tier-1 (test_step_fused_rope_parity_llama)
# and scripts/premerge.sh runs this file unfiltered
def test_generation_parity_paged_int8_pallas(tiny):
    """The fusion on the quantized pool through the interpret-mode
    Pallas kernels — the in-kernel quantizing commit vs
    quant_line_write, end to end (a nucleus row: the full-sort head)."""
    cfg, params = tiny
    outs = []
    for fused in ((), ("rope_kv_write",)):
        rm = RequestManager(InferenceEngine(
            llama, cfg, params,
            _sc(fused, kernels="pallas", kv_quant="int8", slots=2),
        ))
        outs.append(_generate(rm, n_new=4, gens=GENS_TOPP))
        assert rm.engine.retrace_guard.retraces == 0
    assert outs[0] == outs[1]


def test_dense_sync_sampling_fusion(tiny):
    """Dense pool + the sync scheduler: the step that samples on the
    device must generate the host-side head's tokens while dispatching
    STRICTLY fewer programs per step (one program vs step + host-side
    head). Which of the two a manager runs is its class's
    ``supports_fused_sampling``, no flag."""
    cfg, params = tiny
    want, ref = _host_head(cfg, params, _sc((), layout="dense"))
    rm = RequestManager(InferenceEngine(
        llama, cfg, params, _sc((), layout="dense")
    ))
    rm.supports_fast_decode = False  # force the blocking sync path
    assert _generate(rm) == want
    for m in (rm, ref):
        assert m.engine.retrace_guard.retraces == 0
        assert m.stats.sync_steps == m.stats.steps
    kinds = {k[0] for k in rm.engine._steps if isinstance(k, tuple)}
    assert kinds == {"step_sampled"}, kinds
    assert rm.engine.dispatch_count < ref.engine.dispatch_count, (
        "the step that samples on the device must issue strictly fewer "
        "programs than the two-dispatch one: "
        f"{rm.engine.dispatch_count} vs {ref.engine.dispatch_count}"
    )


def test_tp2_fused_parity(tiny):
    """TP2 mesh: the pipelined steps, fusion off and on, must match the
    single-device host-side head's greedy+top-k generations bit for bit
    (the reference's TP output equality bar,
    python_inference_tests.sh:128)."""
    cfg, params = tiny
    want, _ = _host_head(cfg, params, _sc(()), n_new=4)
    mesh = MachineSpec(model=2).make_mesh(jax.devices()[:2])
    for fused in ((), ("rope_kv_write",)):
        rm = RequestManager(InferenceEngine(
            llama, cfg, params, _sc(fused), mesh=mesh
        ))
        assert _generate(rm, n_new=4) == want, fused
        assert rm.engine.retrace_guard.retraces == 0


def test_fused_decode_validation(tiny):
    cfg, params = tiny
    with pytest.raises(ValueError, match="rope_kv_write"):
        InferenceEngine(
            llama, cfg, params,
            _sc(("rope_kv_write",), layout="dense"),
        )
    with pytest.raises(ValueError, match="unknown fused_decode"):
        InferenceEngine(llama, cfg, params, _sc(("bogus",)))
    # string form normalizes like sanitizers
    eng = InferenceEngine(llama, cfg, params, _sc(" rope_kv_write, "))
    assert eng.serving.fused_decode == ("rope_kv_write",)


@pytest.mark.parametrize("spelling, error, names", [
    (dict(fused_decode=("whole_step",)), ValueError,
     r"'whole_step' \(expected 'rope_kv_write'\)"),
    (dict(fused_decode=("sampling",)), ValueError,
     r"unknown fused_decode entry 'sampling' \(expected 'rope_kv_write'\)"),
    (dict(fused_decode="rope_kv_write,sampling"), ValueError,
     r"unknown fused_decode entry 'sampling'"),
    (dict(quantized_allreduce="int8"), TypeError, "quantized_allreduce"),
])
def test_a_deleted_spelling_is_refused_as_any_unknown_one(
        tiny, spelling, error, names):
    """The whole-step walk and its collectives are gone (PR 31), and so
    is the flag over the sampling head (PR 41: the batch chooses it):
    an old value is an unknown fusion, answered with the one that is
    left, and the old field is no field."""
    cfg, params = tiny
    with pytest.raises(error, match=names):
        InferenceEngine(llama, cfg, params,
                        dataclasses.replace(_sc(()), **spelling))
