"""Retrace sentinel + donation sanitizer (flexflow_tpu/analysis).

The headline test drives the PR-2 mixed-step pipelined scheduler over
the paged KV cache through admission/eviction/preemption/COW churn at
64 slots and asserts — via RetraceGuard at the engine's jit chokepoint
— exactly ONE compile per step key and zero recompiles thereafter: the
shape/dtype-drift perf-bug class (a weak dtype flipping, a table shape
drifting) caught at test time instead of as a 100x TPU slowdown.

The donation tests reproduce a synthetic use-after-donate — the PR-2
page-corruption bug class — and assert it raises UseAfterDonateError
loudly instead of silently reading donated memory.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.analysis import (
    DonationSanitizer,
    RetraceError,
    RetraceGuard,
    UseAfterDonateError,
)
from flexflow_tpu.analysis.retrace import abstract_signature
from flexflow_tpu.models import llama
from flexflow_tpu.serve import (
    InferenceEngine,
    RequestManager,
    ServingConfig,
)


@pytest.fixture(scope="module")
def tiny():
    cfg = llama.LLaMAConfig.tiny(dtype=jnp.float32)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def churn_engine(tiny, kv_layout, sanitizers, fused=()):
    """64 slots; paged adds a TIGHT pool (preemption under load) plus
    prefix caching (splice/eviction/COW churn). ``paged-q`` is the
    int8-KV variant: the f32 budget is cut to a quarter so the ~3.9x
    page multiplier of the quantized accounting lands the pool at the
    same page count — same churn, quantized pages. ``fused`` switches
    on megakernel decode-step fusions (ServingConfig.fused_decode)."""
    cfg, params = tiny
    kw = {}
    if kv_layout in ("paged", "paged-q"):
        kw.update(
            page_size=8,
            max_cached_tokens=(
                64 * 24 if kv_layout == "paged" else 64 * 6
            ),
            prefix_caching=True,
        )
        if kv_layout == "paged-q":
            kw["kv_quant"] = "int8"
    sc = ServingConfig(
        max_requests_per_batch=64,
        max_sequence_length=48,
        prefill_chunk=8,
        max_tokens_per_step=4,
        max_spec_tree_tokens=8,
        cache_dtype=jnp.float32,
        kv_layout="paged" if kv_layout == "paged-q" else kv_layout,
        sanitizers=sanitizers,
        fused_decode=fused,
        **kw,
    )
    return InferenceEngine(llama, cfg, params, sc)


def churn_prompts(cfg, n=96):
    """8 groups sharing a 12-token prefix (8+4: a prefix-cache match
    ends mid-page, forcing COW on the shared tail page), unique tails
    of varying length."""
    prompts = []
    for i in range(n):
        g = i % 8
        shared = [(g * 17 + j * 5 + 1) % cfg.vocab_size for j in range(12)]
        tail = [
            (i * 37 + j * 11 + 3) % cfg.vocab_size for j in range(3 + i % 7)
        ]
        prompts.append(shared + tail)
    return prompts


def run_churn(rm, prompts, mixed_sampling=False):
    """``mixed_sampling`` gives every 4th request a per-row top-k head
    (the rest stay greedy) so batches oscillate between decode-head
    modes — exactly the churn the mode-tagged step keys must absorb
    without a single retrace."""
    from flexflow_tpu.serve import GenerationConfig

    gens = [
        # topp=2.0 keeps nucleus filtering off so mixed batches land on
        # the bucketed top-k head, not the full-sort fallback
        GenerationConfig(do_sample=True, topk=5, temperature=0.9, topp=2.0)
        if mixed_sampling and i % 4 == 3 else GenerationConfig()
        for i in range(len(prompts))
    ]
    rids = [
        rm.submit(p, g, max_new_tokens=6) for p, g in zip(prompts, gens)
    ]
    while rm.step():
        pass
    rm.drain()
    return [list(rm.requests[r].output_tokens) for r in rids]


# ---------------------------------------------------------------------------
# the churn invariant: one compile per step key, zero recompiles


@pytest.mark.parametrize("kv_layout", ["paged", "paged-q", "dense"])
def test_churn_one_compile_per_step_key(tiny, kv_layout):
    cfg, _ = tiny
    eng = churn_engine(tiny, kv_layout, sanitizers=("retrace", "donation"))
    rm = RequestManager(eng)
    prompts = churn_prompts(cfg, n=96 if kv_layout != "dense" else 80)
    outs = run_churn(rm, prompts)
    assert all(len(o) == 6 for o in outs)

    # the workload actually churned (admission waves beyond 64 slots;
    # paged additionally preempts, splices, COWs and evicts)
    s = rm.stats
    assert s.admitted >= len(prompts)
    if kv_layout != "dense":
        assert s.preemptions > 0, "pool never exhausted — churn too soft"
        assert s.prefix_hits > 0 and s.prefix_cows > 0 and s.prefix_evictions > 0

    guard = eng.retrace_guard
    # exactly one compile per (C,)-keyed step program, zero thereafter
    guard.assert_one_compile_per_key()
    assert guard.retraces == 0
    counts = guard.compile_counts()
    C = eng.serving.mixed_chunk
    # every request is greedy: the argmax head's programs and no other
    assert counts.get(("mixed_fused", C, False, "greedy", 0)) == 1, counts
    assert counts.get(("mixed_fused", 1, False, "greedy", 0)) == 1, counts
    if kv_layout != "dense":
        assert counts.get("copy_page") == 1, counts
        # quantizing the pool adds NO step programs: the quant write and
        # in-kernel dequant live inside the same jitted steps, so the
        # step-key set is identical with kv_quant on and off: the padded
        # mixed step, every packed rung of its ladder, the decode step
        ladder = eng.pack_ladder(C)
        assert ladder, "the paged mixed step has packed rungs"
        assert set(counts) == {
            ("mixed_fused", C, False, "greedy", 0),
            *(("mixed_packed", C, w, "greedy", 0) for w in ladder),
            ("mixed_fused", 1, False, "greedy", 0), "copy_page",
        }, counts
    # the build log counts at the same chokepoint (obs/builds.py)
    assert s.compiles == guard.total_compiles
    assert s.retraces == 0
    # donated dispatches were poisoned throughout
    assert eng.donation_sanitizer.n_poisoned > 0


def test_churn_fused_decode_zero_retraces(tiny):
    """The fused decode step under the headline churn workload: the
    fusion on (fused_decode=("rope_kv_write",)) over the tight paged
    pool with prefix caching — preemption, splice/COW and eviction all
    exercised, with every 4th request on a top-k decode head so the
    mode-tagged step keys churn too (the batch chooses its head: no
    flag). The bar is the same as unfused: one compile per step key
    (the mode-tagged keys each count once), ZERO steady-state
    retraces, a return to a head seen before compiles nothing, and
    sanitizers-on == sanitizers-off generations bitwise."""
    cfg, _ = tiny
    fused = ("rope_kv_write",)
    eng = churn_engine(
        tiny, "paged", ("retrace", "donation"), fused=fused
    )
    rm = RequestManager(eng)
    # > 64 prompts: a second admission wave (prefix hits) + pool
    # pressure (preemptions) — the same churn bar the unfused headline
    # test sets
    prompts = churn_prompts(cfg, n=80)
    outs = run_churn(rm, prompts, mixed_sampling=True)
    assert all(len(o) == 6 for o in outs)

    s = rm.stats
    assert s.preemptions > 0, "pool never exhausted — churn too soft"
    assert s.prefix_hits > 0 and s.prefix_evictions > 0

    # with a top-k row resident in some slot at every step, every
    # batch lands on the bucketed "topk" head (topk=5 → cap 8); a
    # greedy-only TAIL on the same (already-sealed-by-churn) engine
    # then compiles the "greedy" head keys exactly once each
    tail = [rm.submit(p, max_new_tokens=6) for p in churn_prompts(cfg, n=8)]
    while rm.step():
        pass
    rm.drain()
    assert all(len(rm.requests[r].output_tokens) == 6 for r in tail)

    guard = eng.retrace_guard
    compiled = guard.total_compiles
    # ... and a return to a head seen before (top-k rows again, then
    # the greedy ones that outlive them) compiles nothing
    outs_back = run_churn(rm, churn_prompts(cfg, n=8), mixed_sampling=True)
    assert all(len(o) == 6 for o in outs_back)
    assert guard.total_compiles == compiled
    assert s.head_steps == s.mixed_steps + s.decode_steps
    assert 0 < s.head_greedy_steps < s.head_steps
    guard.assert_one_compile_per_key()
    assert guard.retraces == 0
    counts = guard.compile_counts()
    # the mixed-step keys are sampling-mode-tagged; the workload uses
    # exactly two head modes (bucketed top-k batches, then the
    # greedy-only tail), each compiled once per chunk width
    C = eng.serving.mixed_chunk
    modes = {k[3] for k in counts if k[0] == "mixed_fused"}
    assert modes == {"greedy", "topk"}, counts
    assert all(v == 1 for v in counts.values()), counts
    assert counts.get(("mixed_fused", C, False, "topk", 8)) == 1, counts
    assert counts.get(("mixed_fused", C, False, "greedy", 0)) == 1, counts
    assert eng.donation_sanitizer.n_poisoned > 0

    # sanitizers are pure observers on the fused path too
    outs_off = run_churn(
        RequestManager(churn_engine(tiny, "paged", (), fused=fused)),
        prompts, mixed_sampling=True,
    )
    assert outs == outs_off


@pytest.mark.parametrize("kv_layout", ["paged", "paged-q"])
def test_sanitizers_do_not_change_outputs(tiny, kv_layout):
    """Guard + sanitizer are observers: bitwise-identical generations
    with and without them (quantized pool included — the sanitizers
    must not perturb the in-step quantization either)."""
    cfg, _ = tiny
    prompts = churn_prompts(cfg, n=40)
    outs_on = run_churn(
        RequestManager(
            churn_engine(tiny, kv_layout, sanitizers=("retrace", "donation"))
        ),
        prompts,
    )
    outs_off = run_churn(
        RequestManager(churn_engine(tiny, kv_layout, sanitizers=())),
        prompts,
    )
    assert outs_on == outs_off


@pytest.mark.slow  # ~20s; premerge gate 3/7 runs this file unfiltered
def test_adaptive_spec_one_program_per_bucket(tiny):
    """Adaptive speculation churn: per-request tree resizing compiles
    exactly ONE speculate program per W×D bucket visited and one
    tree-verify step per bucket chunk — the BUCKETED ladder, never
    free-form shapes — with zero retraces, nothing new compiling on a
    repeat of the identical workload (steady state), and
    sanitizers-on == sanitizers-off generations bitwise."""
    from flexflow_tpu.serve import SpecConfig, SpecInferManager

    cfg, params = tiny
    dcfg = llama.LLaMAConfig.tiny(dtype=jnp.float32, num_hidden_layers=1)
    dparams = dict(params)
    dparams["layers"] = {k: v[:1] for k, v in params["layers"].items()}
    prompts = [[3, 17, 91, 42, 7], [9, 8, 7], [42] * 9, [5, 9, 2, 11]]

    def build(sans):
        def sc():
            return ServingConfig(
                max_requests_per_batch=4, max_sequence_length=96,
                prefill_chunk=8, max_spec_tree_tokens=16,
                cache_dtype=jnp.float32, kv_layout="paged", page_size=16,
                sanitizers=sans,
            )

        return SpecInferManager(
            InferenceEngine(llama, cfg, params, sc()),
            InferenceEngine(llama, dcfg, dparams, sc()),
            SpecConfig(2, 4, adaptive=True),
        )

    mgr = build(("retrace", "donation"))
    first = [
        o.output_tokens for o in mgr.generate(prompts, max_new_tokens=16)
    ]
    assert mgr.stats.spec_resizes > 0, "no resize churn exercised"

    ladder = set(mgr.spec.bucket_ladder)
    llm_g, ssm_g = mgr.engine.retrace_guard, mgr.ssm.retrace_guard
    # the draft engine compiled one speculate program per bucket VISITED
    spec_counts = {
        k: v for k, v in ssm_g.compile_counts().items()
        if isinstance(k, tuple) and k and k[0] == "speculate"
    }
    visited = {(k[1], k[2]) for k in spec_counts}
    assert visited <= ladder, (visited, ladder)
    assert len(visited) >= 2, "resize churn never changed the bucket"
    assert all(v == 1 for v in spec_counts.values()), spec_counts
    # the verifier compiled one tree-verify step per bucket chunk
    verify_counts = {
        k: v for k, v in llm_g.compile_counts().items()
        if isinstance(k, tuple) and len(k) == 3 and k[1] is True
    }
    assert {k[0] for k in verify_counts} <= {
        1 + w * d for w, d in ladder
    }, verify_counts
    assert all(v == 1 for v in verify_counts.values()), verify_counts
    assert llm_g.retraces == 0 and ssm_g.retraces == 0

    # steady state: fresh requests repeat the controller trajectory —
    # the identical workload may compile NOTHING new
    total = llm_g.total_compiles + ssm_g.total_compiles
    again = [
        o.output_tokens for o in mgr.generate(prompts, max_new_tokens=16)
    ]
    assert again == first
    assert llm_g.total_compiles + ssm_g.total_compiles == total

    # sanitizers are observers: bitwise-identical without them
    outs_off = [
        o.output_tokens
        for o in build(()).generate(prompts, max_new_tokens=16)
    ]
    assert outs_off == first


@pytest.mark.slow  # ~30s; premerge gate 3/7 runs this file unfiltered
def test_verify_skip_flapping_bounded_step_keys(tiny):
    """Verify-skip churn: a dead-cold draft flaps between skipped
    rounds, cadenced re-probes and (1,1) spec rounds. The whole regime
    must compile a BOUNDED step-key set — the ladder's speculate
    programs, the decode/verify chunks, and one prefill-shaped SSM
    replay program for the lag repayment — with zero retraces, nothing
    new on a repeat of the identical workload, and sanitizers-on ==
    sanitizers-off == plain incremental greedy bitwise."""
    from flexflow_tpu.serve import SpecConfig, SpecInferManager

    cfg, params = tiny
    # UNRELATED random init: nothing it drafts survives verification,
    # so every request bottoms out on the skip arm
    dcfg = llama.LLaMAConfig.tiny(dtype=jnp.float32, num_hidden_layers=1)
    dparams = llama.init_params(jax.random.PRNGKey(7), dcfg)
    prompts = [[3, 17, 91, 42, 7], [9, 8, 7], [42] * 9, [5, 9, 2, 11]]

    def sc(sans):
        return ServingConfig(
            max_requests_per_batch=4, max_sequence_length=96,
            prefill_chunk=8, max_spec_tree_tokens=16,
            cache_dtype=jnp.float32, kv_layout="paged", page_size=16,
            sanitizers=sans,
        )

    def build(sans):
        return SpecInferManager(
            InferenceEngine(llama, cfg, params, sc(sans)),
            InferenceEngine(llama, dcfg, dparams, sc(sans)),
            SpecConfig(2, 3, adaptive=True, verify_skip=True,
                       skip_threshold=0.1, reprobe_every=3),
        )

    ref = [
        o.output_tokens
        for o in RequestManager(
            InferenceEngine(llama, cfg, params, sc(()))
        ).generate(prompts, max_new_tokens=24)
    ]

    mgr = build(("retrace", "donation"))
    first = [
        o.output_tokens for o in mgr.generate(prompts, max_new_tokens=24)
    ]
    assert first == ref
    assert mgr.stats.verify_skipped_rounds > 0, "skip arm never taken"
    assert mgr.stats.spec_reprobes > 0, "re-probe cadence never came due"
    assert mgr._ssm_lag == {}, "SSM cache debt left unpaid"

    ladder = set(mgr.spec.bucket_ladder)
    llm_g, ssm_g = mgr.engine.retrace_guard, mgr.ssm.retrace_guard
    # draft engine: speculate programs stay on the ladder, and the only
    # other shape is the bounded lag-replay step (prefill-chunk sized)
    spec_counts = {
        k: v for k, v in ssm_g.compile_counts().items()
        if isinstance(k, tuple) and k and k[0] == "speculate"
    }
    visited = {(k[1], k[2]) for k in spec_counts}
    assert visited <= ladder, (visited, ladder)
    assert all(v == 1 for v in spec_counts.values()), spec_counts
    assert all(
        v == 1 for v in ssm_g.compile_counts().values()
    ), ssm_g.compile_counts()
    assert all(
        v == 1 for v in llm_g.compile_counts().values()
    ), llm_g.compile_counts()
    assert llm_g.retraces == 0 and ssm_g.retraces == 0

    # steady state: the identical workload flaps identically and may
    # compile NOTHING new
    total = llm_g.total_compiles + ssm_g.total_compiles
    again = [
        o.output_tokens for o in mgr.generate(prompts, max_new_tokens=24)
    ]
    assert again == first
    assert llm_g.total_compiles + ssm_g.total_compiles == total

    outs_off = [
        o.output_tokens
        for o in build(()).generate(prompts, max_new_tokens=24)
    ]
    assert outs_off == first


# ---------------------------------------------------------------------------
# RetraceGuard unit behavior


def test_retrace_guard_raises_on_signature_drift():
    guard = RetraceGuard(strict=True)
    f = jax.jit(guard.instrument(lambda x: x * 2, key="step"))
    f(jnp.zeros((4,), jnp.float32))
    f(jnp.ones((4,), jnp.float32))  # same signature: cached, no trace
    assert guard.compile_counts() == {"step": 1}
    with pytest.raises(RetraceError, match="RECOMPILED"):
        f(jnp.zeros((8,), jnp.float32))  # shape drift


def test_retrace_guard_catches_weak_dtype_flip():
    """THE engine.py:568 bug class: the same step key fed a strongly
    typed np.int32 array one step and a weak Python scalar the next —
    jax quietly recompiles; the guard does not."""
    guard = RetraceGuard(strict=True)
    f = jax.jit(guard.instrument(lambda x: x + 1, key="step"))
    f(jnp.asarray(np.zeros((2,), np.int32), dtype=jnp.int32))
    with pytest.raises(RetraceError, match="RECOMPILED"):
        f(jnp.asarray(0))  # weak-typed scalar: new abstract signature
    sigs = guard.compiles["step"]
    assert sigs[0] != sigs[1]


def test_retrace_guard_warn_mode_records_without_raising():
    guard = RetraceGuard(strict=False)
    f = jax.jit(guard.instrument(lambda x: x * 2, key="k"))
    f(jnp.zeros((2,)))
    f(jnp.zeros((3,)))
    assert guard.retraces == 1
    assert guard.compile_counts() == {"k": 2}
    with pytest.raises(RetraceError):
        guard.assert_one_compile_per_key()


def test_retrace_guard_seal_forbids_new_keys():
    guard = RetraceGuard(strict=True)
    f = jax.jit(guard.instrument(lambda x: x, key="a"))
    f(jnp.zeros((2,)))
    guard.seal()
    f(jnp.zeros((2,)))  # cached replay: fine
    g = jax.jit(guard.instrument(lambda x: x, key="b"))
    with pytest.raises(RetraceError, match="NEW step key"):
        g(jnp.zeros((2,)))
    guard.unseal()
    g(jnp.zeros((2,)))


def test_abstract_signature_distinguishes_weak_types():
    strong = abstract_signature((jnp.asarray(1, dtype=jnp.int32),), {})
    weak = abstract_signature((jnp.asarray(1),), {})
    assert strong != weak


def test_engine_retrace_guard_survives_reset(tiny):
    eng = churn_engine(tiny, "dense", sanitizers=("retrace",))
    rm = RequestManager(eng)
    run_churn(rm, churn_prompts(tiny[0], n=4))
    eng.retrace_guard.reset()
    assert eng.retrace_guard.compile_counts() == {}


# ---------------------------------------------------------------------------
# donation sanitizer


def test_donation_sanitizer_synthetic_use_after_donate():
    san = DonationSanitizer()
    f = jax.jit(lambda c, x: {"k": c["k"] + x}, donate_argnums=(0,))
    cache = {"k": jnp.ones((4,), jnp.float32)}
    out = f(cache, 1.0)
    san.poison(cache, context="synthetic step")
    with pytest.raises(UseAfterDonateError, match="use-after-donate"):
        _ = cache["k"].shape
    with pytest.raises(UseAfterDonateError):
        _ = cache["k"] + 1
    with pytest.raises(UseAfterDonateError):
        np.asarray(cache["k"])
    # the NEW cache is untouched
    assert float(out["k"][0]) == 2.0
    assert san.n_poisoned == 1


def test_donation_proxy_repr_is_safe():
    san = DonationSanitizer()
    cache = {"k": jnp.ones((2,))}
    cache["k"].delete()
    san.poison(cache, context="ctx")
    assert "DeletedBufferProxy" in repr(cache["k"])
    # poisoning again is idempotent
    san.poison(cache, context="ctx2")


def test_engine_use_after_donate_raises(tiny):
    """The deliberately injected PR-2 bug: hold the cache pytree across
    a donating dispatch, then read it."""
    eng = churn_engine(tiny, "paged", sanitizers=("donation",))
    rm = RequestManager(eng)
    stale = eng.cache  # e.g. a debug probe holding the "current" cache
    run_churn(rm, churn_prompts(tiny[0], n=4))
    with pytest.raises(UseAfterDonateError, match="donated to engine step"):
        _ = stale["k"].shape
    # the engine's own (current) cache is healthy
    assert eng.kv_cache_bytes() > 0


def test_engine_without_sanitizer_keeps_plain_jit(tiny):
    eng = churn_engine(tiny, "dense", sanitizers=())
    assert eng.retrace_guard is None and eng.donation_sanitizer is None


def test_sanitizers_string_form_and_validation(tiny):
    cfg, params = tiny
    sc = ServingConfig(
        max_requests_per_batch=2, max_sequence_length=32,
        prefill_chunk=8, max_spec_tree_tokens=8,
        cache_dtype=jnp.float32, sanitizers="retrace-warn,donation",
    )
    eng = InferenceEngine(llama, cfg, params, sc)
    assert eng.retrace_guard is not None and not eng.retrace_guard.strict
    assert eng.donation_sanitizer is not None
    with pytest.raises(ValueError, match="unknown sanitizer"):
        InferenceEngine(
            llama, cfg, params,
            ServingConfig(sanitizers=("bogus",)),
        )
