"""Quantized paged KV cache (serve/kv_quant.py + the dequant-fused
ragged paged attention in serve/kernels.py): engine-level parity of
dense vs paged vs paged+int8, pool-capacity accounting (a fixed
max_cached_tokens HBM budget must expose ~2x/~4x the pages at
bf16/f32 baselines), prefix-cache hits over quantized pages (splice
reuses the exact int8 codes + scales, so warm must be BITWISE equal to
cold), SpecInfer commit over a quantized pool, and the determinism
guarantees the offset-0 scale reset buys: bitwise run-to-run
generation and bitwise preemption/recompute parity.

Parity tolerance (documented in README "Quantized KV cache"): int8
pages with per-page-per-KV-head amax scales measure a max-abs logit
error of ~0.3% of the logit range on the tiny test model; the asserts
here use 2% of max|logit| — headroom over the measured error, far
below anything that would flip a non-tied argmax.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.models import llama
from flexflow_tpu.serve import (
    InferenceEngine,
    RequestManager,
    ServingConfig,
    SpecConfig,
    SpecInferManager,
)
from flexflow_tpu.serve.batch_config import BatchConfig
from flexflow_tpu.serve.kv_quant import (
    SPECS,
    quantized_pool_pages,
    resolve_spec,
)


@pytest.fixture(scope="module")
def tiny():
    cfg = llama.LLaMAConfig.tiny(dtype=jnp.float32)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def make_engine(tiny, *, kv_layout="paged", slots=4, page_size=16,
                max_seq=64, spec_slack=8, **kw):
    cfg, params = tiny
    sc = ServingConfig(
        max_requests_per_batch=slots,
        max_sequence_length=max_seq,
        prefill_chunk=8,
        max_spec_tree_tokens=spec_slack,
        cache_dtype=jnp.float32,
        kv_layout=kv_layout,
        page_size=page_size,
        **kw,
    )
    return InferenceEngine(llama, cfg, params, sc)


def prompts_for(cfg, n=4):
    return [
        [(i * 7 + j * 3 + 1) % cfg.vocab_size for j in range(4 + i)]
        for i in range(n)
    ]


def generate(eng, prompts, n_new=8):
    return [
        o.output_tokens
        for o in RequestManager(eng).generate(prompts, max_new_tokens=n_new)
    ]


# ---------------------------------------------------------------------------
# layout + accounting


class TestPoolAccounting:
    def test_same_budget_buys_2x_pages(self, tiny):
        """The acceptance bar: at a fixed max_cached_tokens HBM budget
        the int8 allocator exposes >= 1.9x the full-precision pool's
        pages, in ~the same device bytes."""
        fp = make_engine(tiny, max_cached_tokens=256)
        q8 = make_engine(tiny, max_cached_tokens=256, kv_quant="int8")
        ratio = q8.pager.num_pages / fp.pager.num_pages
        # f32 baseline on the CPU test mesh: the ideal ratio is ~4x
        # (int8 vs f32); bf16 serving lands at ~2x. Both clear 1.9.
        assert ratio >= 1.9, ratio
        # same HBM, give or take the scratch page + scale rows
        assert q8.kv_cache_bytes() <= 1.15 * fp.kv_cache_bytes()
        # per-line cost (incl. amortized scales) shrank accordingly
        assert q8.kv_bytes_per_line() <= 0.3 * fp.kv_bytes_per_line()

    def test_quantized_pool_pages_math(self):
        # bf16 -> int8 at real head dims: just under 2x (scale rows)
        pages = quantized_pool_pages(100, 128, 8, 64, 2, SPECS["int8"])
        assert 190 <= pages < 200
        # a budget never shrinks below the fp page count
        assert quantized_pool_pages(3, 8, 2, 4, 1, SPECS["int8"]) >= 3

    def test_cache_pytree_layout(self, tiny):
        eng = make_engine(tiny, kv_quant="int8")
        assert eng.cache["k"].dtype == jnp.int8
        assert eng.cache["v"].dtype == jnp.int8
        P1 = eng.pager.num_pages + 1
        KV = tiny[0].num_key_value_heads
        L = tiny[0].num_hidden_layers
        assert eng.cache["k_scale"].shape == (L, P1, KV)
        assert eng.cache["k_scale"].dtype == jnp.float32

    def test_validation(self, tiny):
        with pytest.raises(ValueError, match="requires kv_layout='paged'"):
            make_engine(tiny, kv_layout="dense", kv_quant="int8")
        with pytest.raises(ValueError, match="unknown kv_quant"):
            make_engine(tiny, kv_quant="fp8")
        assert resolve_spec(None) is None
        assert resolve_spec("int8").qmax == 127.0
        # int4 is live (PR 7): packed nibbles, two codes per byte
        spec4 = resolve_spec("int4")
        assert spec4.qmax == 7.0 and spec4.pack == 2
        # packing needs an even head_dim — loud, at construction (a
        # head that rotates is even by DecoderConfig's own check)
        import dataclasses

        odd = dataclasses.replace(
            tiny[0], hidden_size=60, num_attention_heads=4,
            num_key_value_heads=2, positions="learned",
        )
        assert odd.head_dim % 2 == 1
        with pytest.raises(ValueError, match="head_dim"):
            llama.init_paged_kv_cache(odd, 8, 16, kv_quant="int4")

    def test_int4_same_budget_buys_4x_pages(self, tiny):
        """The int4 rung of the capacity ladder: pages store two codes
        per byte along dk, so a fixed HBM budget exposes ~2x the int8
        pages again (~4x bf16 / ~8x the f32 test baseline)."""
        q8 = make_engine(tiny, max_cached_tokens=256, kv_quant="int8")
        q4 = make_engine(tiny, max_cached_tokens=256, kv_quant="int4")
        assert q4.pager.num_pages / q8.pager.num_pages >= 1.9
        assert q4.cache["k"].dtype == jnp.uint8
        # trailing dim packs two codes per byte
        assert q4.cache["k"].shape[-1] == tiny[0].head_dim // 2
        assert q4.kv_bytes_per_line() <= 0.6 * q8.kv_bytes_per_line()


# ---------------------------------------------------------------------------
# logit parity vs the full-precision layouts


def _mixed_batch_logits(tiny, kv_layout, kv_quant=None):
    """The test_paged_kv.py mixed prefill+decode batch at 64 slots."""
    cfg, params = tiny
    R = 64
    eng = make_engine(tiny, kv_layout=kv_layout, slots=R, page_size=32,
                      max_seq=96, spec_slack=31, kv_quant=kv_quant)
    scratch = eng.scratch_pos
    first, second = range(0, R, 2), range(1, R, 2)
    prompts = {
        r: [(r * 13 + j * 7 + 1) % cfg.vocab_size for j in range(5)]
        for r in range(R)
    }
    if kv_layout == "paged":
        for r in range(R):
            assert eng.pager.ensure(r, 8)
    out = []
    bc = BatchConfig.empty(R, 8, scratch)
    for r in first:
        bc.tokens[r, :5] = prompts[r]
        bc.positions[r, :5] = np.arange(5)
        bc.logits_idx[r] = 4
        bc.active[r] = True
    out.append(np.asarray(jax.device_get(eng.run(bc)))[list(first)])
    bc = BatchConfig.empty(R, 8, scratch)
    for r in first:
        bc.tokens[r, 0] = 7 + r % 5
        bc.positions[r, 0] = 5
        bc.logits_idx[r] = 0
        bc.active[r] = True
    for r in second:
        bc.tokens[r, :5] = prompts[r]
        bc.positions[r, :5] = np.arange(5)
        bc.logits_idx[r] = 4
        bc.active[r] = True
    out.append(np.asarray(jax.device_get(eng.run(bc))))
    return out


class TestLogitParity:
    def test_quantized_close_to_dense_and_paged(self, tiny):
        """dense vs paged vs paged+int8 on the mixed 64-slot batch:
        dense == paged bitwise (unchanged invariant), paged+int8 within
        the documented 2%-of-max|logit| tolerance of both."""
        dense = _mixed_batch_logits(tiny, "dense")
        paged = _mixed_batch_logits(tiny, "paged")
        quant = _mixed_batch_logits(tiny, "paged", kv_quant="int8")
        for d, p, q in zip(dense, paged, quant):
            np.testing.assert_array_equal(d, p)
            tol = 0.02 * np.abs(d).max()
            np.testing.assert_allclose(q, d, atol=tol)

    def test_run_to_run_bitwise_determinism(self, tiny):
        a = _mixed_batch_logits(tiny, "paged", kv_quant="int8")
        b = _mixed_batch_logits(tiny, "paged", kv_quant="int8")
        for x, y in zip(a, b):
            np.testing.assert_array_equal(x, y)


# ---------------------------------------------------------------------------
# end-to-end generation


class TestGenerateQuantized:
    def test_greedy_agreement_and_determinism(self, tiny):
        """Greedy generation over the int8 pool: deterministic bitwise
        across runs, and in near-total agreement with the fp paged
        engine (quant noise ~0.3% of the logit range — argmax flips
        need a near-tie; none occur on this model/seed)."""
        cfg, _ = tiny
        prompts = prompts_for(cfg)
        want = generate(make_engine(tiny), prompts)
        got = generate(make_engine(tiny, kv_quant="int8"), prompts)
        again = generate(make_engine(tiny, kv_quant="int8"), prompts)
        assert got == again  # bitwise run-to-run
        flat_w = [t for o in want for t in o]
        flat_g = [t for o in got for t in o]
        agree = sum(a == b for a, b in zip(flat_w, flat_g)) / len(flat_w)
        assert agree >= 0.75, (want, got)

    def test_preemption_recompute_is_bitwise(self, tiny):
        """The offset-0 scale reset makes quantized page content a pure
        function of the tokens written, never of pool history — so an
        oversubscribed pool that preempts and recomputes must produce
        BITWISE the roomy pool's outputs (exactly the fp invariant)."""
        cfg, _ = tiny
        prompts = prompts_for(cfg)
        want = generate(make_engine(tiny, kv_quant="int8"), prompts, n_new=6)
        rm = RequestManager(
            make_engine(tiny, kv_quant="int8", max_cached_tokens=48)
        )
        got = [
            o.output_tokens
            for o in rm.generate(prompts, max_new_tokens=6)
        ]
        assert got == want
        rm.engine.pager.check_no_leaks()
        assert rm.engine.pager.free_pages == rm.engine.pager.num_pages

    def test_pallas_matches_xla_tokens(self, tiny):
        """kernels='pallas' routes through the dequant-fused ragged
        paged kernel (interpret mode off-TPU) — same greedy tokens as
        the XLA dequant-gather path."""
        cfg, _ = tiny
        prompts = prompts_for(cfg, n=3)
        outs = {
            kern: generate(
                make_engine(tiny, kv_quant="int8", kernels=kern), prompts
            )
            for kern in ("xla", "pallas")
        }
        assert outs["pallas"] == outs["xla"]

    def test_tp2_matches_single_device(self, tiny):
        """Quantized pools shard like fp ones (pages on data, KV heads
        on model — scale rows included): tp2 must reproduce the
        single-device tokens bitwise."""
        from flexflow_tpu.core.mesh import MachineSpec
        from flexflow_tpu.serve.llm import LLM

        cfg, params = tiny
        prompts = [[3, 17, 91, 42, 7], [9, 8, 7, 6, 5]]
        want = generate(make_engine(tiny, kv_quant="int8"), prompts, n_new=6)
        sc = ServingConfig(
            max_requests_per_batch=4, max_sequence_length=64,
            prefill_chunk=8, max_spec_tree_tokens=8,
            cache_dtype=jnp.float32, kv_layout="paged", page_size=16,
            kv_quant="int8",
        )
        mesh = MachineSpec(model=2).make_mesh(jax.devices()[:2])
        m = LLM(llama, cfg, params, mesh=mesh)
        m.compile(sc)
        got = [
            o.output_tokens for o in m.generate(prompts, max_new_tokens=6)
        ]
        assert got == want


# ---------------------------------------------------------------------------
# prefix cache over quantized pages


def test_prefix_cache_hit_over_quantized_pages_is_bitwise(tiny):
    """Splice/COW are dtype-agnostic byte copies: a warm admission
    reuses the EXACT int8 codes + page scales the cold run committed,
    so hit-path outputs must be bitwise the cold outputs — with real
    hits and a mid-page match forcing COW."""
    cfg, _ = tiny
    shared = [(j * 11 + 3) % cfg.vocab_size for j in range(20)]  # 16+4: COW
    prompts = [shared + [i * 7 + 1, i * 3 + 2, 9] for i in range(6)]
    rm = RequestManager(
        make_engine(
            tiny, slots=4, kv_quant="int8", prefix_caching=True,
            max_cached_tokens=512,
        )
    )
    cold = [o.output_tokens for o in rm.generate(prompts, max_new_tokens=6)]
    warm = [o.output_tokens for o in rm.generate(prompts, max_new_tokens=6)]
    assert warm == cold
    assert rm.stats.prefix_hits > 0 and rm.stats.prefix_hit_tokens > 0
    assert rm.stats.prefix_cows > 0  # the 20-token prefix ends mid-page
    rm.engine.pager.check_no_leaks(
        external=rm.prefix_cache.page_refs()
    )


# ---------------------------------------------------------------------------
# SpecInfer commit over a quantized pool


def test_specinfer_commit_over_quantized_pool(tiny):
    """Tree-verify writes quantize at slack lines; commit dequantizes
    the accepted lines at their source page scales and re-commits them
    (models/transformer.commit_kv_paged kv_quant path). Speculative decoding
    stays lossless against the SAME quantized engine's incremental
    decode on this model/seed, and both pools drain clean."""
    cfg, params = tiny
    dcfg = llama.LLaMAConfig.tiny(dtype=jnp.float32, num_hidden_layers=1)
    dparams = {
        "embed": params["embed"],
        "layers": {k: v[:1] for k, v in params["layers"].items()},
        "final_norm_scale": params["final_norm_scale"],
        "lm_head": params["lm_head"],
    }
    prompts = [[3, 17, 91, 42, 7], [9, 8, 7, 6, 5], [42] * 9]
    want = generate(
        make_engine(tiny, kv_quant="int8", spec_slack=16), prompts
    )
    mgr = SpecInferManager(
        make_engine(tiny, kv_quant="int8", spec_slack=16),
        InferenceEngine(
            llama, dcfg, dparams,
            ServingConfig(
                max_requests_per_batch=4, max_sequence_length=64,
                prefill_chunk=8, max_spec_tree_tokens=16,
                cache_dtype=jnp.float32, kv_layout="paged", page_size=16,
            ),
        ),
        SpecConfig(beam_width=2, beam_depth=3),
    )
    got = [
        o.output_tokens for o in mgr.generate(prompts, max_new_tokens=8)
    ]
    assert got == want
    for eng in (mgr.engine, mgr.ssm):
        eng.pager.check_no_leaks()
        assert eng.pager.free_pages == eng.pager.num_pages
