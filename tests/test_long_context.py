"""Context-parallel long-context serving (ServingConfig.kv_shard=
"context", ROADMAP item 5a): ring ragged paged attention over
sequence-sharded KV page pools.

Contracts under test:
  * PageAllocator cp_shards partition: striped logical→shard ownership,
    per-shard free lists, all-or-nothing ensure across shards, COW/
    splice on the owning shard, per-shard no-leak audit.
  * Admission goes per-shard: a prompt strictly larger than ONE shard's
    pool serves under CP (and is a terminal ERROR without it), and its
    greedy output is BITWISE the single-shard run of a servable
    configuration — on this box CP attention is the table-gather XLA
    fallback, which is bit-for-bit the CP-off math regardless of which
    shard's row slice a page lives in (serve/kernels.py). fp and int8
    pools are asserted bitwise; int4 runs at its documented tolerance
    (PR 7: 16x coarser grid) plus run-to-run bitwise.
  * Chunked prefill streams across shard boundaries (striped pages fill
    evenly), preemption/recompute and host-tier spill→re-admit keep
    their bitwise contracts with the striped layout.
  * kernels.ring_ragged_paged_attention (the shard_map ppermute
    program on a seq>1 mesh) matches the XLA reference within f32
    reassociation tolerance, and the ENGINE on a real seq=2 mesh
    agrees greedily with the single-device run.
  * Retrace guard: CP churn compiles one program per step key, zero
    steady-state recompiles.
  * The fused RoPE + KV-write prologue (fused_decode="rope_kv_write")
    joins the ring body bitwise on full-precision pools; the quantized
    ring commit stays excluded by name.

Wired as premerge gate 8 (scripts/premerge.sh).
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding

from flexflow_tpu.core.mesh import MachineSpec, set_mesh
from flexflow_tpu.models import llama
from flexflow_tpu.serve import (
    InferenceEngine,
    PageAllocator,
    RequestManager,
    ServingConfig,
)
from flexflow_tpu.serve import kernels as K


@pytest.fixture(scope="module")
def tiny():
    cfg = llama.LLaMAConfig.tiny(dtype=jnp.float32)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def make_rm(tiny, *, slots=2, max_seq=96, page_size=8, prefill_chunk=8,
            mesh=None, **kw):
    cfg, params = tiny
    sc = ServingConfig(
        max_requests_per_batch=slots,
        max_sequence_length=max_seq,
        prefill_chunk=prefill_chunk,
        max_spec_tree_tokens=8,
        cache_dtype=jnp.float32,
        kv_layout="paged",
        page_size=page_size,
        **kw,
    )
    return RequestManager(InferenceEngine(llama, cfg, params, sc, mesh=mesh))


def prompt_of(cfg, n, seed=3):
    return [(seed + 7 * j) % cfg.vocab_size for j in range(n)]


# ---------------------------------------------------------------------------
# allocator: striped partition invariants


class TestCpAllocator:
    def test_striped_ensure_and_audit(self):
        pa = PageAllocator(12, 8, 2, 16, cp_shards=3)
        assert pa.pages_per_shard == 4
        assert pa.ensure(0, 5 * 16)  # 5 logical pages -> shards 0,1,2,0,1
        assert pa.used_pages_by_shard() == [2, 2, 1]
        for j in range(5):
            assert pa.shard_of_page(int(pa.table[0][j])) == j % 3
        pa.check_no_leaks()

    def test_ensure_all_or_nothing_on_shard_exhaustion(self):
        # shard 0 runs dry while others have room: nothing allocates
        pa = PageAllocator(6, 6, 2, 16, cp_shards=3)  # 2 pages/shard
        assert pa.ensure(0, 5 * 16)  # shards get 2,2,1 — shard 0 full
        before = pa.table.copy()
        free_before = pa.free_pages_by_shard()
        # slot 1 needs 4 pages -> 2 on shard 0, but shard 0 has 0 free
        assert not pa.ensure(1, 4 * 16)
        np.testing.assert_array_equal(pa.table, before)
        assert pa.free_pages_by_shard() == free_before
        pa.check_no_leaks()

    def test_release_returns_pages_to_owning_shard(self):
        pa = PageAllocator(12, 8, 2, 16, cp_shards=3)
        pa.ensure(0, 7 * 16)
        pa.release(0)
        assert pa.free_pages_by_shard() == [4, 4, 4]
        pa.check_no_leaks()

    def test_cow_draws_from_owning_shard(self):
        pa = PageAllocator(12, 8, 2, 16, cp_shards=3)
        pa.ensure(0, 4 * 16)
        old = int(pa.table[0][1])  # logical 1 -> shard 1
        fresh = pa.cow(0, 1)
        assert fresh is not None and pa.shard_of_page(fresh) == 1
        assert int(pa.table[0][1]) == fresh and fresh != old
        pa.check_no_leaks()

    def test_splice_asserts_striping(self):
        pa = PageAllocator(12, 8, 2, 16, cp_shards=3)
        pa.ensure(0, 2 * 16)
        good = [int(pa.table[0][0]), int(pa.table[0][1])]
        pa.release(0)
        pa.splice(0, good)  # original striped order: fine
        pa.release(0)
        with pytest.raises(AssertionError, match="striping"):
            pa.splice(0, list(reversed(good)))

    def test_shard_balance_gauge(self):
        pa = PageAllocator(12, 8, 2, 16, cp_shards=3)
        assert pa.shard_balance() == 1.0
        pa.ensure(0, 4 * 16)  # 2,1,1
        assert pa.shard_balance() == 0.5
        pa.ensure(0, 6 * 16)  # 2,2,2
        assert pa.shard_balance() == 1.0

    def test_can_ever_fit_is_per_shard(self):
        pa = PageAllocator(12, 8, 2, 16, cp_shards=3)
        assert pa.can_ever_fit(12 * 16)      # 4 per shard — exactly fits
        assert not pa.can_ever_fit(13 * 16)  # shard 0 would need 5

    def test_indivisible_pool_rejected(self):
        with pytest.raises(ValueError, match="divisible"):
            PageAllocator(10, 4, 2, 16, cp_shards=3)


# ---------------------------------------------------------------------------
# validation (satellite: loud kv_shard="context" checks)


class TestValidation:
    def test_context_requires_paged(self, tiny):
        cfg, params = tiny
        sc = ServingConfig(kv_layout="dense", kv_shard="context",
                           context_shards=2)
        with pytest.raises(ValueError, match="paged"):
            InferenceEngine(llama, cfg, params, sc)

    def test_context_needs_degree(self, tiny):
        cfg, params = tiny
        sc = ServingConfig(kv_layout="paged", kv_shard="context")
        with pytest.raises(ValueError, match="at least 2 shards"):
            InferenceEngine(llama, cfg, params, sc)

    def test_degree_must_match_mesh(self, tiny):
        cfg, params = tiny
        mesh = MachineSpec(seq=2).make_mesh(jax.devices()[:2])
        sc = ServingConfig(kv_layout="paged", kv_shard="context",
                           context_shards=4)
        with pytest.raises(ValueError, match="seq-axis"):
            InferenceEngine(llama, cfg, params, sc, mesh=mesh)

    def test_shards_without_kv_shard_rejected(self):
        with pytest.raises(ValueError, match="no effect"):
            ServingConfig(context_shards=4).validate_long_context()

    def test_unknown_kv_shard(self):
        with pytest.raises(ValueError, match="kv_shard"):
            ServingConfig(kv_shard="sequence").validate_long_context()

    def test_per_shard_budget_needs_one_page(self):
        sc = ServingConfig(kv_layout="paged", kv_shard="context",
                           context_shards=2, page_size=128,
                           max_cached_tokens=64)
        with pytest.raises(ValueError, match="PER SHARD"):
            sc.validate_long_context()

    def test_ring_gqa_error_names_fixes(self):
        # satellite: the ring_attention GQA divisibility error must name
        # the actual remedies (repeat KV heads / lower the degree /
        # drop head sharding), not just restate the constraint
        from flexflow_tpu.parallel.sequence import ring_attention

        mesh = MachineSpec(seq=2, model=4).make_mesh(jax.devices()[:8])
        q = jnp.zeros((1, 8, 8, 4), jnp.float32)
        kv = jnp.zeros((1, 8, 2, 4), jnp.float32)  # 2 KV heads vs model=4
        with pytest.raises(ValueError) as ei:
            ring_attention(q, kv, kv, mesh)
        msg = str(ei.value)
        assert "repeat" in msg and "lower" in msg and "shard_heads" in msg


# ---------------------------------------------------------------------------
# the headline contract: a prompt strictly larger than one shard's pool
# serves under CP, bitwise the single-shard run


class TestLongContextServing:
    # per-shard budget 40 tokens (5 pages of 8); prompt 72 tokens needs
    # 9 pages > 5 — unservable on one shard, servable striped over 3
    PER_SHARD = 40
    SHARDS = 3
    PROMPT_LEN = 72

    def _outputs(self, tiny, kv_quant, **kw):
        cfg, _ = tiny
        rm = make_rm(tiny, kv_quant=kv_quant, **kw)
        outs = rm.generate([prompt_of(cfg, self.PROMPT_LEN)],
                           max_new_tokens=12)
        rm.drain()
        return rm, outs[0]

    @pytest.mark.parametrize("kv_quant", [None, "int8"])
    def test_cp_serves_beyond_one_shard_bitwise(self, tiny, kv_quant):
        _, ref = self._outputs(tiny, kv_quant, max_cached_tokens=200)
        assert ref.error is None
        rm, out = self._outputs(
            tiny, kv_quant, max_cached_tokens=self.PER_SHARD,
            kv_shard="context", context_shards=self.SHARDS,
        )
        assert out.error is None
        assert out.output_tokens == ref.output_tokens, (
            "CP-on greedy output diverged from the single-shard run — "
            "the XLA table gather must be bitwise layout-blind"
        )
        assert out.profile.context_shards == self.SHARDS
        rm.engine.pager.check_no_leaks()

    @pytest.mark.slow
    def test_cp_int4_tolerance(self, tiny):
        # int4's 16x-coarser grid: run-to-run bitwise + the documented
        # >=0.6 greedy agreement vs the single-shard run (PR-7 bars)
        _, ref = self._outputs(tiny, "int4", max_cached_tokens=200)
        rm, out1 = self._outputs(
            tiny, "int4", max_cached_tokens=self.PER_SHARD,
            kv_shard="context", context_shards=self.SHARDS,
        )
        _, out2 = self._outputs(
            tiny, "int4", max_cached_tokens=self.PER_SHARD,
            kv_shard="context", context_shards=self.SHARDS,
        )
        assert out1.error is None and out1.output_tokens == out2.output_tokens
        agree = np.mean([
            a == b for a, b in zip(out1.output_tokens, ref.output_tokens)
        ])
        assert agree >= 0.6, f"int4 CP greedy agreement {agree}"

    def test_unservable_without_cp_is_terminal_error(self, tiny):
        cfg, _ = tiny
        rm = make_rm(tiny, max_cached_tokens=self.PER_SHARD)
        out = rm.generate([prompt_of(cfg, self.PROMPT_LEN)],
                          max_new_tokens=12)[0]
        assert out.error is not None and "max_cached_tokens" in out.error

    def test_prompt_beyond_aggregate_is_terminal_error(self, tiny):
        cfg, _ = tiny
        rm = make_rm(tiny, max_cached_tokens=16, kv_shard="context",
                     context_shards=2)
        out = rm.generate([prompt_of(cfg, 72)], max_new_tokens=4)[0]
        assert out.error is not None
        assert "shard" in out.error

    def test_chunked_prefill_crosses_shard_boundaries(self, tiny):
        cfg, _ = tiny
        # chunk (8) < page_size (16): several dispatches per page, pages
        # striped over shards as the prompt streams in
        ref = make_rm(tiny, page_size=16, max_cached_tokens=400)
        r_out = ref.generate([prompt_of(cfg, 70)], max_new_tokens=8)[0]
        rm = make_rm(tiny, page_size=16, max_cached_tokens=64,
                     kv_shard="context", context_shards=2)
        rid = rm.submit(prompt_of(cfg, 70), max_new_tokens=8)
        peak = [0, 0]
        while rm.requests[rid].status.value not in ("completed", "error"):
            rm.step()
            used = rm.engine.pager.used_pages_by_shard()
            peak = [max(a, b) for a, b in zip(peak, used)]
        rm.drain()
        out = rm.result(rid)
        assert out.error is None
        assert out.output_tokens == r_out.output_tokens
        # 70 tokens = 5 pages of 16 -> striped 3/2: both shards filled
        assert peak[0] >= 3 and peak[1] >= 2, peak
        rm.engine.pager.check_no_leaks()

    def test_preemption_recompute_parity_under_cp(self, tiny):
        cfg, _ = tiny
        prompts = [prompt_of(cfg, 40, seed=3), prompt_of(cfg, 40, seed=11)]
        ref = make_rm(tiny, max_cached_tokens=400)
        ref_outs = [o.output_tokens
                    for o in ref.generate(prompts, max_new_tokens=16)]
        # tight striped pool: 2 concurrent requests force preemption
        rm = make_rm(tiny, max_cached_tokens=40, kv_shard="context",
                     context_shards=2)
        outs = rm.generate(prompts, max_new_tokens=16)
        assert [o.error for o in outs] == [None, None]
        assert [o.output_tokens for o in outs] == ref_outs
        assert rm.stats.preemptions > 0, (
            "pool was not tight enough to exercise CP preemption"
        )
        rm.engine.pager.check_no_leaks()

    def test_spill_readmit_under_cp_is_bitwise_warm(self, tiny):
        cfg, _ = tiny
        # page-aligned prompt so warm matches land aligned; host tier
        # on; max_seq sized so the allocator clamp (one slot's striped
        # worst case) leaves the pool tight enough that the filler run
        # must reclaim the cached prefix
        prompt = prompt_of(cfg, 32)
        kw = dict(
            max_seq=56, max_cached_tokens=40, kv_shard="context",
            context_shards=2, prefix_caching=True,
            cache_policy="prefill", host_cache_bytes=1 << 24,
        )
        rm = make_rm(tiny, **kw)
        cold = rm.generate([prompt], max_new_tokens=8)[0]
        # pressure the pool so the cached prefix SPILLS per-shard
        filler = prompt_of(cfg, 48, seed=91)
        rm.generate([filler], max_new_tokens=8)
        assert rm.stats.spills > 0, "no spill under pressure"
        # the same prompt re-admits from the host tier
        warm = rm.generate([prompt], max_new_tokens=8)[0]
        assert rm.stats.readmits > 0, "match did not re-admit"
        assert warm.output_tokens == cold.output_tokens
        # re-admitted pages landed back on their striped shards
        rm.drain()
        rm.engine.pager.check_no_leaks(
            external=rm.prefix_cache.page_refs()
        )

    def test_cp_stats_and_profile(self, tiny):
        cfg, _ = tiny
        rm = make_rm(tiny, max_cached_tokens=self.PER_SHARD,
                     kv_shard="context", context_shards=self.SHARDS)
        out = rm.generate([prompt_of(cfg, 60)], max_new_tokens=6)[0]
        assert out.error is None
        s = rm.stats.snapshot()
        assert s["cp_shards"] == self.SHARDS
        assert s["ring_steps"] >= (self.SHARDS - 1)
        assert 0.0 < s["shard_balance"] <= 1.0
        assert out.profile.context_shards == self.SHARDS


# ---------------------------------------------------------------------------
# ring kernel (shard_map ppermute program on a real seq mesh)


def _ring_problem(seed, quant=False):
    rng = np.random.default_rng(seed)
    R, C, H, KV, dk, ps, NP, shards = 3, 2, 4, 2, 8, 4, 6, 2
    rows = 12  # 2 shards x 6 rows
    q = jnp.asarray(rng.normal(size=(R, C, H, dk)), jnp.float32)
    if quant:
        kp = jnp.asarray(rng.integers(-127, 128, (rows, ps, KV, dk)),
                         jnp.int8)
        vp = jnp.asarray(rng.integers(-127, 128, (rows, ps, KV, dk)),
                         jnp.int8)
        ks = jnp.asarray(rng.uniform(0.01, 0.1, (rows, KV)), jnp.float32)
        vs = jnp.asarray(rng.uniform(0.01, 0.1, (rows, KV)), jnp.float32)
    else:
        kp = jnp.asarray(rng.normal(size=(rows, ps, KV, dk)), jnp.float32)
        vp = jnp.asarray(rng.normal(size=(rows, ps, KV, dk)), jnp.float32)
        ks = vs = None
    pt = np.zeros((R, NP), np.int32)
    for r in range(R):
        for j in range(NP):
            # striped: logical j on shard j%2, some rows reused across
            # requests (shared prefix pages)
            pt[r, j] = (j % 2) * 6 + ((j // 2 + r) % 6)
    mask = rng.random((R, C, NP * ps)) > 0.3
    mask[0, :, :] = False  # one fully-masked row exercises the guards
    return q, kp, vp, jnp.asarray(pt), jnp.asarray(mask), ks, vs


class TestRingKernel:
    @pytest.mark.parametrize("quant", [False, True])
    def test_ring_matches_xla_reference(self, quant):
        mesh = MachineSpec(seq=2).make_mesh(jax.devices()[:2])
        q, kp, vp, pt, mask, ks, vs = _ring_problem(0, quant)
        ref = K.ring_ragged_paged_attention_xla(
            q, kp, vp, pt, mask, k_scale=ks, v_scale=vs, cp_shards=2
        )
        out = K.ring_ragged_paged_attention(
            q, kp, vp, pt, mask, mesh, k_scale=ks, v_scale=vs
        )
        # request 0 is FULLY masked: its output is padding no caller
        # ever reads (the ring yields exact zeros, the reference's
        # softmax-over--inf yields uniform garbage) — assert it is
        # finite and compare only the live rows
        assert np.isfinite(np.asarray(out[0])).all()
        np.testing.assert_allclose(
            np.asarray(out[1:]), np.asarray(ref[1:]), rtol=3e-5, atol=3e-5
        )

    def test_xla_fallback_is_bitwise_plain(self):
        q, kp, vp, pt, mask, _, _ = _ring_problem(1)
        a = K.ring_ragged_paged_attention_xla(q, kp, vp, pt, mask,
                                              cp_shards=2)
        b = K.ragged_paged_attention_xla(q, kp, vp, pt, mask)
        assert (np.asarray(a) == np.asarray(b)).all()

    def test_ring_rejects_misaligned_rows(self):
        mesh = MachineSpec(seq=2).make_mesh(jax.devices()[:2])
        q, kp, vp, pt, mask, _, _ = _ring_problem(2)
        with pytest.raises(ValueError, match="divisible"):
            K.ring_ragged_paged_attention(
                q, kp[:11], vp[:11], pt, mask, mesh
            )

    @pytest.mark.slow
    def test_engine_on_seq2_mesh_agrees_greedily(self, tiny):
        cfg, _ = tiny
        prompt = prompt_of(cfg, 47)
        mesh = MachineSpec(seq=2).make_mesh(jax.devices()[:2])
        rm = make_rm(tiny, max_cached_tokens=56, kv_shard="context",
                     mesh=mesh)
        out = rm.generate([prompt], max_new_tokens=10)[0]
        assert out.error is None
        ref = make_rm(tiny, max_cached_tokens=200)
        r_out = ref.generate([prompt], max_new_tokens=10)[0]
        # the ppermute ring reassociates the softmax reduction — token-
        # level agreement is the contract here (bitwise belongs to the
        # seq-degree-1 fallback layout, asserted above)
        assert out.output_tokens == r_out.output_tokens


# ---------------------------------------------------------------------------
# retrace guard: CP churn compiles one program per step key


class TestCpRetrace:
    def test_cp_churn_zero_steady_state_recompiles(self, tiny):
        cfg, _ = tiny
        rm = make_rm(
            tiny, slots=4, max_cached_tokens=48, kv_shard="context",
            context_shards=2, sanitizers=("retrace",),
        )
        prompts = [prompt_of(cfg, 20 + 4 * i, seed=5 + i) for i in range(8)]
        for p in prompts:
            rm.submit(p, max_new_tokens=8)
        while rm.step():
            pass
        rm.drain()
        assert rm.stats.preemptions > 0 or rm.stats.admitted == 8
        guard = rm.engine.retrace_guard
        assert guard is not None
        s = rm.stats.snapshot()
        assert s["retraces"] == 0, f"CP churn recompiled: {s}"
        assert s["compiles"] > 0
        # repeat the workload: NOTHING new compiles (steady state)
        before = s["compiles"]
        for p in prompts:
            rm.submit(p, max_new_tokens=8)
        while rm.step():
            pass
        rm.drain()
        s2 = rm.stats.snapshot()
        assert s2["retraces"] == 0
        assert s2["compiles"] == before, (
            f"steady-state CP workload compiled new programs: "
            f"{before} -> {s2['compiles']}"
        )


# ---------------------------------------------------------------------------
# the fused prologue inside the ring body (rope_kv_write × kv_shard)


@pytest.mark.slow  # seq=2 shard_map compile x2 (~4s); premerge gate 8
# unfiltered (the validation check below stays in tier-1)
def test_ring_fused_rope_kv_write_bitwise(tiny):
    """seq=2 mesh, kernels='pallas': the fused prologue inside the ring
    body is bitwise the unfused ring composition — prefill chunk AND
    decode step, logits and pool bytes."""
    cfg, params = tiny
    mesh = MachineSpec(seq=2).make_mesh(jax.devices()[:2])
    rng = np.random.RandomState(0)
    ps, NP, Pp = 8, 4, 5  # rows = 6, divisible by the seq degree
    cache0 = llama.init_paged_kv_cache(cfg, Pp, ps)
    cspecs = llama.paged_kv_cache_pspecs(cfg, kv_shard="context")
    cache0 = {
        n: jax.device_put(a, NamedSharding(mesh, cspecs[n]))
        for n, a in cache0.items()
    }
    R = 2
    pt = jnp.asarray([[0, 1, Pp, Pp], [2, 3, Pp, Pp]], jnp.int32)
    ptoks = jnp.asarray(rng.randint(0, cfg.vocab_size, (R, 5)), jnp.int32)
    ppos = jnp.broadcast_to(jnp.arange(5, dtype=jnp.int32), (R, 5))
    lidx = jnp.full((R,), 4, jnp.int32)
    outs = {}
    for fused in (False, True):
        c = dict(cache0)
        step = functools.partial(
            llama.serve_step_paged, cfg=cfg, cache_len=NP * ps - 1,
            kernels="pallas", fused_rope=fused, cp_mesh=mesh,
        )
        with set_mesh(mesh):
            l1, c = jax.jit(step)(params, c, ptoks, ppos, lidx,
                                  None, None, pt)
            dtok = jnp.asarray([[7], [11]], jnp.int32)
            dpos = jnp.full((R, 1), 5, jnp.int32)
            l2, c = jax.jit(step)(params, c, dtok, dpos,
                                  jnp.zeros((R,), jnp.int32),
                                  None, None, pt)
        outs[fused] = (l1, l2, c)
    a, b = outs[False], outs[True]
    assert bool(jnp.all(a[0] == b[0])), "prefill logits diverge"
    assert bool(jnp.all(a[1] == b[1])), "decode logits diverge"
    for n in a[2]:
        assert bool(jnp.all(a[2][n][:, :Pp] == b[2][n][:, :Pp])), n


def test_ring_fused_validation_lifted_and_quant_still_excluded():
    """validate_long_context: fp rope_kv_write × seq-sharded passes;
    the QUANTIZED ring commit stays excluded by name."""
    sc = ServingConfig(
        max_requests_per_batch=4, max_sequence_length=48, prefill_chunk=8,
        max_spec_tree_tokens=8, cache_dtype=jnp.float32, kv_layout="paged",
        page_size=8, kernels="pallas", fused_decode=("rope_kv_write",),
        kv_shard="context", context_shards=0,
    )
    sc.validate_long_context(mesh_seq_degree=2)  # no raise
    bad = dataclasses.replace(sc, kv_quant="int8")
    with pytest.raises(ValueError, match="QUANTIZED"):
        bad.validate_long_context(mesh_seq_degree=2)
