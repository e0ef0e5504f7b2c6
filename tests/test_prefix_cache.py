"""Automatic prefix caching tests (serve/prefix_cache.py): radix-tree
match/insert/evict unit behavior, bitwise logit parity between a
cache-hit generation and the same prompt prefilled cold (dense
passthrough and paged), copy-on-write on partially-matched tail pages,
and LRU eviction under pool pressure (the cache must never fail an
admission a cold pool would admit). Fast deterministic cases run in
tier-1; the Poisson shared-system-prompt variant is marked ``slow``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.models import llama
from flexflow_tpu.serve import (
    InferenceEngine,
    PageAllocator,
    PrefixCache,
    RequestManager,
    ServingConfig,
)
from flexflow_tpu.serve.batch_config import BatchConfig


@pytest.fixture(scope="module")
def tiny():
    cfg = llama.LLaMAConfig.tiny(dtype=jnp.float32)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def make_engine(tiny, kv_layout="paged", *, slots=4, page_size=8, max_seq=64,
                **kw):
    cfg, params = tiny
    sc = ServingConfig(
        max_requests_per_batch=slots,
        max_sequence_length=max_seq,
        prefill_chunk=8,
        max_spec_tree_tokens=8,
        cache_dtype=jnp.float32,
        kv_layout=kv_layout,
        page_size=page_size,
        **kw,
    )
    return InferenceEngine(llama, cfg, params, sc)


def _prompts(cfg, n, shared_len=20, tail_len=5):
    shared = [(j * 7 + 3) % cfg.vocab_size for j in range(shared_len)]
    return [
        shared + [(i * 13 + j * 3 + 1) % cfg.vocab_size
                  for j in range(tail_len)]
        for i in range(n)
    ]


def _audit(rm):
    rm.engine.pager.check_no_leaks(
        external=rm.prefix_cache.page_refs() if rm.prefix_cache else None
    )


# ---------------------------------------------------------------------------
# radix tree unit behavior (bare allocator, no engine)


class TestRadixTree:
    def _cache(self, num_pages=32, ps=4, slots=8):
        pa = PageAllocator(num_pages, 8, slots, ps)
        cache = PrefixCache(pa, copy_page=None)
        pa.reclaim_cb = cache.reclaim
        return pa, cache

    def test_empty_tree_misses(self):
        _, cache = self._cache()
        assert cache.match([1, 2, 3, 4, 5]) == ([], 0)
        assert cache.attach(0, [1, 2, 3, 4, 5]) == 0

    def test_insert_then_match_full_and_partial(self):
        pa, cache = self._cache()
        toks = [1, 2, 3, 4, 5, 6, 7, 8, 9, 10]  # 2.5 pages of 4
        assert pa.ensure(0, len(toks))
        cache.insert(0, toks, len(toks))
        pa.release(0)
        # exact re-ask: capped at len-1 (last token recomputed)
        pages, m = cache.match(toks)
        assert m == 9 and len(pages) == 3
        # longer prompt sharing the prefix: all 10 cached tokens match
        pages, m = cache.match(toks + [11, 12])
        assert m == 10 and len(pages) == 3
        # shorter prompt: partial use of a full block
        pages, m = cache.match([1, 2, 3, 4, 5, 6, 99])
        assert m == 6 and len(pages) == 2
        # divergence inside the first block
        pages, m = cache.match([1, 9, 9, 9, 9])
        assert m == 1 and len(pages) == 1
        pa.check_no_leaks(external=cache.page_refs())

    def test_attach_cow_on_partial_tail(self):
        pa, cache = self._cache()
        toks = list(range(10, 20))  # 2.5 pages
        assert pa.ensure(0, len(toks))
        cache.insert(0, toks, len(toks))
        tail_page = int(pa.table[0][2])
        pa.release(0)
        m = cache.attach(1, toks + [77])  # matches all 10 → tail mid-page
        assert m == 10
        assert int(pa.table[1][2]) != tail_page  # private COW copy
        assert [int(p) for p in pa.table[1][:2]] == [
            int(n) for n in cache.match(toks)[0][:2]
        ]  # full blocks shared by reference
        pa.check_no_leaks(external=cache.page_refs())

    def test_lru_eviction_spares_in_use_pages(self):
        pa, cache = self._cache(num_pages=8, ps=4)
        a, b = [1] * 8, [2] * 8  # 2 full pages each
        for slot, toks in ((0, a), (1, b)):
            assert pa.ensure(slot, len(toks))
            cache.insert(slot, toks, len(toks))
        pa.release(0)          # a idle (evictable)
        cache.match(b)         # b more recently used
        m = cache.attach(2, b + [9])   # keeps b's pages referenced
        assert m == 8
        pa.release(1)
        freed = cache.reclaim(8)
        # only a's 2 pages + b's now-idle... b's pages are spliced into
        # slot 2 (refcount 2) — NOT evictable; a's leaf-first chain
        # peels both its pages
        assert freed == 2
        assert cache.match(a)[1] == 0      # a gone
        assert cache.match(b + [9])[1] == 8  # b survives
        pa.check_no_leaks(external=cache.page_refs())

    def test_clear_returns_pool_to_free(self):
        pa, cache = self._cache()
        toks = list(range(12))
        assert pa.ensure(0, len(toks))
        cache.insert(0, toks, len(toks))
        pa.release(0)
        assert pa.free_pages < pa.num_pages
        cache.clear()
        pa.check_no_leaks()
        assert pa.free_pages == pa.num_pages


# ---------------------------------------------------------------------------
# cache-hit correctness: generation parity


def _rm(tiny, layout, **kw):
    return RequestManager(make_engine(tiny, layout, **kw))


class TestHitParity:
    def test_dense_passthrough(self, tiny):
        """prefix_caching=True on the dense layout is a documented
        no-op: no cache object, identical outputs."""
        cfg, _ = tiny
        prompts = _prompts(cfg, 3)
        want = [o.output_tokens
                for o in _rm(tiny, "dense").generate(prompts, max_new_tokens=6)]
        rm = _rm(tiny, "dense", prefix_caching=True)
        assert rm.prefix_cache is None
        for _ in range(2):  # second pass would hit, if anything cached
            got = [o.output_tokens
                   for o in rm.generate(prompts, max_new_tokens=6)]
            assert got == want

    def test_paged_hit_matches_cold(self, tiny):
        """The headline claim: a generation served from cached prefix
        pages produces bitwise the tokens of a cold prefill — on the
        seeding pass (misses + concurrent same-prefix admissions) AND
        the fully-hitting second pass."""
        cfg, _ = tiny
        prompts = _prompts(cfg, 3)
        want = [o.output_tokens
                for o in _rm(tiny, "paged").generate(prompts, max_new_tokens=6)]
        rm = _rm(tiny, "paged", prefix_caching=True)
        first = [o.output_tokens for o in rm.generate(prompts, max_new_tokens=6)]
        second = rm.generate(prompts, max_new_tokens=6)
        assert first == want
        assert [o.output_tokens for o in second] == want
        # every second-pass admission hit the cache past the shared stem
        assert all(o.profile.cached_prefix_len >= 16 for o in second)
        assert rm.stats.prefix_hits >= 3
        assert rm.stats.prefix_hit_tokens >= 3 * 16
        _audit(rm)

    def test_continuous_and_sync_schedulers_hit_identically(self, tiny):
        cfg, _ = tiny
        prompts = _prompts(cfg, 4)
        want = [o.output_tokens
                for o in _rm(tiny, "paged").generate(prompts, max_new_tokens=5)]
        for continuous in (True, False):
            rm = _rm(tiny, "paged", prefix_caching=True,
                     continuous_batching=continuous)
            for _ in range(2):
                got = [o.output_tokens
                       for o in rm.generate(prompts, max_new_tokens=5)]
                assert got == want
            assert rm.stats.prefix_hits > 0
            _audit(rm)

    def test_cache_policy_prefill_publishes_early(self, tiny):
        """policy='prefill' inserts the prompt when its last chunk is
        dispatched — a later same-prompt request hits even though the
        seeder never completed 'normally' long ago; outputs unchanged."""
        cfg, _ = tiny
        prompts = _prompts(cfg, 2)
        want = [o.output_tokens
                for o in _rm(tiny, "paged").generate(prompts, max_new_tokens=5)]
        rm = _rm(tiny, "paged", prefix_caching=True, cache_policy="prefill")
        assert [o.output_tokens
                for o in rm.generate(prompts, max_new_tokens=5)] == want
        assert rm.stats.prefix_inserts > 0
        got = rm.generate(prompts, max_new_tokens=5)
        assert [o.output_tokens for o in got] == want
        assert all(o.profile.cached_prefix_len > 0 for o in got)
        _audit(rm)

    def test_cow_divergent_tail(self, tiny):
        """A prompt diverging mid-page from a cached one must COW the
        tail page: the cached original stays pristine (the original
        prompt still matches and still decodes identically)."""
        cfg, _ = tiny
        shared = [(j * 7 + 3) % cfg.vocab_size for j in range(20)]
        pa_prompt = shared + [9, 9, 9]
        pb_prompt = shared + [5, 5, 5, 5]
        cold = _rm(tiny, "paged")
        want_a = [o.output_tokens
                  for o in cold.generate([pa_prompt], max_new_tokens=5)]
        want_b = [o.output_tokens
                  for o in cold.generate([pb_prompt], max_new_tokens=5)]
        rm = _rm(tiny, "paged", prefix_caching=True)
        assert [o.output_tokens
                for o in rm.generate([pa_prompt], max_new_tokens=5)] == want_a
        assert [o.output_tokens
                for o in rm.generate([pb_prompt], max_new_tokens=5)] == want_b
        assert rm.stats.prefix_cows >= 1
        # the COW must not have corrupted the cached original
        assert [o.output_tokens
                for o in rm.generate([pa_prompt], max_new_tokens=5)] == want_a
        _audit(rm)

    def test_hit_skips_prefill_work(self, tiny):
        """A full hit really starts prefill at the cached offset: the
        second pass dispatches fewer prefill tokens than the first."""
        cfg, _ = tiny
        prompts = _prompts(cfg, 2)
        rm = _rm(tiny, "paged", prefix_caching=True)
        rm.generate(prompts, max_new_tokens=4)
        cold_prefill = rm.stats.prefill_tokens
        rm.generate(prompts, max_new_tokens=4)
        warm_prefill = rm.stats.prefill_tokens - cold_prefill
        assert warm_prefill < cold_prefill / 2
        _audit(rm)


# ---------------------------------------------------------------------------
# bitwise LOGIT parity, engine level (no scheduler noise)


def _prefill_last_logits(eng, tokens, start, slot):
    """Chunked prefill of tokens[start:] on ``slot``; returns the final
    chunk's logits row (the one the first sampled token comes from)."""
    chunk, scratch = 8, eng.scratch_pos
    logits = None
    off = start
    while off < len(tokens):
        n = min(chunk, len(tokens) - off)
        bc = BatchConfig.empty(eng.num_slots, chunk, scratch)
        bc.tokens[slot, :n] = tokens[off:off + n]
        bc.positions[slot, :n] = np.arange(off, off + n)
        bc.logits_idx[slot] = n - 1
        bc.active[slot] = True
        logits = np.asarray(jax.device_get(eng.run(bc)))[slot]
        off += n
    return logits


def test_cache_hit_logit_bitwise_parity(tiny):
    """The acceptance bar, at the logit level: prefilling only the
    uncached suffix over spliced (and COW'd) pages yields BITWISE the
    final-position logits of a cold full prefill — same engine config,
    different slot, different physical pages."""
    prompt = [(j * 11 + 5) % 256 for j in range(21)]  # 2 full pages + 5
    eng = make_engine(tiny, "paged", page_size=8, prefix_caching=True)
    pa = eng.pager
    cache = PrefixCache(pa, copy_page=eng.copy_page)
    pa.reclaim_cb = cache.reclaim

    # cold full prefill on slot 0 seeds pages; publish lines [0, 21)
    assert pa.ensure(0, len(prompt))
    cold = _prefill_last_logits(eng, prompt, 0, slot=0)
    cache.insert(0, prompt, len(prompt))
    pa.release(0)

    # hit path on slot 2: match 20 of 21 tokens (cap P-1), COW the tail
    matched = cache.attach(2, prompt)
    assert matched == 20 and matched % 8 == 4  # ends mid-page → COW'd
    hit = _prefill_last_logits(eng, prompt, matched, slot=2)
    np.testing.assert_array_equal(cold, hit)
    pa.check_no_leaks(external=cache.page_refs())


def test_eviction_under_pressure_regression(tiny):
    """Oversubscribed pool with a warm cache: admissions that need
    pages must evict idle cached pages (never preempt, never fail) and
    outputs must match the cold allocator exactly."""
    cfg, _ = tiny
    # 10 pages of 8 = 80 tokens — two 23-token prompts + outputs fit,
    # but not alongside a stale cache: eviction must kick in
    batches = [
        _prompts(cfg, 2, shared_len=18 + 2 * b, tail_len=5)
        for b in range(3)
    ]
    cold = _rm(tiny, "paged", max_cached_tokens=80)
    rm = _rm(tiny, "paged", max_cached_tokens=80, prefix_caching=True)
    for batch in batches:
        want = [o.output_tokens
                for o in cold.generate(batch, max_new_tokens=5)]
        got = [o.output_tokens for o in rm.generate(batch, max_new_tokens=5)]
        assert got == want
        _audit(rm)
    assert rm.stats.prefix_evictions > 0
    # the cache never made admission harder than the cold pool
    assert rm.stats.preemptions == cold.stats.preemptions
    assert rm.stats.failed == 0


@pytest.mark.slow
def test_poisson_shared_system_prompt_parity(tiny):
    """Poisson-arrival shared-system-prompt workload: caching on vs off must produce identical
    outputs while the cache reports a substantial hit rate."""
    cfg, _ = tiny
    rng = np.random.default_rng(7)
    system = [(j * 7 + 3) % cfg.vocab_size for j in range(24)]
    prompts = [
        system + [int(t) for t in rng.integers(0, cfg.vocab_size, size=6)]
        for _ in range(24)
    ]
    outs = {}
    for caching in (False, True):
        rm = _rm(tiny, "paged", slots=8, max_seq=96, prefix_caching=caching)
        rids, due = [], list(prompts)
        while due or any(
            rm.requests[r].status.value not in ("completed", "error")
            for r in rids
        ):
            for _ in range(int(rng.integers(0, 3))):
                if due:
                    rids.append(rm.submit(due.pop(0), max_new_tokens=6))
            if not rm.step() and due:
                rids.append(rm.submit(due.pop(0), max_new_tokens=6))
        rm.drain()
        outs[caching] = [rm.requests[r].output_tokens for r in rids]
        if caching:
            assert rm.stats.prefix_hit_tokens > 0
            _audit(rm)
    assert outs[True] == outs[False]
