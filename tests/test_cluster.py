"""Cluster serving tests (serve/cluster/): router placement/affinity/
shed units over fake replicas, end-to-end parity of the routed cluster
against the bare engine (1-replica bitwise; N-replica round-robin), and
disaggregated prefill→decode page migration — byte-exact over fp, int8
and int4 pools, with ``check_no_leaks`` audited on BOTH replicas after
every hand-off.

The shed contract is the PR-2 one: an SLO-shed request surfaces as
``RequestStatus.ERROR`` / ``GenerationResult.error`` — terminal, never
a hang of ``generate()``, the stream, or the C-host step loop.
"""
import dataclasses
import json

import jax
import jax.numpy as jnp
import pytest

from flexflow_tpu.metrics import ClusterStats, SchedulerStats
from flexflow_tpu.models import llama
from flexflow_tpu.serve import (
    ClusterManager,
    GenerationConfig,
    InferenceEngine,
    RequestManager,
    RequestStatus,
    ServingConfig,
)
from flexflow_tpu.serve.cluster import Router
from flexflow_tpu.serve.cluster.migration import migrate_request


@pytest.fixture(scope="module")
def tiny():
    cfg = llama.LLaMAConfig.tiny(dtype=jnp.float32)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def sc_kwargs(**kw):
    base = dict(
        max_requests_per_batch=4,
        max_sequence_length=96,
        prefill_chunk=8,
        max_spec_tree_tokens=8,
        cache_dtype=jnp.float32,
        kv_layout="paged",
        page_size=16,
    )
    base.update(kw)
    return base


PROMPTS = [
    [3, 17, 91, 42, 7],
    [9, 8, 7, 6, 5, 4],
    [1, 2, 3, 4, 5, 6, 7, 8, 9],
    [11, 22, 33],
]


_BARE = {}


def bare_outputs(tiny, n_new=8, **kw):
    """One bare engine's greedy outputs, computed once a configuration
    (deterministic; every engine built compiles its step programs again,
    ROADMAP A13)."""
    key = (n_new,) + tuple(
        sorted((k, v) for k, v in kw.items() if v is not None))
    if key not in _BARE:
        cfg, params = tiny
        rm = RequestManager(InferenceEngine(
            llama, cfg, params, ServingConfig(**sc_kwargs(**kw))))
        _BARE[key] = [r.output_tokens
                      for r in rm.generate(PROMPTS, max_new_tokens=n_new)]
    return _BARE[key]


# ---------------------------------------------------------------------------
# config validation (fails at construction, like kv_quant/fused_decode)


def test_cluster_config_validation(tiny):
    cfg, params = tiny
    with pytest.raises(ValueError, match="replicas"):
        InferenceEngine(llama, cfg, params,
                        ServingConfig(**sc_kwargs(replicas=0)))
    with pytest.raises(ValueError, match="router_policy"):
        InferenceEngine(llama, cfg, params,
                        ServingConfig(**sc_kwargs(router_policy="nope")))
    with pytest.raises(ValueError, match="BOTH pools"):
        ServingConfig(**sc_kwargs(replicas=2, prefill_replicas=1)
                      ).validate_cluster()
    with pytest.raises(ValueError, match="must equal"):
        ServingConfig(
            **sc_kwargs(replicas=3, prefill_replicas=1, decode_replicas=1)
        ).validate_cluster()
    with pytest.raises(ValueError, match="paged"):
        ServingConfig(
            max_requests_per_batch=4, max_sequence_length=96,
            kv_layout="dense", replicas=2, prefill_replicas=1,
            decode_replicas=1,
        ).validate_cluster()
    with pytest.raises(ValueError, match="slo_queue_delay_s"):
        ServingConfig(**sc_kwargs(slo_queue_delay_s=-1.0)).validate_cluster()
    # a valid disaggregated config constructs
    ServingConfig(
        **sc_kwargs(replicas=2, prefill_replicas=1, decode_replicas=1)
    ).validate_cluster()


# ---------------------------------------------------------------------------
# router units over fake replicas


class FakeReplica:
    def __init__(self, index, *, score=0, delay=0.0, load=0.0):
        self.index = index
        self._score = score
        self._delay = delay
        self._load = load

    def prefix_score(self, tokens):
        return self._score

    def queue_delay_s(self):
        return self._delay

    def load(self):
        return self._load


def test_router_prefix_routes_to_longest_match():
    stats = ClusterStats()
    reps = [FakeReplica(0, score=0), FakeReplica(1, score=32),
            FakeReplica(2, score=16)]
    r = Router(reps, "prefix", stats=stats)
    pos, how = r.route(list(range(40)))
    assert (pos, how) == (1, "prefix")
    assert stats.placements == {"prefix": 1}


def test_router_prefix_miss_falls_back_to_least_loaded():
    reps = [FakeReplica(0, delay=2.0), FakeReplica(1, delay=0.1),
            FakeReplica(2, delay=1.0)]
    r = Router(reps, "prefix", stats=ClusterStats())
    pos, how = r.route([1, 2, 3])
    assert (pos, how) == (1, "least_loaded")


def test_router_prefix_tie_breaks_by_load():
    reps = [FakeReplica(0, score=16, delay=5.0),
            FakeReplica(1, score=16, delay=0.0)]
    r = Router(reps, "prefix")
    pos, how = r.route([1] * 20)
    assert (pos, how) == (1, "prefix")


def test_router_round_robin_cycles():
    reps = [FakeReplica(i) for i in range(3)]
    r = Router(reps, "round_robin", stats=ClusterStats())
    assert [r.route([1])[0] for _ in range(6)] == [0, 1, 2, 0, 1, 2]


def test_router_least_loaded_picks_min_delay():
    reps = [FakeReplica(0, delay=0.5, load=3),
            FakeReplica(1, delay=0.5, load=1),
            FakeReplica(2, delay=0.9)]
    r = Router(reps, "least_loaded")
    assert r.route([1])[0] == 1  # equal delay -> fewer live requests


def test_router_session_affinity():
    stats = ClusterStats()
    reps = [FakeReplica(0, score=99), FakeReplica(1)]
    r = Router(reps, "prefix", stats=stats)
    pos0, how0 = r.route([1] * 8, session_id="chat")
    assert (pos0, how0) == (0, "prefix")
    # replica 1 now holds a longer match, but the session sticks to 0
    reps[1]._score = 10 ** 6
    pos1, how1 = r.route([1] * 8, session_id="chat")
    assert (pos1, how1) == (0, "affinity")
    assert stats.affinity_hits == 1
    # a session whose replica is over-SLO re-routes instead of shedding
    reps[0]._delay = 99.0
    r.slo_queue_delay_s = 1.0
    pos2, how2 = r.route([1] * 8, session_id="chat")
    assert pos2 == 1 and how2 != "affinity"


def test_router_sheds_when_every_replica_over_slo():
    stats = ClusterStats()
    reps = [FakeReplica(0, delay=5.0), FakeReplica(1, delay=9.0)]
    r = Router(reps, "prefix", slo_queue_delay_s=1.0, stats=stats)
    assert r.route([1, 2, 3]) == (None, "shed")
    assert stats.sheds == 1
    # headroom on one replica redirects instead of shedding
    reps[1]._delay = 0.2
    pos, _ = r.route([1, 2, 3])
    assert pos == 1
    assert stats.sheds == 1


# ---------------------------------------------------------------------------
# end-to-end parity: the router must never change the tokens


def test_single_replica_router_bitwise_vs_bare_engine(tiny):
    cfg, params = tiny
    base = bare_outputs(tiny)
    cm = ClusterManager.build(
        llama, cfg, params, ServingConfig(**sc_kwargs(replicas=1))
    )
    outs = cm.generate(PROMPTS, max_new_tokens=8)
    assert [r.output_tokens for r in outs] == base
    assert all(r.error is None for r in outs)
    # ProfileInfo mirrors: replica id + the router's delay estimate
    assert all(r.profile.replica_id == 0 for r in outs)
    assert all(r.profile.router_queue_delay_s >= 0.0 for r in outs)
    cm.check_no_leaks()


def test_single_replica_router_bitwise_sampling(tiny):
    """Same-seed SAMPLING parity: the routed scheduler must replay the
    bare engine's exact dispatch (and so PRNG-split) sequence."""
    cfg, params = tiny
    gen = GenerationConfig(do_sample=True, temperature=0.7, topk=8)
    rm = RequestManager(
        InferenceEngine(llama, cfg, params, ServingConfig(**sc_kwargs()))
    )
    base = [r.output_tokens for r in rm.generate(PROMPTS, gen,
                                                 max_new_tokens=8)]
    cm = ClusterManager.build(
        llama, cfg, params, ServingConfig(**sc_kwargs(replicas=1))
    )
    outs = cm.generate(PROMPTS, gen, max_new_tokens=8)
    assert [r.output_tokens for r in outs] == base


def test_round_robin_two_replicas_output_parity(tiny):
    cfg, params = tiny
    base = bare_outputs(tiny)
    cm = ClusterManager.build(
        llama, cfg, params,
        ServingConfig(**sc_kwargs(replicas=2, router_policy="round_robin")),
    )
    outs = cm.generate(PROMPTS, max_new_tokens=8)
    assert [r.output_tokens for r in outs] == base
    placed = {r.profile.replica_id for r in outs}
    assert placed == {0, 1}  # round robin actually spread the work
    assert cm.cluster_stats()["placements"] == {"round_robin": 4}
    cm.check_no_leaks()


def test_prefix_routing_partitions_families(tiny):
    """Two prefix families over two prefix-cached replicas: the router
    seeds each family on one replica (least-loaded on the first miss)
    and every later relative follows its family by radix-tree match —
    outputs stay bitwise the cold engine's (the PR-3 hit-path
    guarantee, now load-bearing for placement)."""
    cfg, params = tiny
    sysA = [5] * 16
    sysB = [7] * 16
    fam = [sysA + [i, i + 1] for i in range(3)] + \
          [sysB + [i, i + 9] for i in range(3)]
    kw = sc_kwargs(max_sequence_length=64, prefix_caching=True)
    rm = RequestManager(
        InferenceEngine(llama, cfg, params, ServingConfig(**kw))
    )
    # cold reference: each prompt generated in isolation
    base = [
        rm2.output_tokens
        for rm2 in (
            RequestManager(
                InferenceEngine(llama, cfg, params, ServingConfig(**kw))
            ).generate([p], max_new_tokens=4)[0]
            for p in fam
        )
    ]
    cm = ClusterManager.build(
        llama, cfg, params, ServingConfig(**kw, replicas=2)
    )
    outs = []
    for p in fam:  # sequential so inserts land before the next match
        outs.append(cm.generate([p], max_new_tokens=4)[0])
    assert [r.output_tokens for r in outs] == base
    s = cm.cluster_stats()
    assert s["placements"].get("prefix", 0) >= 4  # relatives matched
    byrep = {}
    for p, r in zip(fam, outs):
        byrep.setdefault(tuple(p[:16]), set()).add(r.profile.replica_id)
    # each family stayed on one replica
    assert all(len(v) == 1 for v in byrep.values())
    assert s["replicas"]["prefix_hits"] >= 4
    cm.check_no_leaks()


# ---------------------------------------------------------------------------
# disaggregated prefill→decode migration


@pytest.mark.parametrize("kv_quant", [None, "int8", "int4"])
def test_migrated_prefill_bitwise_vs_local(tiny, kv_quant):
    """The acceptance bar: a request prefilled on the prefill pool and
    decoded on the decode pool after page migration generates BITWISE
    the single-replica tokens — fp, int8 and int4 pools (codes AND
    scale rows migrate byte-exact, so rescale-on-growth continues the
    same history). Zero pages leaked on either replica afterwards."""
    cfg, params = tiny
    kw = {} if kv_quant is None else {"kv_quant": kv_quant}
    base = bare_outputs(tiny, **kw)
    cm = ClusterManager.build(
        llama, cfg, params,
        ServingConfig(
            **sc_kwargs(replicas=2, prefill_replicas=1, decode_replicas=1,
                        **kw)
        ),
    )
    outs = cm.generate(PROMPTS, max_new_tokens=8)
    assert [r.output_tokens for r in outs] == base
    s = cm.cluster_stats()
    assert s["migrations"] == len(PROMPTS)
    assert s["migrated_pages"] >= len(PROMPTS)
    assert s["migrated_bytes"] > 0
    # decode happened on the decode replica, and nothing leaked
    assert all(r.profile.replica_id == 1 for r in outs)
    cm.check_no_leaks()
    # prefill pool released every held slot
    assert cm.replicas[0].rm.hold_finished == set()
    assert cm.replicas[0].engine.pager.used_pages == 0


def test_migration_single_token_budget_finishes_on_prefill_pool(tiny):
    """max_new_tokens=1 owes nothing after the prefill pass — the
    request finishes on the prefill replica, no migration happens, and
    nothing is held forever."""
    cfg, params = tiny
    base = bare_outputs(tiny, n_new=1)
    cm = ClusterManager.build(
        llama, cfg, params,
        ServingConfig(
            **sc_kwargs(replicas=2, prefill_replicas=1, decode_replicas=1)
        ),
    )
    outs = cm.generate(PROMPTS, max_new_tokens=1)
    assert [r.output_tokens for r in outs] == base
    s = cm.cluster_stats()
    assert s["migrations"] == 0
    assert cm.replicas[0].rm.hold_finished == set()
    cm.check_no_leaks()


def test_migrate_request_helper_moves_pages_exactly(tiny):
    """Unit-level: run one prefill pass by hand, migrate, and compare
    the destination's uploaded page bytes against the source's."""
    import numpy as np

    cfg, params = tiny
    sc = ServingConfig(**sc_kwargs(replicas=2, prefill_replicas=1,
                                   decode_replicas=1))
    cm = ClusterManager.build(llama, cfg, params, sc)
    src, dst = cm.replicas
    prompt = list(range(1, 20))  # 19 tokens -> 2 pages of 16
    rid = src.rm.submit(prompt, GenerationConfig(max_new_tokens=1))
    src.rm.hold_on_finish(rid)
    while src.rm.step():
        pass
    src.rm.drain()
    req = src.rm.requests[rid]
    assert req.status is RequestStatus.COMPLETED and req.slot >= 0
    src_pages = [int(p) for p in src.engine.pager.table[req.slot][:2]]
    src_bytes = [
        jax.device_get(src.engine.fetch_page(p)) for p in src_pages
    ]
    rid2 = migrate_request(src, dst, rid, GenerationConfig(max_new_tokens=4),
                           stats=cm.stats)
    assert rid2 is not None
    dst_slot = dst.rm.requests[rid2].slot
    dst_pages = [int(p) for p in dst.engine.pager.table[dst_slot][:2]]
    for sp, dp in zip(src_bytes, dst_pages):
        got = jax.device_get(dst.engine.fetch_page(dp))
        for k in sp:
            np.testing.assert_array_equal(sp[k], got[k])
    src.rm.release_held(rid)
    cm.check_no_leaks()


def test_adopt_prefilled_rolls_back_without_capacity(tiny):
    """adopt_prefilled with every slot occupied returns None and leaves
    no state behind (the migration retries later)."""
    cfg, params = tiny
    rm = RequestManager(
        InferenceEngine(llama, cfg, params, ServingConfig(**sc_kwargs()))
    )
    rids = [rm.submit([1 + i, 2, 3], max_new_tokens=32) for i in range(4)]
    rm.step()  # admit all four; slots full
    assert all(s is not None for s in rm.slots)
    before = rm.engine.pager.used_pages
    assert rm.adopt_prefilled([9, 9, 9, 9], 3,
                              GenerationConfig(max_new_tokens=4)) is None
    assert rm.engine.pager.used_pages == before
    for _ in range(200):
        if not rm.step():
            break
    rm.drain()
    del rids


# ---------------------------------------------------------------------------
# shed + error paths (the PR-2 contract: terminal, never a hang)


def test_shed_surfaces_error_not_hang(tiny):
    cfg, params = tiny
    cm = ClusterManager.build(
        llama, cfg, params,
        ServingConfig(**sc_kwargs(replicas=2, slo_queue_delay_s=0.05)),
    )
    # saturate the delay estimates so admission must shed
    for rep in cm.replicas:
        rep.queue_delay_s = lambda: 10.0
    cm.router.slo_queue_delay_s = 0.05
    outs = cm.generate(PROMPTS[:2], max_new_tokens=4)
    assert all(r.error is not None and "shed" in r.error for r in outs)
    assert all(r.output_tokens == [] for r in outs)
    assert cm.stats.sheds == 2
    # shed requests are terminal for the step loop immediately
    assert all(
        cm.requests[c].status is RequestStatus.ERROR for c in cm.requests
    )


def test_unservable_prompt_errors_through_cluster(tiny):
    """The PR-2 unservable-request path flows through the router
    unchanged: a prompt that alone exceeds the KV budget errors instead
    of hanging the cluster."""
    cfg, params = tiny
    cm = ClusterManager.build(
        llama, cfg, params,
        ServingConfig(**sc_kwargs(replicas=2, max_cached_tokens=32)),
    )
    good = [1, 2, 3, 4]
    bad = list(range(80))  # > 32-token pool on whichever replica
    outs = cm.generate([good, bad], max_new_tokens=4)
    assert outs[0].error is None and len(outs[0].output_tokens) == 4
    assert outs[1].error is not None
    cm.check_no_leaks()


def test_cluster_stream_delivers_every_token_and_terminals(tiny):
    cfg, params = tiny
    base = bare_outputs(tiny, n_new=6)
    cm = ClusterManager.build(
        llama, cfg, params,
        ServingConfig(**sc_kwargs(replicas=2, router_policy="round_robin")),
    )
    got = {}
    done = set()
    for ev in cm.generate_stream(PROMPTS, max_new_tokens=6):
        if ev.done:
            assert ev.error is None
            done.add(ev.request_id)
        else:
            got.setdefault(ev.request_id, []).append(ev.token)
    assert len(done) == len(PROMPTS)
    assert [got[c] for c in sorted(got)] == base


def test_cluster_stream_disaggregated_no_duplicate_tokens(tiny):
    """Across a migration the stream's per-request token counts stay
    monotone: the first output token (sampled on the prefill pool,
    visible on both sides of the hand-off) is sent exactly once."""
    cfg, params = tiny
    base = bare_outputs(tiny, n_new=6)
    cm = ClusterManager.build(
        llama, cfg, params,
        ServingConfig(
            **sc_kwargs(replicas=2, prefill_replicas=1, decode_replicas=1)
        ),
    )
    got = {}
    for ev in cm.generate_stream(PROMPTS, max_new_tokens=6):
        if not ev.done:
            got.setdefault(ev.request_id, []).append(ev.token)
    assert [got[c] for c in sorted(got)] == base
    assert cm.cluster_stats()["migrations"] == len(PROMPTS)


# ---------------------------------------------------------------------------
# stats + integration surfaces


def test_cluster_stats_aggregates_scheduler_stats():
    a, b = SchedulerStats(), SchedulerStats()
    a.prefix_hits, a.prefix_misses, a.admitted = 3, 1, 4
    b.prefix_hits, b.prefix_misses, b.admitted = 1, 3, 4
    cs = ClusterStats()
    cs.record_placement("prefix")
    cs.record_placement("affinity")
    cs.migrations, cs.migrated_bytes = 2, 1024
    snap = cs.snapshot([a, b])
    assert snap["replicas"]["admitted"] == 8
    assert snap["replicas"]["prefix_hits"] == 4
    assert snap["replicas"]["prefix_hit_rate"] == 0.5
    assert snap["placements"] == {"prefix": 1, "affinity": 1}
    assert snap["affinity_hits"] == 1
    assert len(snap["per_replica"]) == 2
    assert "cluster" in cs.report([a, b])


def test_c_backend_cluster_and_shed_terminal(tiny):
    """The C host's loop drives a cluster exactly like a bare manager,
    and a shed request is terminal for num_active (never spins)."""
    from flexflow_tpu.serve import c_backend

    model = dict(
        vocab_size=256, hidden_size=64, intermediate_size=172,
        num_hidden_layers=2, num_attention_heads=4,
        num_key_value_heads=2, max_position_embeddings=128,
    )
    serving = dict(
        max_requests_per_batch=2, max_sequence_length=64,
        prefill_chunk=8, max_spec_tree_tokens=8,
        kv_layout="paged", page_size=16, replicas=2,
    )
    try:
        assert c_backend.init(json.dumps({
            "family": "llama", "model": model, "serving": serving,
            "max_new_tokens": 4,
        })) == 0
        rid = c_backend.register_request([3, 17, 9], 4)
        while c_backend.step():
            pass
        assert c_backend.num_active() == 0
        assert len(c_backend.fetch(rid)) == 4
        # shed: force every replica over a tiny SLO
        cm = c_backend._STATE["rm"]
        for rep in cm.replicas:
            rep.queue_delay_s = lambda: 10.0
        cm.router.slo_queue_delay_s = 0.01
        rid2 = c_backend.register_request([5, 6, 7], 4)
        assert c_backend.num_active() == 0  # terminal on arrival
        assert c_backend.fetch(rid2) is None
        assert cm.requests[rid2].status is RequestStatus.ERROR
    finally:
        c_backend.shutdown()


def test_llm_compile_builds_cluster(tiny):
    from flexflow_tpu.serve.llm import LLM

    cfg, params = tiny
    llm = LLM(llama, cfg, params)
    llm.compile(ServingConfig(**sc_kwargs(replicas=2,
                                          router_policy="round_robin")))
    assert isinstance(llm.rm, ClusterManager)
    outs = llm.generate(PROMPTS[:2], max_new_tokens=4)
    assert len(outs) == 2 and all(len(o.output_tokens) == 4 for o in outs)


def test_retrace_guard_clean_across_cluster(tiny):
    """Every replica warmed then rerun under the strict retrace
    sentinel: steady-state cluster serving (round-robin so both
    replicas work) compiles each step key once and never retraces."""
    cfg, params = tiny
    cm = ClusterManager.build(
        llama, cfg, params,
        ServingConfig(**sc_kwargs(replicas=2, router_policy="round_robin",
                                  sanitizers=("retrace",))),
    )
    cm.generate(PROMPTS, max_new_tokens=4)  # warm
    cm.generate(PROMPTS, max_new_tokens=4)  # steady state: replay only
    for rep in cm.replicas:
        assert rep.rm.stats.retraces == 0
        assert rep.rm.stats.compiles > 0
