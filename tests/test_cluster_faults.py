"""Fault-tolerant cluster serving (serve/cluster/{health,faults}.py +
manager failover).

The contracts under test:

* **Health machine** — HEALTHY → SUSPECT → DOWN → PROBING transitions
  driven by step exceptions and latency spikes, circuit-breaker
  exponential backoff, probe re-admission (units, no engine).
* **Failover** — a replica death re-admits its in-flight requests to
  survivors through recompute (prompt + flushed tokens re-prefill), so
  GREEDY generations are BITWISE the fault-free run's; bounded retries
  / no-healthy-replica end in a terminal ``GenerationResult.error``,
  never a hang.
* **Determinism** — the same seeded :class:`FaultPlan` replays the same
  scenario; the chaos sweep asserts every submitted request reaches a
  terminal state with zero page/held-slot leaks on surviving replicas.
* **Back-pressure** — the bounded migration queue drains held prefills
  through recompute re-admission instead of parking them; degraded
  pools (dead prefill or decode pool) fall back to non-disaggregated
  serving on the surviving pool.
"""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from flexflow_tpu.models import llama
from flexflow_tpu.serve import (
    ClusterManager,
    GenerationConfig,
    InferenceEngine,
    RequestManager,
    RequestStatus,
    ServingConfig,
)
from flexflow_tpu.serve.cluster import (
    Fault,
    FaultPlan,
    HealthConfig,
    HealthState,
    ReplicaHealth,
    migrate_request,
)
from flexflow_tpu.serve.cluster.faults import InjectedFault


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


def no_held_slots(cm):
    for pos, rep in enumerate(cm.replicas):
        if cm.health[pos].state is not HealthState.DOWN:
            assert rep.rm.hold_finished == set(), (
                f"replica {rep.index} still holds {rep.rm.hold_finished}"
            )


# ---------------------------------------------------------------------------
# health state machine units (no engine)


def test_health_exception_path_to_down_and_probe():
    h = ReplicaHealth(0, HealthConfig(failure_threshold=2,
                                      probe_backoff_steps=4))
    assert h.state is HealthState.HEALTHY and h.routable
    assert h.record_failure(RuntimeError("boom"), step_no=1) == "suspect"
    assert h.state is HealthState.SUSPECT and h.routable
    assert h.record_failure(RuntimeError("boom"), step_no=2) == "down"
    assert h.state is HealthState.DOWN and not h.routable
    # backoff not expired yet
    assert not h.maybe_probe(step_no=5)
    assert h.maybe_probe(step_no=6)
    assert h.state is HealthState.PROBING and h.routable
    # a probing failure re-opens the circuit with the backoff DOUBLED
    assert h.record_failure(RuntimeError("again"), step_no=7) == "down"
    assert h.backoff_steps == 8
    assert not h.maybe_probe(step_no=14)
    assert h.maybe_probe(step_no=15)
    # enough clean steps with work close the circuit and reset backoff
    for i in range(h.cfg.probe_successes - 1):
        assert h.record_success(0.01, step_no=16 + i) is None
    assert h.record_success(0.01, step_no=20) == "recovered"
    assert h.state is HealthState.HEALTHY
    assert h.backoff_steps == 4 and h.trips == 0


def test_health_suspect_recovers_on_clean_streak():
    cfg = HealthConfig(recovery_steps=3)
    h = ReplicaHealth(0, cfg)
    h.record_failure(RuntimeError("blip"), step_no=1)
    assert h.state is HealthState.SUSPECT
    assert h.record_success(0.01, 2) is None
    assert h.record_success(0.01, 3) is None
    assert h.record_success(0.01, 4) == "recovered"
    assert h.state is HealthState.HEALTHY


def test_health_latency_spikes_suspect_then_down():
    cfg = HealthConfig(min_latency_samples=2, latency_spike_factor=4.0,
                       latency_spike_steps=2, spike_down_steps=4)
    h = ReplicaHealth(0, cfg)
    for i in range(3):
        h.record_success(0.01, i)  # warm the EMA
    assert h.record_success(1.0, 10) is None           # spike 1
    assert h.record_success(1.0, 11) == "suspect"      # spike 2
    assert h.record_success(1.0, 12) is None           # spike 3
    assert h.record_success(1.0, 13) == "down"         # spike 4: breaker
    assert h.state is HealthState.DOWN
    # spikes never fed the EMA — it still reflects the clean baseline
    assert h._ema < 0.1


# ---------------------------------------------------------------------------
# fault plan determinism + serialization


def test_fault_plan_seeded_reproducible_and_json_roundtrip():
    a = FaultPlan.random(1234, n_replicas=3, horizon=50)
    b = FaultPlan.random(1234, n_replicas=3, horizon=50)
    assert a.faults == b.faults
    c = FaultPlan.random(1235, n_replicas=3, horizon=50)
    assert a.faults != c.faults or len(a.faults) != len(c.faults)
    back = FaultPlan.from_json(a.to_json())
    assert back.faults == a.faults
    with pytest.raises(ValueError, match="unknown fault kind"):
        Fault(kind="meteor", replica=0, step=1)
    with pytest.raises(ValueError, match="step >= 1"):
        Fault(kind="crash", replica=0, step=0)


def test_injected_crash_raises_at_replica_surface(tiny):
    cfg, params = tiny
    cm = ClusterManager.build(
        llama, cfg, params, ServingConfig(**sc_kwargs(replicas=1))
    )
    inj = cm.attach_faults(FaultPlan([Fault("crash", replica=0, step=2)]))
    rep = cm.replicas[0]
    rep.rm.submit(PROMPTS[0], max_new_tokens=4)
    rep.step()  # step 1: clean
    with pytest.raises(InjectedFault, match="injected crash"):
        rep.step()  # step 2: the scripted crash
    assert inj.fired and inj.fired[0]["kind"] == "crash"


# ---------------------------------------------------------------------------
# failover: replica death -> recompute re-admission on survivors


def test_single_replica_death_failover_bitwise(tiny):
    """The acceptance bar: kill one of two replicas mid-run — every
    re-admitted greedy request regenerates BITWISE the fault-free
    cluster run's tokens via recompute re-admission, with zero leaks
    and zero held slots on the survivor."""
    cfg, params = tiny
    sc = ServingConfig(**sc_kwargs(replicas=2, router_policy="round_robin"))
    base = [
        r.output_tokens
        for r in ClusterManager.build(llama, cfg, params, sc).generate(
            PROMPTS, max_new_tokens=8
        )
    ]
    cm = ClusterManager.build(llama, cfg, params, sc)
    cm.attach_faults(FaultPlan([Fault("crash", replica=1, step=3)]))
    outs = cm.generate(PROMPTS, max_new_tokens=8)
    assert all(r.error is None for r in outs)
    assert [r.output_tokens for r in outs] == base
    s = cm.cluster_stats()
    assert s["replica_down"] == 1
    assert s["failovers"] >= 1 and s["retries"] >= s["failovers"]
    moved = [r for r in outs if r.profile.retries > 0]
    assert moved, "the dead replica held requests that must have moved"
    assert all(r.profile.failover_replica_id == 0 for r in moved)
    assert all(r.profile.replica_id == 0 for r in moved)
    # the crash is persistent: the replica is DOWN (or half-open)
    assert cm.health_snapshot()[1] in ("down", "probing")
    assert cm.health_snapshot()[0] == "healthy"
    cm.check_no_leaks()
    no_held_slots(cm)


def test_transient_fault_absorbed_without_failover(tiny):
    """One transient step exception stays below the failure threshold:
    SUSPECT, not DOWN — nothing moves, outputs stay bitwise."""
    cfg, params = tiny
    cm = ClusterManager.build(
        llama, cfg, params,
        ServingConfig(**sc_kwargs(replicas=2, router_policy="round_robin")),
    )
    cm.attach_faults(FaultPlan([Fault("transient", replica=1, step=3)]))
    outs = cm.generate(PROMPTS, max_new_tokens=8)
    assert all(r.error is None for r in outs)
    assert [r.output_tokens for r in outs] == bare_outputs(tiny)
    s = cm.cluster_stats()
    assert s["replica_down"] == 0 and s["failovers"] == 0
    assert s["replica_suspect"] >= 1 and s["step_faults"] == 1
    assert cm.health_snapshot()[1] in ("suspect", "healthy")
    cm.check_no_leaks()


def test_probe_readmission_recovers_replica(tiny):
    """Two consecutive transient exceptions trip the breaker; after the
    backoff the replica half-opens (PROBING), routed traffic is the
    probe, and clean steps close the circuit — counted and observable
    via health_snapshot."""
    cfg, params = tiny
    cm = ClusterManager.build(
        llama, cfg, params,
        ServingConfig(**sc_kwargs(replicas=2, router_policy="round_robin")),
    )
    cm.attach_faults(
        FaultPlan([Fault("transient", replica=1, step=2, count=2)])
    )
    outs = cm.generate(PROMPTS, max_new_tokens=6)
    assert all(r.error is None for r in outs)
    s = cm.cluster_stats()
    assert s["replica_down"] == 1 and s["failovers"] >= 1
    # idle-step past the backoff: the breaker half-opens
    for _ in range(2 * cm.health.cfg.probe_backoff_steps):
        cm.step()
    assert cm.health_snapshot()[1] == "probing"
    assert cm.stats.probes >= 1
    # probe traffic: the transient fault is long gone, steps succeed
    outs2 = cm.generate(PROMPTS, max_new_tokens=6)
    assert all(r.error is None for r in outs2)
    assert [r.output_tokens for r in outs2] == bare_outputs(tiny, n_new=6)
    assert cm.health_snapshot()[1] == "healthy"
    assert cm.stats.replica_recoveries == 1
    # the recovered replica actually served traffic again
    assert any(r.profile.replica_id == 1 for r in outs2)
    cm.check_no_leaks()
    no_held_slots(cm)


def test_latency_spike_trips_breaker_and_fails_over(tiny):
    """A stalled replica (sustained injected latency) is circuit-broken
    like a crashed one; its requests recompute elsewhere, bitwise."""
    cfg, params = tiny
    cm = ClusterManager.build(
        llama, cfg, params,
        ServingConfig(**sc_kwargs(replicas=2, router_policy="round_robin")),
        health_config=HealthConfig(min_latency_samples=2,
                                   latency_spike_factor=5.0,
                                   latency_spike_steps=2,
                                   spike_down_steps=3),
    )
    cm.attach_faults(
        FaultPlan([Fault("latency", replica=1, step=4, count=8,
                         seconds=60.0)])
    )
    outs = cm.generate(PROMPTS, max_new_tokens=8)
    assert all(r.error is None for r in outs)
    assert [r.output_tokens for r in outs] == bare_outputs(tiny)
    s = cm.cluster_stats()
    assert s["replica_suspect"] >= 1
    assert s["replica_down"] == 1 and s["failovers"] >= 1
    cm.check_no_leaks()
    no_held_slots(cm)


def test_all_replicas_down_terminal_error_never_hangs(tiny):
    """Total outage: every request ends in a terminal error — the
    generate() loop exits, nothing is left PENDING, and a NEW submit
    against the dead cluster errors on arrival."""
    cfg, params = tiny
    cm = ClusterManager.build(
        llama, cfg, params,
        ServingConfig(**sc_kwargs(replicas=2, router_policy="round_robin")),
    )
    cm.attach_faults(FaultPlan([
        Fault("crash", replica=0, step=1),
        Fault("crash", replica=1, step=1),
    ]))
    outs = cm.generate(PROMPTS[:2], max_new_tokens=4)
    assert all(r.error is not None for r in outs)
    assert all(
        cm.requests[c].status is RequestStatus.ERROR for c in cm.requests
    )
    assert cm.health_snapshot().count("down") + \
        cm.health_snapshot().count("probing") == 2
    cid = cm.submit(PROMPTS[2], max_new_tokens=4)
    res = cm.result(cid)
    assert res.error is not None and "healthy" in res.error


def test_stream_across_failover_monotone_tokens(tiny):
    """Streamed token counts stay monotone across a failover: the
    re-admission's known tokens are exactly the flushed (= streamed)
    prefix, so nothing is re-sent and the final streams equal the
    fault-free outputs."""
    cfg, params = tiny
    cm = ClusterManager.build(
        llama, cfg, params,
        ServingConfig(**sc_kwargs(replicas=2, router_policy="round_robin")),
    )
    cm.attach_faults(FaultPlan([Fault("crash", replica=1, step=4)]))
    got, done = {}, set()
    for ev in cm.generate_stream(PROMPTS, max_new_tokens=8):
        if ev.done:
            assert ev.error is None
            assert ev.request_id not in done
            done.add(ev.request_id)
        else:
            got.setdefault(ev.request_id, []).append(ev.token)
    assert len(done) == len(PROMPTS)
    assert [got[c] for c in sorted(got)] == bare_outputs(tiny)
    cm.check_no_leaks()


def test_oom_fault_pressures_pool_without_leaks(tiny):
    """Injected page-pool pressure (pages stolen mid-run) surfaces as
    preemption/recompute — outputs stay bitwise (the PR-1 preemption
    guarantee), and releasing the stolen pages leaves a clean pool."""
    cfg, params = tiny
    kw = sc_kwargs(replicas=2, router_policy="round_robin",
                   max_cached_tokens=160)
    cm = ClusterManager.build(llama, cfg, params, ServingConfig(**kw))
    inj = cm.attach_faults(
        FaultPlan([Fault("oom", replica=0, step=3, count=4, pages=6)])
    )
    outs = cm.generate(PROMPTS, max_new_tokens=8)
    assert all(r.error is None for r in outs)
    assert [r.output_tokens for r in outs] == bare_outputs(
        tiny, max_cached_tokens=160
    )
    assert any(f["kind"] == "oom" for f in inj.fired)
    inj.release_all()
    cm.check_no_leaks()


# ---------------------------------------------------------------------------
# disaggregated faults: migration retry/rollback + pool fallbacks


def test_migration_failure_retries_then_succeeds(tiny):
    cfg, params = tiny
    base = bare_outputs(tiny)
    cm = ClusterManager.build(
        llama, cfg, params,
        ServingConfig(
            **sc_kwargs(replicas=2, prefill_replicas=1, decode_replicas=1)
        ),
    )
    cm.attach_faults(
        FaultPlan([Fault("migration", replica=0, step=1, count=1)])
    )
    outs = cm.generate(PROMPTS, max_new_tokens=8)
    assert all(r.error is None for r in outs)
    assert [r.output_tokens for r in outs] == base
    s = cm.cluster_stats()
    assert s["migration_failures"] == 1
    assert s["migrations"] == len(PROMPTS)  # every request still moved
    cm.check_no_leaks()
    no_held_slots(cm)


def test_migration_rollback_on_midtransfer_failure(tiny):
    """An exception AFTER adoption (mid page-transfer) rolls the
    destination back completely: no ghost request, no leaked pages —
    and the source still holds, so a retry succeeds."""
    cfg, params = tiny
    cm = ClusterManager.build(
        llama, cfg, params,
        ServingConfig(
            **sc_kwargs(replicas=2, prefill_replicas=1, decode_replicas=1)
        ),
    )
    src, dst = cm.replicas
    rid = src.rm.submit(list(range(1, 20)), GenerationConfig(max_new_tokens=1))
    src.rm.hold_on_finish(rid)
    while src.rm.step():
        pass
    src.rm.drain()
    orig_upload = dst.engine.upload_page

    def boom(*a, **k):
        raise RuntimeError("mid-transfer wire failure")

    dst.engine.upload_page = boom
    with pytest.raises(RuntimeError, match="mid-transfer"):
        migrate_request(src, dst, rid, GenerationConfig(max_new_tokens=4),
                        stats=cm.stats)
    assert dst.rm.requests == {}
    assert all(s is None for s in dst.rm.slots)
    assert dst.engine.pager.used_pages == 0
    dst.engine.upload_page = orig_upload
    rid2 = migrate_request(src, dst, rid, GenerationConfig(max_new_tokens=4),
                           stats=cm.stats)
    assert rid2 is not None
    src.rm.release_held(rid)
    cm.check_no_leaks()


def test_migration_queue_budget_drains_via_recompute(tiny):
    """Back-pressure: with a 1-deep migration queue and a saturated
    decode pool, overflow prefills release their held pages and drain
    through recompute re-admission — outputs bitwise the unbounded-hold
    cluster, zero parked holds at the end."""
    cfg, params = tiny
    prompts = [[(i * 13 + j * 3 + 5) % 64 + 2 for j in range(6)]
               for i in range(10)]

    def run(budget):
        cm = ClusterManager.build(
            llama, cfg, params,
            ServingConfig(**sc_kwargs(
                replicas=2, prefill_replicas=1, decode_replicas=1,
                migration_queue_budget=budget,
            )),
        )
        outs = cm.generate(prompts, max_new_tokens=12)
        assert all(r.error is None for r in outs)
        assert all(len(r.output_tokens) == 12 for r in outs)
        cm.check_no_leaks()
        no_held_slots(cm)
        return [r.output_tokens for r in outs], cm.cluster_stats()

    base, _ = run(None)
    outs, s = run(1)
    assert outs == base
    assert s["migration_queue_overflows"] >= 1
    assert s["migration_queue_peak"] <= 1
    assert s["retries"] >= s["migration_queue_overflows"]
    assert s["migration_queue_depth"] == 0


def test_decode_pool_death_falls_back_to_surviving_pool(tiny):
    """Decode-replica death: already-adopted requests re-prefill on the
    surviving (prefill) pool, and new/queued work serves single-phase
    there — non-disaggregated fallback, outputs still bitwise."""
    cfg, params = tiny
    cm = ClusterManager.build(
        llama, cfg, params,
        ServingConfig(
            **sc_kwargs(replicas=2, prefill_replicas=1, decode_replicas=1)
        ),
    )
    cm.attach_faults(FaultPlan([Fault("crash", replica=1, step=1)]))
    outs = cm.generate(PROMPTS, max_new_tokens=8)
    assert all(r.error is None for r in outs)
    assert [r.output_tokens for r in outs] == bare_outputs(tiny)
    s = cm.cluster_stats()
    assert s["replica_down"] == 1
    assert all(r.profile.replica_id == 0 for r in outs)
    cm.check_no_leaks()
    no_held_slots(cm)


def test_prefill_pool_death_routes_to_decode_pool(tiny):
    """Prefill-replica death: the router's pool is empty, so new
    submissions fall back single-phase onto the decode pool instead of
    shedding — and in-flight prefills fail over there too."""
    cfg, params = tiny
    cm = ClusterManager.build(
        llama, cfg, params,
        ServingConfig(
            **sc_kwargs(replicas=2, prefill_replicas=1, decode_replicas=1)
        ),
    )
    cm.attach_faults(FaultPlan([Fault("crash", replica=0, step=2)]))
    outs = cm.generate(PROMPTS, max_new_tokens=8)
    assert all(r.error is None for r in outs)
    assert [r.output_tokens for r in outs] == bare_outputs(tiny)
    s = cm.cluster_stats()
    assert s["replica_down"] == 1
    assert all(r.profile.replica_id == 1 for r in outs)
    # later submissions go straight to the surviving pool
    cid = cm.submit(PROMPTS[0], max_new_tokens=4)
    while not cm._terminal(cid):
        if not cm.step():
            break
    cm.drain()
    res = cm.result(cid)
    assert res.error is None and len(res.output_tokens) == 4
    assert cm.cluster_stats()["placements"].get("pool_fallback", 0) >= 1
    cm.check_no_leaks()
    no_held_slots(cm)


# ---------------------------------------------------------------------------
# seeded chaos: every request terminal, zero leaks on survivors


@pytest.mark.parametrize("seed,n_rep,kv_quant", [
    (11, 2, None),
    # the 3-replica int8 variant builds three quantized engines — kept
    # out of the tier-1 time budget; premerge gate 6/6 runs it unfiltered
    pytest.param(23, 3, "int8", marks=pytest.mark.slow),
])
def test_chaos_plan_every_request_terminal(tiny, seed, n_rep, kv_quant):
    """Random seeded FaultPlan over the replica pool: whatever fires
    (crashes, transients, spikes, migration failures, page OOM), every
    submitted request must reach a terminal state — a result or an
    error, never a hang — with clean pools on every surviving replica."""
    cfg, params = tiny
    kw = sc_kwargs(replicas=n_rep, router_policy="prefix",
                   prefix_caching=True)
    if kv_quant:
        kw["kv_quant"] = kv_quant
    cm = ClusterManager.build(llama, cfg, params, ServingConfig(**kw))
    inj = cm.attach_faults(FaultPlan.random(seed, n_rep, horizon=25))
    prompts = [[(i * 7 + j * 5 + 3) % 64 + 2 for j in range(4 + i % 6)]
               for i in range(9)]
    cids = [
        cm.submit(p, max_new_tokens=6, session_id=f"chat-{i % 3}")
        for i, p in enumerate(prompts)
    ]
    steps = 0
    late_submitted = False
    while any(not cm._terminal(c) for c in cids):
        steps += 1
        assert steps < 3000, (
            f"hang: health={cm.health_snapshot()} "
            f"stats={cm.cluster_stats()}"
        )
        cm.step()
        if steps == 8 and not late_submitted:
            # mid-run arrivals must route around whatever is broken
            late_submitted = True
            cids.append(cm.submit([5, 9, 2, 7], max_new_tokens=4))
    cm.drain()
    for c in cids:
        assert cm._terminal(c)
        res = cm.result(c)
        if res.error is None:
            assert 1 <= len(res.output_tokens) <= 6
    inj.release_all()
    cm.check_no_leaks()
    no_held_slots(cm)


def test_chaos_same_seed_same_fired_sequence(tiny):
    """Determinism end-to-end: the same seed over the same workload
    fires the same faults at the same replica-local steps and yields
    identical per-request outcomes."""
    cfg, params = tiny

    def run():
        cm = ClusterManager.build(
            llama, cfg, params,
            ServingConfig(**sc_kwargs(replicas=2,
                                      router_policy="round_robin")),
        )
        inj = cm.attach_faults(FaultPlan.random(77, 2, horizon=12))
        outs = cm.generate(PROMPTS, max_new_tokens=6)
        inj.release_all()
        return (
            [f for f in inj.fired],
            [(r.output_tokens, r.error is None) for r in outs],
        )

    fired_a, outs_a = run()
    fired_b, outs_b = run()
    assert fired_a == fired_b
    assert outs_a == outs_b


# ---------------------------------------------------------------------------
# satellites: SLO cold-rate guard + SpecInfer×cluster validation


def test_queue_delay_guards_cold_and_reset_rate(tiny):
    """The SLO queue-delay estimate must never divide by (or shed on) a
    zero/unsampled token-rate EMA: fresh replicas, single-sample rates
    and just-reset (probe re-admission) replicas all report 0."""
    cfg, params = tiny
    cm = ClusterManager.build(
        llama, cfg, params, ServingConfig(**sc_kwargs(replicas=1))
    )
    rep = cm.replicas[0]
    rep.rm.submit(PROMPTS[2], max_new_tokens=4)  # backlog without a rate
    assert rep.backlog_tokens() > 0
    assert rep.queue_delay_s() == 0.0
    # one sample is still cold; two make a denominator
    rep._rate, rep._rate_samples = 5.0, 1
    assert rep.queue_delay_s() == 0.0
    rep._rate_samples = 2
    assert rep.queue_delay_s() > 0.0
    # reset (DOWN -> abandon -> probe re-admission) goes cold again
    rep.reset_rate()
    assert rep.queue_delay_s() == 0.0
    while rep.rm.step():
        pass
    rep.rm.drain()


def test_validate_cluster_specinfer_rejects_disagg_only(tiny):
    # replicated clusters compose with SpecInfer now (per-replica SSM
    # mirrors, serve/cluster/replica.py + tests/test_adaptive_spec.py);
    # only the disaggregated prefill/decode pools still reject it —
    # the page-migration hand-off does not carry the draft caches
    ServingConfig(**sc_kwargs(replicas=2)).validate_cluster(specinfer=True)
    with pytest.raises(ValueError, match="SpecInfer"):
        ServingConfig(
            **sc_kwargs(replicas=2, prefill_replicas=1, decode_replicas=1)
        ).validate_cluster(specinfer=True)
    # 1 replica + ssms remains fine
    ServingConfig(**sc_kwargs()).validate_cluster(specinfer=True)
    # the new failover/back-pressure fields validate too
    with pytest.raises(ValueError, match="failover_retries"):
        ServingConfig(**sc_kwargs(failover_retries=-1)).validate_cluster()
    with pytest.raises(ValueError, match="migration_queue_budget"):
        ServingConfig(
            **sc_kwargs(migration_queue_budget=-2)
        ).validate_cluster()


def test_llm_compile_specinfer_disagg_fails_at_construction(tiny):
    from flexflow_tpu.serve.llm import LLM, SSM

    cfg, params = tiny
    llm = LLM(llama, cfg, params)
    ssm = SSM(llama, cfg, params)
    with pytest.raises(ValueError, match="SpecInfer"):
        llm.compile(
            ServingConfig(**sc_kwargs(
                replicas=2, prefill_replicas=1, decode_replicas=1,
                kv_layout="paged",
            )),
            ssms=[ssm],
        )
    assert llm.rm is None  # nothing was built before the raise
