"""Self-driving serving (serve/autotune/): cost model, traffic
estimator, offline search, and the live journaled autoscaler.

The contracts under test:

* **Cost model** — structural sanities the search and policy lean on:
  capacity is monotone in replicas, quantized KV multiplies the page
  budget, oversubscription only slows a candidate down.
* **Estimator** — bit-identical profiles from identical observation
  sequences (the replayable-decisions property), pre-envelope windows
  never fit garbage (ready() gates), wall clock enters ONLY at
  ``profile(step_time_s=...)``.
* **Search** — emits a ``validate_cluster``-accepted ServingConfig and
  never emits the SpecInfer × disaggregated combination the engine
  rejects.
* **Policy** — hysteresis (breach/clear streaks with a dead band),
  cooldown windows in cluster steps, dry-run/advise mode, every
  decision journaled — all over a scripted fake cost model, so the
  decision logic is tested in isolation.
* **E2E (slow)** — a real cluster under a deterministic bursty
  workload drives a journaled scale_out AND scale_in with zero
  lost/duplicated tokens, and ``ClusterManager.recover`` mid-scale-
  event rebuilds per the journal's begin→commit discipline.
"""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from flexflow_tpu.metrics import ClusterStats
from flexflow_tpu.models import llama
from flexflow_tpu.serve import ClusterManager, ServingConfig
from flexflow_tpu.serve.autotune import (
    Autoscaler,
    ModelGeometry,
    ServingCandidate,
    ServingCostModel,
    ServingPrediction,
    TrafficEstimator,
    TrafficProfile,
    search_serving_config,
)
from flexflow_tpu.serve.cluster import replay_journal


GEOM = ModelGeometry(
    hidden_size=512, num_layers=8, num_heads=8, num_kv_heads=8,
    intermediate_size=2048, vocab_size=32000,
)
TRAFFIC = TrafficProfile(
    arrival_rate_rps=50.0, prompt_len_p50=128.0, prompt_len_p99=512.0,
    output_len_p50=128.0, output_len_p99=256.0, prefix_share=0.25,
    spec_accept_rate=0.7,
)


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


# ---------------------------------------------------------------------------
# cost model units (no engine)


def test_capacity_monotone_in_replicas():
    cm = ServingCostModel(GEOM)
    caps = [
        cm.predict(ServingCandidate(replicas=n), TRAFFIC)
        .capacity_tokens_per_s
        for n in (1, 2, 3, 4)
    ]
    for lo, hi in zip(caps, caps[1:]):
        assert hi >= lo, f"capacity regressed with more replicas: {caps}"


def test_quantized_kv_multiplies_page_budget():
    cm = ServingCostModel(GEOM)
    fp = cm.predict(ServingCandidate(kv_quant=None), TRAFFIC)
    i8 = cm.predict(ServingCandidate(kv_quant="int8"), TRAFFIC)
    i4 = cm.predict(ServingCandidate(kv_quant="int4"), TRAFFIC)
    assert i8.kv_pages_capacity > fp.kv_pages_capacity
    assert i4.kv_pages_capacity > i8.kv_pages_capacity
    # the budget invariant: ~1.9x for int8, ~3.8x for int4
    assert i8.kv_pages_capacity >= 1.8 * fp.kv_pages_capacity
    assert i4.kv_pages_capacity >= 3.5 * fp.kv_pages_capacity


def test_oversubscription_slows_decode():
    cm = ServingCostModel(GEOM)
    cand = ServingCandidate()
    alone = cm.predict(cand, TRAFFIC)
    shared = cm.predict(cand, TRAFFIC, oversubscription=4.0)
    assert shared.decode_step_s > alone.decode_step_s
    assert shared.capacity_tokens_per_s < alone.capacity_tokens_per_s


def test_speculation_raises_commit_rate():
    cm = ServingCostModel(GEOM)
    plain = cm.predict(ServingCandidate(speculation=False), TRAFFIC)
    spec = cm.predict(ServingCandidate(speculation=True), TRAFFIC)
    # accept=0.7 over depth 4 commits well over one token per verify
    assert spec.capacity_tokens_per_s > plain.capacity_tokens_per_s


def test_infeasible_when_model_exceeds_hbm():
    huge = dataclasses.replace(GEOM, hidden_size=16384, num_layers=120,
                               num_heads=128, num_kv_heads=128,
                               intermediate_size=53248)
    pred = ServingCostModel(huge).predict(ServingCandidate(), TRAFFIC)
    assert not pred.feasible
    assert "HBM" in pred.reason


def test_geometry_from_model_config():
    cfg = llama.LLaMAConfig.tiny()
    g = ModelGeometry.from_model_config(cfg)
    assert g.num_layers == cfg.num_hidden_layers
    assert g.hidden_size == cfg.hidden_size
    assert g.param_count() > 0
    assert g.kv_bytes_per_token("int8") < g.kv_bytes_per_token(None)


# ---------------------------------------------------------------------------
# estimator units (no engine)


def _feed(est):
    for i in range(12):
        est.observe(
            submitted=3 * (i + 1),
            completions=[(100 + i, 40)] if i % 2 else [],
            queue_delay_s=0.002 * i,
            prefix_hits=5 * i, prefix_misses=2 * i,
            spec_accepted=7 * i, spec_drafted=10 * i,
        )


def test_estimator_deterministic():
    a, b = TrafficEstimator(), TrafficEstimator()
    _feed(a)
    _feed(b)
    assert a.snapshot() == b.snapshot()
    assert a.profile(step_time_s=0.01) == b.profile(step_time_s=0.01)


def test_estimator_pre_envelope_gating():
    est = TrafficEstimator(warmup_steps=8)
    assert not est.ready()
    # observations without completions never open the gate
    for i in range(10):
        est.observe(submitted=i)
    assert not est.ready()
    est.observe(submitted=11, completions=[(64, 16)])
    assert est.ready()
    # counters that go BACKWARD (a stats reset) clamp to zero deltas
    est.observe(submitted=0, prefix_hits=0, spec_drafted=0)
    assert est.snapshot()["arrivals_per_step"] >= 0.0


def test_estimator_wall_clock_only_at_the_edge():
    est = TrafficEstimator(warmup_steps=1)
    est.observe(submitted=4, completions=[(128, 64)])
    with pytest.raises(ValueError, match="step_time_s"):
        est.profile(step_time_s=0.0)
    p1 = est.profile(step_time_s=0.01)
    p2 = est.profile(step_time_s=0.02)
    # halving the step rate halves the fitted arrival rate — the
    # profile itself carries no clock of its own
    assert p1.arrival_rate_rps == pytest.approx(2 * p2.arrival_rate_rps)


def test_estimator_accept_rate_ema():
    est = TrafficEstimator(ema_alpha=0.5)
    est.observe(submitted=1, spec_accepted=7, spec_drafted=10)
    est.observe(submitted=2, spec_accepted=14, spec_drafted=20)
    assert 0.0 < est.spec_accept_rate() <= 0.7


# ---------------------------------------------------------------------------
# offline search


def test_search_emits_validate_cluster_accepted_config():
    best, report = search_serving_config(
        GEOM, TRAFFIC, chip_budget=8, slo_ttft_s=2.0, slo_tpot_s=0.1,
    )
    assert best is not None
    assert report.evaluated > 100
    sc = best.to_serving_config()
    sc.validate_cluster()  # must not raise — the emit contract
    assert sc.kv_layout == "paged"
    assert report.prediction.feasible
    assert report.summary().startswith("serving search:")


def test_every_emitted_candidate_builds_an_engine(tiny):
    """What the search emits, the engine accepts: every candidate of the
    leaderboard for the tiny geometry lowers to a ServingConfig whose
    fusions the family advertises, and one replica's InferenceEngine
    builds from it. (Until PR 31 the DEFAULT candidate lowered to a
    fusion the engine refused on a TPU, and only there.)"""
    from flexflow_tpu.serve import InferenceEngine

    cfg, params = tiny
    best, report = search_serving_config(
        ModelGeometry.from_model_config(cfg), TRAFFIC, chip_budget=8,
    )
    assert best is not None and report.table
    built = []
    for cand in [best] + [c for c, _ in report.table]:
        sc = cand.to_serving_config(
            max_sequence_length=96, cache_dtype=jnp.float32,
            replicas=1, prefill_replicas=0, decode_replicas=0,
        )
        assert set(sc.fused_decode) <= set(llama.FUSED_DECODE)
        if sc not in built:
            built.append(sc)
            InferenceEngine(llama, cfg, params, sc)  # must not raise
    assert built


def test_search_never_emits_spec_x_disagg():
    _, report = search_serving_config(GEOM, TRAFFIC, chip_budget=8)
    for cand, _pred in report.table:
        assert not (cand.speculation and cand.prefill_replicas), (
            "search leaderboard contains the SpecInfer x disaggregated "
            "combination validate_cluster rejects"
        )


def test_search_respects_chip_budget():
    best, report = search_serving_config(GEOM, TRAFFIC, chip_budget=4)
    assert best is not None and best.chips <= 4
    for cand, _pred in report.table:
        assert cand.chips <= 4


def test_search_infeasible_reports_none():
    huge = dataclasses.replace(GEOM, hidden_size=16384, num_layers=120,
                               num_heads=128, num_kv_heads=128,
                               intermediate_size=53248)
    best, report = search_serving_config(huge, TRAFFIC, chip_budget=1)
    assert best is None and report.best is None
    # the weight-headroom prune rejects every tp the budget allows
    assert report.pruned > 0


# ---------------------------------------------------------------------------
# policy units over a fake cost model (no engine)


class _FakeCost:
    """Scripted predictions: breach TTFT below ``calm_at`` replicas,
    comfortable at/above it."""

    def __init__(self, calm_at=2):
        self.calm_at = calm_at

    def predict(self, cand, profile, **kw):
        breach = cand.replicas < self.calm_at
        ttft = 9.0 if breach else 0.01
        return ServingPrediction(
            tokens_per_s=100.0 * cand.replicas,
            capacity_tokens_per_s=200.0 * cand.replicas,
            ttft_s_p50=ttft / 3, ttft_s_p99=ttft,
            tpot_s_p50=0.001, tpot_s_p99=0.002,
            queue_delay_s=ttft / 10, decode_step_s=0.001,
            hbm_bytes_per_chip=1e9, hbm_fill=0.1,
            kv_pages_capacity=1000, kv_pages_needed=10, page_fill=0.01,
            feasible=True,
        )


class _FakeRM:
    pass


class _FakeRep:
    def __init__(self, index):
        self.index = index
        self.role = "mixed"
        self.rm = _FakeRM()
        self.stats = type(
            "S", (), {"decode_tokens": 0, "prefix_hits": 0,
                      "prefix_misses": 0, "spec_accepted": 0,
                      "spec_drafted": 0},
        )()

    def rate_snapshot(self):
        return {"token_rate": 10.0, "rate_samples": 4.0,
                "backlog_tokens": 0.0, "queue_delay_s": 0.0}


class _FakeCM:
    def __init__(self, replicas=1, serving=None, journal=None):
        self.replicas = [_FakeRep(i) for i in range(replicas)]
        self.serving = serving or ServingConfig(
            autoscale="drive", slo_ttft_s=1.0,
            autoscale_max_replicas=4, kv_layout="paged",
        )
        self.stats = ClusterStats()
        self._draining = set()
        self.disaggregated = False
        self.prefill_pool = []
        self.decode_pool = []
        self.journal = journal
        self._step_counter = 0
        self._window = []

    def scale_out(self, *, role="mixed", **kw):
        self.replicas.append(_FakeRep(len(self.replicas)))
        self.stats.scale_outs += 1
        return len(self.replicas) - 1

    def begin_scale_in(self, pos):
        self._draining.add(self.replicas[pos].index)
        self.stats.scale_ins += 1

    def _routable_pos(self, pos):
        return self.replicas[pos].index not in self._draining

    def drain_completion_window(self):
        w, self._window = self._window, []
        return w


def _policy(cm, **kw):
    base = dict(
        cost_model=_FakeCost(),
        estimator=TrafficEstimator(warmup_steps=1),
        cooldown_steps=4, min_replicas=1, max_replicas=4,
        eval_interval_steps=1, breach_evals=2, clear_evals=3,
        step_time_s=0.01,
    )
    base.update(kw)
    return Autoscaler(cm, **base)


def _drive(cm, policy, steps, submit_per_step=1):
    out = []
    for _ in range(steps):
        cm._step_counter += 1
        cm.stats.submitted += submit_per_step
        cm._window.append((64, 32))
        out.append(policy.on_step(cm._step_counter))
    return [d for d in out if d is not None]


def test_policy_breach_streak_then_scale_out():
    cm = _FakeCM(replicas=1)
    policy = _policy(cm, cooldown_steps=1)
    decs = _drive(cm, policy, 1)
    assert decs == [], "acted on a single breach evaluation"
    decs = _drive(cm, policy, 1)
    assert [d.kind for d in decs] == ["scale_out"]
    assert decs[0].applied and len(cm.replicas) == 2
    assert cm.stats.scale_outs == 1
    assert cm.stats.autoscale_decisions == 1
    assert cm.stats.autoscale_predicted_tps > 0


def test_policy_scale_in_after_clear_streak_and_cooldown():
    cm = _FakeCM(replicas=2)
    policy = _policy(cm, cost_model=_FakeCost(calm_at=1))
    decs = _drive(cm, policy, 12)
    kinds = [d.kind for d in decs]
    assert kinds == ["scale_in"], kinds
    # clear_evals=3 means no action before eval 3; cooldown arms from
    # construction so the first action cannot precede step 4
    assert decs[0].step >= 4
    assert cm.stats.scale_ins == 1
    assert len(cm._draining) == 1
    # the retiree is the LAST-joined replica
    assert decs[0].detail["index"] == 1


def test_policy_cooldown_blocks_consecutive_actions():
    cm = _FakeCM(replicas=1)
    policy = _policy(cm, cooldown_steps=6, max_replicas=3,
                     clear_evals=99)
    decs = _drive(cm, policy, 20)
    steps = [d.step for d in decs if d.kind == "scale_out"]
    assert len(steps) == 1, (
        f"calm_at=2 fake: one scale_out should settle it, got {steps}"
    )
    # force permanent breach: even at the ceiling no second action
    policy.cost_model = _FakeCost(calm_at=99)
    decs = _drive(cm, policy, 20)
    steps = [d.step for d in decs]
    for a, b in zip(steps, steps[1:]):
        assert b - a >= 6, f"cooldown violated: {steps}"
    assert len(cm.replicas) == 3, "ceiling not respected"


def test_policy_hysteresis_dead_band():
    """Inside the band (holds the SLO but not with margin) the policy
    must HOLD — no flapping."""

    class _Band(_FakeCost):
        def predict(self, cand, profile, **kw):
            p = super().predict(cand, profile, **kw)
            # every size holds the 1.0s SLO at 0.8s — but never with
            # the 0.5 low_band margin
            return dataclasses.replace(p, ttft_s_p99=0.8)

    cm = _FakeCM(replicas=2)
    policy = _policy(cm, cost_model=_Band())
    assert _drive(cm, policy, 20) == []
    assert len(cm.replicas) == 2 and not cm._draining


def test_policy_dry_run_applies_nothing():
    cm = _FakeCM(replicas=1)
    policy = _policy(cm, dry_run=True)
    decs = _drive(cm, policy, 8)
    assert decs and all(not d.applied for d in decs)
    assert all(d.kind == "scale_out" for d in decs)
    assert len(cm.replicas) == 1 and cm.stats.scale_outs == 0
    assert cm.stats.autoscale_decisions == len(decs)


def test_policy_decisions_journaled(tmp_path):
    from flexflow_tpu.serve.cluster import RequestJournal

    path = str(tmp_path / "a.journal")
    journal = RequestJournal(path)
    cm = _FakeCM(replicas=1, journal=journal)
    policy = _policy(cm)
    decs = _drive(cm, policy, 4)
    journal.flush()
    journal.close()
    assert decs
    with open(path, "rb") as f:
        raw = f.read()
    assert b"autoscale" in raw and b"scale_out" in raw
    # the decision record is replay-INERT: unknown kinds are ignored
    state = replay_journal(path)
    assert state.entries == {} and state.members is None


def test_policy_validates_bands():
    cm = _FakeCM(replicas=1)
    with pytest.raises(ValueError, match="max_replicas"):
        _policy(cm, min_replicas=3, max_replicas=1)
    with pytest.raises(ValueError, match="low_band"):
        _policy(cm, low_band=1.5)


def test_policy_from_manager_requires_objective():
    with pytest.raises(ValueError, match="objective"):
        ServingConfig(autoscale="drive",
                      autoscale_max_replicas=2).validate_cluster()


# ---------------------------------------------------------------------------
# manager integration: completion window + per-replica counters


def test_completion_window_and_counters(tiny):
    cfg, params = tiny
    cm = ClusterManager.build(
        llama, cfg, params, ServingConfig(**sc_kwargs(replicas=2)),
    )
    cids = [cm.submit(p, max_new_tokens=6) for p in PROMPTS]
    while cm.step():
        pass
    cm.drain()
    cm.step()  # one more sweep after the drain settles stragglers
    window = cm.drain_completion_window()
    assert len(window) == len(PROMPTS)
    assert sorted(p for p, _o in window) == sorted(
        len(p) for p in PROMPTS
    )
    assert all(out > 0 for _p, out in window)
    # drained means drained
    assert cm.drain_completion_window() == []
    snap = cm.cluster_stats()
    rec = snap["arrivals_completions_per_replica"]
    assert sum(v["arrivals"] for v in rec.values()) == len(PROMPTS)
    assert sum(v["completions"] for v in rec.values()) == len(PROMPTS)
    assert snap["queue_delay_s_p50"] >= 0.0
    assert snap["autoscale_decisions"] == 0
    for c in cids:
        assert cm.result(c).error is None


def test_replica_rate_snapshot(tiny):
    cfg, params = tiny
    cm = ClusterManager.build(
        llama, cfg, params, ServingConfig(**sc_kwargs()),
    )
    rep = cm.replicas[0]
    snap = rep.rate_snapshot()
    # cold replica: the documented pre-envelope contract — no estimate
    assert snap == {"token_rate": 0.0, "rate_samples": 0.0,
                    "backlog_tokens": 0.0, "queue_delay_s": 0.0}
    cm.submit(PROMPTS[0], max_new_tokens=6)
    while cm.step():
        pass
    snap = rep.rate_snapshot()
    assert snap["token_rate"] > 0.0 and snap["rate_samples"] >= 2
    # the gate contract holds between the snapshot and the live method
    assert snap["queue_delay_s"] == rep.queue_delay_s()


def test_estimator_on_live_cluster_deterministic(tiny):
    cfg, params = tiny

    def run():
        cm = ClusterManager.build(
            llama, cfg, params, ServingConfig(**sc_kwargs()),
        )
        est = TrafficEstimator(warmup_steps=2)
        for p in PROMPTS:
            cm.submit(p, max_new_tokens=6)
        while cm.step():
            est.observe_cluster(cm)
        cm.drain()
        cm.step()
        est.observe_cluster(cm)
        return est

    a, b = run(), run()
    assert a.ready()
    sa, sb = a.snapshot(), b.snapshot()
    # queue_delay_s folds the replica's WALL-CLOCK-measured rate
    # estimate (Replica.rate_snapshot) and is telemetry, not replayable
    # state; every counter-derived statistic must be bit-identical
    sa.pop("queue_delay_s"), sb.pop("queue_delay_s")
    assert sa == sb


# ---------------------------------------------------------------------------
# e2e: the autoscaler drives journaled scale events under burst (slow)


class _BacklogCost(_FakeCost):
    """Breach while the live cluster has a backlog, comfortable once
    it drains — ties the scripted predictions to the actual workload
    so the e2e decisions are deterministic on the step clock."""

    def __init__(self, cm):
        self.cm = cm

    def predict(self, cand, profile, **kw):
        busy = len(self.cm._open_cids) > 2
        ttft = 9.0 if (busy and cand.replicas < 2) else 0.01
        return ServingPrediction(
            tokens_per_s=100.0 * cand.replicas,
            capacity_tokens_per_s=200.0 * cand.replicas,
            ttft_s_p50=ttft / 3, ttft_s_p99=ttft,
            tpot_s_p50=0.001, tpot_s_p99=0.002,
            queue_delay_s=ttft / 10, decode_step_s=0.001,
            hbm_bytes_per_chip=1e9, hbm_fill=0.1,
            kv_pages_capacity=1000, kv_pages_needed=10, page_fill=0.01,
            feasible=True,
        )


def _autoscale_serving(jdir, **kw):
    base = sc_kwargs(
        replicas=1, journal_dir=jdir, autoscale="drive",
        slo_ttft_s=1.0, autoscale_min_replicas=1,
        autoscale_max_replicas=2, autoscale_cooldown_steps=8,
    )
    base.update(kw)
    return ServingConfig(**base)


def _tune_policy(cm):
    """Deterministic e2e knobs: scripted cost model on the live
    backlog, eval every 2 steps, fast streaks, pinned step time."""
    a = cm.autoscaler
    a.cost_model = _BacklogCost(cm)
    a.estimator = TrafficEstimator(warmup_steps=2)
    a.eval_interval_steps = 2
    a.breach_evals = 2
    a.clear_evals = 2
    a.step_time_s = 0.01
    return a


@pytest.mark.slow
def test_autoscale_e2e_burst_scale_out_then_in(tiny, tmp_path):
    cfg, params = tiny
    serving = _autoscale_serving(str(tmp_path / "j"))
    cm = ClusterManager.build(llama, cfg, params, serving)
    assert cm.autoscaler is not None
    _tune_policy(cm)

    # burst: everything at once, more requests than batch slots
    burst = PROMPTS * 3
    cids = [cm.submit(p, max_new_tokens=8) for p in burst]
    steps = 0
    while any(not cm._terminal(c) for c in cids):
        steps += 1
        assert steps < 4000, "burst hung"
        if not cm.step():
            cm.drain()
    cm.drain()
    # idle steps past the cooldown let the clear streak drive scale_in
    for _ in range(60):
        cm.step()
        if cm.stats.scale_ins >= 1:
            break
    for _ in range(20):  # let the drain-based retirement commit
        cm.step()

    assert cm.stats.scale_outs >= 1, "no scale_out under burst"
    assert cm.stats.scale_ins >= 1, "no scale_in after the burst"
    assert cm.stats.autoscale_decisions >= 2
    kinds = [d.kind for d in cm.autoscaler.decisions]
    assert "scale_out" in kinds and "scale_in" in kinds
    assert kinds.index("scale_out") < kinds.index("scale_in")

    # zero lost/duplicated tokens: every request terminal-success, and
    # outputs BITWISE a static single-replica reference run
    outs = [list(cm.result(c).output_tokens) for c in cids]
    assert all(cm.result(c).error is None for c in cids)
    ref_cm = ClusterManager.build(
        llama, cfg, params, ServingConfig(**sc_kwargs(replicas=1)),
    )
    ref_cids = [ref_cm.submit(p, max_new_tokens=8) for p in burst]
    while ref_cm.step():
        pass
    ref_cm.drain()
    refs = [list(ref_cm.result(c).output_tokens) for c in ref_cids]
    assert outs == refs, "autoscaled outputs drifted from the reference"

    # the journal carries both the decision audit trail AND the scale
    # events' members snapshots
    cm.journal.flush()
    path = cm.journal.path
    with open(path, "rb") as f:
        raw = f.read()
    assert b"autoscale" in raw
    state = replay_journal(path)
    assert state.members is not None


@pytest.mark.slow
def test_autoscale_recover_mid_scale_event(tiny, tmp_path):
    """SIGKILL between a scale_in's begin and its commit: the journal
    replays the event as never-happened (membership keeps BOTH
    replicas) and every journaled request still finishes bitwise."""
    cfg, params = tiny
    serving = _autoscale_serving(str(tmp_path / "j"))
    cm = ClusterManager.build(llama, cfg, params, serving)
    _tune_policy(cm)

    burst = PROMPTS * 3
    cids = [cm.submit(p, max_new_tokens=8) for p in burst]
    # drive until the policy has scaled out AND begun a scale_in, then
    # "crash" before the next step's maybe_retire commits it (the
    # scale_ins counter only increments AT the commit — _draining is
    # the begin-without-commit window)
    steps = 0
    while not cm._draining:
        alive = cm.step()
        steps += 1
        assert steps < 4000, (
            f"never reached mid-scale-event (scale_outs="
            f"{cm.stats.scale_outs})"
        )
        if not alive and not cm._draining:
            cm.drain()
    assert cm.stats.scale_outs >= 1
    assert len(cm._draining) == 1, "scale_in should still be draining"
    # crash NOW: no more steps, no retire, no commit — journal holds a
    # begin without a commit plus the scale_out's committed snapshot
    cm.journal.flush()
    del cm

    cm2 = ClusterManager.recover(llama, cfg, params, serving)
    # the committed scale_out survives; the uncommitted scale_in
    # replays as never-happened
    assert len(cm2.replicas) == 2
    assert cm2._draining == set()
    assert cm2.autoscaler is not None
    _tune_policy(cm2)
    steps = 0
    while any(not cm2._terminal(c) for c in cids):
        steps += 1
        assert steps < 4000, "recovered requests hung"
        if not cm2.step():
            cm2.drain()
    cm2.drain()
    outs = [list(cm2.result(c).output_tokens) for c in cids]
    assert all(cm2.result(c).error is None for c in cids)
    ref_cm = ClusterManager.build(
        llama, cfg, params, ServingConfig(**sc_kwargs(replicas=1)),
    )
    ref_cids = [ref_cm.submit(p, max_new_tokens=8) for p in burst]
    while ref_cm.step():
        pass
    ref_cm.drain()
    refs = [list(ref_cm.result(c).output_tokens) for c in ref_cids]
    assert outs == refs, "recovered outputs drifted from the reference"
    cm2.check_no_leaks()


@pytest.mark.slow
def test_autoscale_advise_mode_applies_nothing_e2e(tiny, tmp_path):
    cfg, params = tiny
    serving = _autoscale_serving(str(tmp_path / "j"), autoscale="advise")
    cm = ClusterManager.build(llama, cfg, params, serving)
    assert cm.autoscaler is not None and cm.autoscaler.dry_run
    _tune_policy(cm)
    cids = [cm.submit(p, max_new_tokens=8) for p in PROMPTS * 3]
    steps = 0
    while any(not cm._terminal(c) for c in cids):
        steps += 1
        assert steps < 4000, "advise-mode requests hung"
        if not cm.step():
            cm.drain()
    cm.drain()
    for _ in range(20):
        cm.step()
    assert cm.stats.autoscale_decisions >= 1, "advise mode went silent"
    assert cm.stats.scale_outs == 0 and cm.stats.scale_ins == 0
    assert len(cm.replicas) == 1
    assert all(not d.applied for d in cm.autoscaler.decisions)
    assert all(cm.result(c).error is None for c in cids)


# ---------------------------------------------------------------------------
# PR-19 satellite: the autoscaler drive loop under the lock sanitizer —
# decisions and outputs BITWISE identical sanitizer-on vs -off, zero
# findings. Gate 14 selects this by the `locks_sanitizer` fragment.


@pytest.mark.slow
def test_locks_sanitizer_autoscale_drive_bitwise(tiny, tmp_path):
    from flexflow_tpu.analysis.locks import (
        active_lock_sanitizer,
        disable_lock_sanitizer,
    )

    cfg, params = tiny
    burst = PROMPTS * 3

    def drive(jdir, sanitizers):
        serving = _autoscale_serving(jdir, replica_transport="loopback",
                                     sanitizers=sanitizers)
        cm = ClusterManager.build(llama, cfg, params, serving)
        assert cm.autoscaler is not None
        _tune_policy(cm)
        cids = [cm.submit(p, max_new_tokens=8) for p in burst]
        steps = 0
        while any(not cm._terminal(c) for c in cids):
            steps += 1
            assert steps < 4000, "burst hung"
            if not cm.step():
                cm.drain()
        cm.drain()
        for _ in range(60):
            cm.step()
            if cm.stats.scale_ins >= 1:
                break
        outs = [list(cm.result(c).output_tokens) for c in cids]
        kinds = [d.kind for d in cm.autoscaler.decisions]
        return outs, kinds, cm.stats.autoscale_decisions

    try:
        assert active_lock_sanitizer() is None
        base = drive(str(tmp_path / "off"), ())
        assert active_lock_sanitizer() is None
        sanitized = drive(str(tmp_path / "on"), ("locks",))
        san = active_lock_sanitizer()
        assert san is not None, "ServingConfig wiring did not enable"
        assert san.findings == [], "\n".join(san.findings)
        assert san.acquisitions > 0
        assert sanitized == base, (
            "lock sanitizer changed autoscaler drive-loop behavior"
        )
    finally:
        disable_lock_sanitizer()
