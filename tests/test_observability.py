"""Observability layer (flexflow_tpu/obs): cluster-wide request
tracing, metrics export, and the failure flight recorder.

The load-bearing scenario is ISSUE 13's acceptance run: a
fault-injected (``FaultPlan`` transport partition) multi-replica run
over the loopback transport must produce (1) ONE stitched Chrome-trace
JSON in which a migrated request's spans appear under a single trace id
across both replicas and the wire hop, (2) a Prometheus text snapshot
passing the counter drift guard, and (3) a flight-recorder dump for the
tripped replica whose final events match the health machine's recorded
transition — all asserted deterministically (step clocks, never wall
time). And the inverse contract: with tracing DISABLED, the sync
scheduler's dispatched-programs-per-decode-step count and step-loop
host allocations are unchanged vs a no-obs run.

Timestamps asserted here compare ``perf_counter`` stamps within ONE
process (in-process and loopback clusters); cross-process stamps are
not comparable and are not asserted.
"""
import dataclasses
import json
import logging
import subprocess
import sys
import time
import tracemalloc
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu import logging_utils
from flexflow_tpu.models import llama
from flexflow_tpu.obs import (
    ExportDriftError,
    FlightRecorder,
    NULL_TRACER,
    TraceBuffer,
    attach_observability,
    check_export_coverage,
    chrome_trace,
    prometheus_text,
    write_chrome_trace,
    write_prometheus,
)
from flexflow_tpu.obs import builds as obs_builds
from flexflow_tpu.obs import export as obs_export
from flexflow_tpu.obs.flight_recorder import redact_event
from flexflow_tpu.obs.tracer import STEP_SPANS, NullTracer
from flexflow_tpu.profiling import StepTimes
from flexflow_tpu.serve import (
    ClusterManager,
    InferenceEngine,
    RequestManager,
    ServingConfig,
    SpecConfig,
    SpecInferManager,
)
from flexflow_tpu.serve.cluster import Fault, FaultPlan, HealthState


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


def make_rm(tiny, **kw):
    cfg, params = tiny
    return RequestManager(
        InferenceEngine(llama, cfg, params, ServingConfig(**sc_kwargs(**kw)))
    )


def assert_profile_times(res):
    """The ProfileInfo timestamp invariants every committed-output path
    must satisfy: start <= first_token <= finish, first_token stamped."""
    p = res.profile
    assert res.error is None, res.error
    assert res.output_tokens, "no committed output"
    assert p.start_time > 0
    assert p.first_token_time > 0, (
        "first_token_time missing on a committed-output path"
    )
    assert p.finish_time > 0
    assert p.start_time <= p.first_token_time <= p.finish_time, (
        p.start_time, p.first_token_time, p.finish_time,
    )
    assert p.ttft_s >= 0 and p.latency_s >= p.ttft_s


# ---------------------------------------------------------------------------
# tracer units


def test_tracer_dual_clock_lanes_and_spans():
    buf = TraceBuffer()
    steps = [7]
    tr = buf.tracer("laneA", clock=lambda: steps[0])
    tr.event("admit", trace_id=3, rid=9)
    steps[0] = 8
    with tr.span("work", trace_id=3, lane="laneB"):
        pass
    a, b = buf.events
    assert a["name"] == "admit" and a["lane"] == "laneA"
    assert a["trace_id"] == 3 and a["step"] == 7 and a["dur"] == 0.0
    assert a["attrs"] == {"rid": 9}
    assert a["t"] > 0  # the wall half of the dual clock
    assert b["name"] == "work" and b["lane"] == "laneB"
    assert b["step"] == 8 and b["dur"] >= 0.0


def test_buffer_capacity_bound_drain_and_extend():
    buf = TraceBuffer(capacity=3)
    tr = buf.tracer("x")
    for i in range(5):
        tr.event(f"e{i}")
    assert [e["name"] for e in buf.events] == ["e2", "e3", "e4"]
    assert buf.dropped == 2
    shipped = buf.drain()
    assert buf.events == [] and len(shipped) == 3
    # extend re-tags only untagged lanes (envelope merge semantics)
    buf.extend([{"name": "r", "lane": "", "trace_id": 1, "t": 0.0,
                 "step": 0, "dur": 0.0}], lane="replica9")
    assert buf.events[0]["lane"] == "replica9"


def test_null_tracer_disabled_and_safe():
    assert NULL_TRACER.enabled is False
    NULL_TRACER.event("anything", x=1)  # safe no-op even unguarded
    with NULL_TRACER.span("s"):
        pass


# ---------------------------------------------------------------------------
# exporters


def test_chrome_trace_lane_pids_and_args():
    events = [
        {"name": "a", "lane": "replica0", "trace_id": 5, "t": 1.0,
         "step": 2, "dur": 0.5, "attrs": {"k": 1}},
        {"name": "b", "lane": "wire", "trace_id": 5, "t": 2.0,
         "step": 3, "dur": 0.0},
    ]
    doc = chrome_trace(events)
    meta = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    slices = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    pid_names = {e["pid"]: e["args"]["name"] for e in meta}
    assert sorted(pid_names.values()) == ["replica0", "wire"]
    assert len(slices) == 2
    a = slices[0]
    assert a["ts"] == 1.0e6 and a["dur"] == 0.5e6 and a["tid"] == 5
    assert a["args"] == {"step": 2, "trace_id": 5, "k": 1}
    # one trace id, two lanes: the stitching property the UI shows
    assert {e["pid"] for e in slices} == set(pid_names)


def test_prometheus_text_counters_labels_and_profiles():
    from flexflow_tpu.metrics import ClusterStats, SchedulerStats
    from flexflow_tpu.serve.batch_config import ProfileInfo

    sched = SchedulerStats()
    sched.admitted = 3
    cs = ClusterStats()
    cs.migrations = 2
    cs.record_placement("prefix")
    prof = ProfileInfo(start_time=1.0, first_token_time=1.5,
                       finish_time=2.0, llm_decoding_steps=4)
    text = prometheus_text(
        scheduler={"0": sched}, cluster=cs, profiles=[prof],
    )
    assert '# TYPE flexflow_scheduler_admitted counter' in text
    assert 'flexflow_scheduler_admitted{replica="0"} 3' in text
    assert 'flexflow_cluster_migrations 2' in text
    assert 'flexflow_cluster_placements{how="prefix"} 1' in text
    assert 'flexflow_requests_total 1' in text
    assert 'flexflow_request_llm_decoding_steps_sum 4' in text
    assert 'flexflow_request_latency_seconds_sum 1' in text
    assert 'flexflow_request_ttft_seconds_sum 0.5' in text


def test_export_drift_guard_passes_on_current_fields():
    check_export_coverage()


def test_export_drift_guard_catches_missing_and_stale(monkeypatch):
    # a counter someone "forgot" to export -> missing
    monkeypatch.setattr(
        obs_export, "SCHED_COUNTERS",
        frozenset(obs_export.SCHED_COUNTERS - {"admitted"}),
    )
    with pytest.raises(ExportDriftError, match="admitted"):
        check_export_coverage()
    # an exporter entry for a field that no longer exists -> stale
    monkeypatch.setattr(
        obs_export, "SCHED_COUNTERS",
        frozenset(obs_export.SCHED_COUNTERS | {"admitted", "bogus_field"}),
    )
    with pytest.raises(ExportDriftError, match="bogus_field"):
        check_export_coverage()


@pytest.mark.parametrize("layout, ladder", [("paged", (8, 16)), ("dense", ())])
def test_rung_trims_on_every_surface(tiny, layout, ladder):
    """The steps that gave prompt tokens up to stay on a rung of the
    engine's ladder (ISSUE 61): in ``snapshot()``, on the report line
    beside the steps by width, on the scrape surface beside the
    ``pack_fill`` counters, and on each ``mixed_step`` event; all zero
    on an engine whose mixed step has no ladder."""
    rm = make_rm(tiny, kv_layout=layout)
    assert rm.engine.pack_ladder(8) == ladder
    buf = attach_observability(rm)
    rng = np.random.default_rng(61)
    prompts = [rng.integers(1, 200, 20 + 3 * i).tolist() for i in range(7)]
    outs = rm.generate(prompts, max_new_tokens=8)
    assert all(o.error is None for o in outs)
    s = rm.stats
    snap = s.snapshot()
    assert snap["rung_trims"] == s.rung_trims
    assert snap["rung_trim_tokens"] == s.rung_trim_tokens
    assert f" trims={s.rung_trims}/{s.rung_trim_tokens}tok" in s.report()
    text = prometheus_text(scheduler={"0": s})
    assert "# TYPE flexflow_scheduler_rung_trims counter" in text
    assert f'flexflow_scheduler_rung_trims{{replica="0"}} {s.rung_trims}' in text
    assert (f'flexflow_scheduler_rung_trim_tokens{{replica="0"}} '
            f'{s.rung_trim_tokens}') in text
    assert {"rung_trims", "rung_trim_tokens", "step_tokens_real",
            "step_tokens_width"} <= obs_export.SCHED_COUNTERS
    steps = [e for e in buf.events if e["name"] == "mixed_step"]
    assert len(steps) == s.mixed_steps > 0
    given = [e["attrs"]["trimmed"] for e in steps]
    assert sum(given) == s.rung_trim_tokens
    assert sum(g > 0 for g in given) == s.rung_trims
    if ladder:
        assert 0 < s.rung_trims <= s.rung_trim_tokens
        assert all(0 <= g <= 4 for g in given)    # the slots at most
    else:
        assert s.rung_trims == s.rung_trim_tokens == 0


# ---------------------------------------------------------------------------
# flight recorder units


def test_flight_recorder_ring_bound_redaction_and_dump(tmp_path):
    rec = FlightRecorder(capacity=4, out_dir=str(tmp_path))
    buf = TraceBuffer()
    buf.recorder = rec
    tr = buf.tracer("replica0")
    for i in range(10):
        tr.event(f"e{i}", tokens=[1, 2, 3], prompt="secret", n=i)
    tr.event("boom", lane="replica1")
    assert [e["name"] for e in rec.events("replica0")] == [
        "e6", "e7", "e8", "e9",
    ]
    doc = rec.dump("replica0", "replica_down", step=12,
                   extra={"down_at_step": 12})
    assert doc["reason"] == "replica_down" and doc["step"] == 12
    assert len(doc["events"]) == 4
    for ev in doc["events"]:
        attrs = ev.get("attrs") or {}
        assert "tokens" not in attrs and "prompt" not in attrs, (
            "user content leaked into a flight-recorder dump"
        )
        assert attrs.get("redacted") is True
        assert "n" in attrs  # non-content attrs survive
    # written to disk, JSON round-trips
    assert rec.paths and rec.dumps_for("replica0") == [doc]
    with open(rec.paths[0]) as f:
        assert json.load(f)["reason"] == "replica_down"
    # redact_event leaves content-free events untouched
    plain = {"name": "x", "lane": "l", "trace_id": 1, "t": 0.0,
             "step": 0, "dur": 0.0}
    assert redact_event(plain) == plain


# ---------------------------------------------------------------------------
# disabled mode is free (the acceptance inverse)


@pytest.mark.parametrize("steady", [False, True], ids=["cold", "steady"])
def test_disabled_tracing_is_free_on_the_sync_scheduler(tiny, steady):
    """With the null tracer: (a) no event dict is ever built — every
    event site guards on ``.enabled`` before building arguments (proven
    by making ``NullTracer.event`` raise) — and the phase spans of a
    step, called unguarded, are bare profiler annotations of the six
    ``ff.step.*`` names' own (recorded only while a profiler session is
    open; none is here); (b) the sync scheduler's
    dispatched-programs-per-decode-step count is unchanged vs a traced
    run; (c) no buffer is touched: the step loop keeps NOTHING from
    obs/ frames. ``steady``: the same on a server whose programs are
    all built, and there the build log (obs/builds.py) does nothing
    either — its wrapper is not entered (it runs when a program is
    traced, never at a dispatch), its listener hears no event, its
    frames keep nothing and its counters stand."""
    kw = dict(kv_layout="dense", continuous_batching=False)
    rm_off = make_rm(tiny, **kw)
    obs_files = ["*obs*tracer.py", "*obs*export.py",
                 "*obs*flight_recorder.py"]
    heard = []

    def listen(event, *a, **k):
        heard.append(event)

    def no_build(self, name, *a, **k):
        raise AssertionError(f"{name} traced on a server that had built it")

    old_tracing = obs_builds.BuildLog.tracing
    if steady:
        assert all(o.error is None for o in
                   rm_off.generate(PROMPTS, max_new_tokens=6))
        obs_files.append("*obs*builds.py")
        obs_builds.BuildLog.tracing = no_build
        jax.monitoring.register_event_duration_secs_listener(listen)
    built = dataclasses.replace(rm_off.stats)
    dispatches_before = rm_off.engine.dispatch_count
    # (a) a NullTracer.event call anywhere in the step loop would raise
    def _boom(self, *a, **k):
        raise AssertionError(
            "tracer invoked while disabled — an event site is "
            "missing its `.enabled` guard"
        )
    spans = []

    def _bare(self, name, **kw):
        assert not kw, "a step span takes a name and nothing else"
        spans.append((name, type(old_span(self, name))))
        return old_span(self, name)

    old_event, old_span = NullTracer.event, NullTracer.span
    NullTracer.event = _boom
    NullTracer.span = _bare
    try:
        # (c) measured around the run: zero allocations from obs/ code
        tracemalloc.start()
        outs_off = rm_off.generate(PROMPTS, max_new_tokens=6)
        snap = tracemalloc.take_snapshot()
    finally:
        tracemalloc.stop()
        NullTracer.event = old_event
        NullTracer.span = old_span
        if steady:
            obs_builds.BuildLog.tracing = old_tracing
            jax.monitoring.unregister_event_duration_listener(listen)
    obs_allocs = snap.filter_traces(
        [tracemalloc.Filter(True, pattern) for pattern in obs_files]
    ).statistics("filename")
    assert not obs_allocs, (
        f"disabled tracing allocated host memory: {obs_allocs}"
    )
    if steady:
        assert not heard, f"a built server's steps built: {set(heard)}"
        s_now = rm_off.stats
        assert (s_now.compiles, s_now.retraces, s_now.builds) == (
            built.compiles, built.retraces, built.builds)
        assert s_now.build_in_step_s == built.build_in_step_s
    dispatches_off = rm_off.engine.dispatch_count - dispatches_before
    assert all(o.error is None for o in outs_off)
    # the dense layout has no page reservation; prefill goes through
    # the sync step, decode-only iterations through the pipeline
    assert {n for n, _ in spans} == set(STEP_SPANS) - {"step.reserve"}
    assert {t for _, t in spans} == {jax.profiler.TraceAnnotation}

    # (b) the traced run dispatches the SAME device programs (tracing
    # is host-side observation, never a different step sequence) and
    # its outputs are bitwise identical
    rm_on = make_rm(tiny, **kw)
    attach_observability(rm_on)
    outs_on = rm_on.generate(PROMPTS, max_new_tokens=6)
    assert [o.output_tokens for o in outs_on] == [
        o.output_tokens for o in outs_off
    ]
    assert rm_on.engine.dispatch_count == dispatches_off


# ---------------------------------------------------------------------------
# single-engine lifecycle spans + ProfileInfo invariants (incremental)


def test_single_engine_lifecycle_spans_and_profile(tiny):
    rm = make_rm(tiny)
    buf = attach_observability(rm)
    outs = rm.generate(PROMPTS, max_new_tokens=6)
    for o in outs:
        assert_profile_times(o)  # satellite: incremental path
    names = {e["name"] for e in buf.events}
    assert {"admit", "prefill_chunk", "flush", "first_token",
            "terminal", "dispatch"} <= names
    assert ("mixed_step" in names) or ("decode_step" in names)
    # without a cluster the rid IS the trace id, and the lifecycle
    # reads in order on the deterministic step clock
    rid = outs[0].request_id
    mine = [e for e in buf.events if e["trace_id"] == rid]
    assert [e["name"] for e in mine][0] == "admit"
    assert [e["name"] for e in mine][-1] == "terminal"
    steps = [e["step"] for e in mine]
    assert steps == sorted(steps), "step clock must be monotone"
    assert all(e["lane"] == "engine" for e in mine)
    # the engine's dispatch chokepoint traced every device program
    dispatch_events = [e for e in buf.events if e["name"] == "dispatch"]
    assert len(dispatch_events) == rm.engine.dispatch_count


def test_spec_draft_verify_spans_and_profile(tiny):
    """SpecInfer emits draft/verify spans; speculative committed
    outputs satisfy the ProfileInfo timestamp invariants (satellite)."""
    cfg, params = tiny
    mgr = SpecInferManager(
        InferenceEngine(llama, cfg, params,
                        ServingConfig(**sc_kwargs(kv_layout="dense"))),
        None,
        SpecConfig(2, 3, draft="early_exit", draft_layers=1),
    )
    buf = attach_observability(mgr)
    outs = mgr.generate(PROMPTS, max_new_tokens=8)
    for o in outs:
        assert_profile_times(o)  # satellite: speculative path
    names = {e["name"] for e in buf.events}
    assert "spec_draft" in names and "spec_verify" in names
    verifies = [e for e in buf.events if e["name"] == "spec_verify"]
    assert {e["trace_id"] for e in verifies} == {
        o.request_id for o in outs
    }
    assert all(
        e["attrs"]["accepted"] <= e["attrs"]["drafted"] for e in verifies
    )


# ---------------------------------------------------------------------------
# ProfileInfo invariants on the cluster recovery paths (satellite)


def test_profile_invariants_recompute_after_failover(tiny):
    cfg, params = tiny
    sc = ServingConfig(**sc_kwargs(replicas=2,
                                   router_policy="round_robin"))
    cm = ClusterManager.build(llama, cfg, params, sc)
    cm.attach_faults(FaultPlan([Fault("crash", replica=1, step=4)]))
    outs = cm.generate(PROMPTS, max_new_tokens=6)
    assert cm.cluster_stats()["failovers"] >= 1
    for o in outs:
        assert_profile_times(o)
    moved = [o for o in outs if o.profile.retries > 0]
    assert moved, "no request actually failed over"


def test_profile_invariants_migrated_disaggregated(tiny):
    cfg, params = tiny
    sc = ServingConfig(**sc_kwargs(replicas=2, prefill_replicas=1,
                                   decode_replicas=1))
    cm = ClusterManager.build(llama, cfg, params, sc)
    outs = cm.generate(PROMPTS, max_new_tokens=6)
    assert cm.cluster_stats()["migrations"] == len(PROMPTS)
    for o in outs:
        assert_profile_times(o)
        assert o.profile.replica_id == 1  # decode home


# ---------------------------------------------------------------------------
# the acceptance scenario: fault-injected loopback disaggregated run


def _run_fault_scenario(tiny):
    """1 prefill + 1 decode replica over the LOOPBACK transport; every
    request migrates prefill→decode over the wire, then a scripted
    transport PARTITION kills the decode replica at its replica-local
    step 3 — its adopted requests fail over (recompute) back to the
    surviving pool and still complete. Deterministic: the partition is
    keyed to the replica-local step clock, health transitions count
    cluster steps, and the workload is fixed."""
    cfg, params = tiny
    sc = ServingConfig(**sc_kwargs(replicas=2, prefill_replicas=1,
                                   decode_replicas=1,
                                   replica_transport="loopback"))
    cm = ClusterManager.build(llama, cfg, params, sc)
    recorder = FlightRecorder(capacity=128)
    buf = attach_observability(cm, recorder=recorder)
    cm.attach_faults(FaultPlan([
        Fault("partition", replica=1, step=3, count=100000),
    ]))
    outs = cm.generate(PROMPTS, max_new_tokens=6)
    return cm, recorder, buf, outs


@pytest.fixture(scope="module")
def fault_run(tiny):
    return _run_fault_scenario(tiny)


def test_fault_run_completes_through_failover(fault_run):
    cm, recorder, buf, outs = fault_run
    assert all(o.error is None for o in outs)
    assert all(len(o.output_tokens) == 6 for o in outs)
    st = cm.cluster_stats()
    assert st["migrations"] == len(PROMPTS)
    assert st["rpc_errors"] > 0 and st["replica_down"] >= 1
    assert cm.health[1].state is HealthState.DOWN


def test_fault_run_trace_stitches_across_replicas_and_wire(
    fault_run, tmp_path,
):
    """ONE Chrome trace; a migrated request's spans under a SINGLE
    trace id across the prefill replica, the wire hop, and the decode
    replica (plus the router lane)."""
    cm, recorder, buf, outs = fault_run
    for cid in (o.request_id for o in outs):
        lanes = {e["lane"] for e in buf.events if e["trace_id"] == cid}
        assert {"replica0", "wire", "replica1", "router"} <= lanes, (
            f"request {cid} spans are not stitched: {lanes}"
        )
        mine = {e["name"] for e in buf.events if e["trace_id"] == cid}
        assert {"admit", "wire_migrate", "adopt", "place"} <= mine
    # failover is visible on the router lane; the partitioned RPCs and
    # their retries are visible on the wire lane
    names = {e["name"] for e in buf.events}
    assert {"failover", "health", "rpc", "rpc_retry", "wire"} <= names
    # the exported JSON preserves the stitching: a migrated request's
    # tid appears under the pids of both replicas AND the wire lane
    path = str(tmp_path / "trace.json")
    write_chrome_trace(path, buf)
    with open(path) as f:
        doc = json.load(f)
    pid_names = {
        e["pid"]: e["args"]["name"]
        for e in doc["traceEvents"] if e["ph"] == "M"
    }
    cid = outs[0].request_id
    lanes_of_cid = {
        pid_names[e["pid"]]
        for e in doc["traceEvents"]
        if e["ph"] == "X" and e["args"].get("trace_id") == cid
    }
    assert {"replica0", "wire", "replica1"} <= lanes_of_cid


def test_fault_run_prometheus_snapshot_passes_drift_guard(
    fault_run, tmp_path,
):
    cm, recorder, buf, outs = fault_run
    path = str(tmp_path / "metrics.prom")
    text = write_prometheus(
        path,
        scheduler={str(r.index): r.rm.stats for r in cm.replicas},
        cluster=cm.stats,
        profiles=[o.profile for o in outs],
    )
    assert f"flexflow_cluster_migrations {len(PROMPTS)}" in text
    assert "flexflow_cluster_rpc_errors" in text
    assert 'flexflow_scheduler_admitted{replica="0"}' in text
    assert f"flexflow_requests_total {len(PROMPTS)}" in text
    with open(path) as f:
        assert f.read() == text


def test_fault_run_flight_recorder_matches_health_machine(fault_run):
    """The tripped replica's dump ends with EXACTLY the transition the
    health machine recorded: a 'health' event, state 'down', at the
    machine's down_at_step — compared on the deterministic step clock."""
    cm, recorder, buf, outs = fault_run
    dumps = recorder.dumps_for("replica1")
    assert dumps, "no flight-recorder dump for the tripped replica"
    first = dumps[0]
    assert first["reason"] == "replica_down"
    assert first["health_state"] == "down"
    last = first["events"][-1]
    assert last["name"] == "health"
    assert last["attrs"]["state"] == "down"
    assert last["step"] == first["down_at_step"], (
        "dump's final event does not match the health machine's "
        f"recorded trip: {last} vs down_at_step={first['down_at_step']}"
    )
    # the dump is redacted: no user content keys anywhere
    for ev in first["events"]:
        attrs = ev.get("attrs") or {}
        assert "tokens" not in attrs and "prompt" not in attrs


def test_drop_fault_traces_retries_without_dumping(tiny):
    """The other transport fault kind: a lossy link (first attempt of
    each RPC dropped) is ABSORBED by retries — the wire lane records
    the rpc_retry events (the cost is visible), but no health
    transition happens and the flight recorder must NOT dump: absorbed
    losses are not failures."""
    cfg, params = tiny
    sc = ServingConfig(**sc_kwargs(replicas=2,
                                   router_policy="round_robin",
                                   replica_transport="loopback"))
    cm = ClusterManager.build(llama, cfg, params, sc)
    recorder = FlightRecorder(capacity=64)
    buf = attach_observability(cm, recorder=recorder)
    cm.attach_faults(FaultPlan([
        Fault("drop", replica=0, step=1, count=100000),
        Fault("drop", replica=1, step=1, count=100000),
    ]))
    outs = cm.generate(PROMPTS, max_new_tokens=4)
    assert all(o.error is None for o in outs)
    retries = [e for e in buf.events if e["name"] == "rpc_retry"]
    assert retries, "dropped first attempts left no rpc_retry events"
    assert recorder.events("wire"), "wire lane ring is empty"
    assert not recorder.dumps, (
        "absorbed transport losses must not trigger a post-mortem"
    )
    assert not any(e["name"] == "health" for e in buf.events)


#: event names whose (name, lane, trace_id, step) sequence is fully
#: deterministic (scheduling + fault plan + step clocks; latency-spike
#: health events are wall-time-derived and deliberately excluded)
_DETERMINISTIC_NAMES = frozenset({
    "admit", "adopt", "prefill_chunk", "first_token", "terminal",
    "wire_migrate", "place", "failover", "migrate", "recompute_readmit",
    "mixed_step", "decode_step", "sync_step", "flush", "dispatch",
    "heartbeat_gap", "probe",
})


def _deterministic_keys(buf):
    return [
        (e["name"], e["lane"], e["trace_id"], e["step"])
        for e in buf.events if e["name"] in _DETERMINISTIC_NAMES
    ]


@pytest.mark.slow
def test_fault_scenario_trace_is_deterministic(tiny, fault_run):
    """Same scenario twice → the same event sequence on the
    deterministic clock (names × lanes × trace ids × steps). Wall
    stamps differ; nothing else may."""
    _, _, buf2, outs2 = _run_fault_scenario(tiny)
    cm, recorder, buf, outs = fault_run
    assert [o.output_tokens for o in outs2] == [
        o.output_tokens for o in outs
    ]
    assert _deterministic_keys(buf2) == _deterministic_keys(buf)


# ---------------------------------------------------------------------------
# cross-process: a subprocess replica server ships its spans home


def _spawn_traced_server(serving_dict, index=0):
    spec = {
        "family": "llama",
        "config": {"preset": "tiny", "dtype": "float32"},
        "seed": 0,
        "index": index,
        "serving": serving_dict,
        "trace": True,
    }
    import os

    proc = subprocess.Popen(
        [sys.executable, "-m", "flexflow_tpu.serve.cluster.server",
         "--port", "0", "--spec", json.dumps(spec)],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    port = None
    deadline = time.time() + 180
    while time.time() < deadline:
        line = proc.stdout.readline()
        if not line:
            time.sleep(0.1)
            if proc.poll() is not None:
                raise RuntimeError("replica server died during startup")
            continue
        if line.startswith("FLEXFLOW_REPLICA_SERVER PORT="):
            port = int(line.strip().rpartition("=")[2])
            break
    if port is None:
        proc.kill()
        raise RuntimeError("replica server never announced its port")
    return proc, port


@pytest.mark.slow
def test_socket_server_ships_trace_events_in_envelopes(tiny):
    """True cross-process correlation: the subprocess replica traces
    into its own buffer (spec ``trace: true``) and every state-bearing
    envelope ships the events home — the client's ONE buffer ends up
    holding the subprocess scheduler's lifecycle spans under the
    cluster trace ids."""
    cfg, params = tiny
    serving = sc_kwargs(cache_dtype="float32")
    proc, port = _spawn_traced_server(serving)
    try:
        sc = ServingConfig(**sc_kwargs(
            replicas=1, replica_transport="socket",
            replica_endpoints=(f"127.0.0.1:{port}",),
            rpc_deadline_s=120.0,
        ))
        cm = ClusterManager.build(llama, cfg, params, sc)
        buf = attach_observability(cm)
        outs = cm.generate(PROMPTS[:2], max_new_tokens=4)
        assert all(o.error is None for o in outs)
        shipped = [e for e in buf.events if e["lane"] == "replica0"]
        names = {e["name"] for e in shipped}
        assert {"admit", "prefill_chunk", "terminal"} <= names, names
        # server-side spans carry the CLUSTER trace ids (the trace
        # context rode the submit RPC)
        cids = {o.request_id for o in outs}
        assert cids <= {e["trace_id"] for e in shipped}
        cm.replicas[0]._rpc("shutdown", {})
    finally:
        proc.terminate()
        proc.wait(timeout=30)


# ---------------------------------------------------------------------------
# satellites: FF_LOG warning, StepTimes percentiles


def test_ff_log_unknown_level_warns_once_names_tokens(monkeypatch):
    monkeypatch.setenv("FF_LOG", "serve=trace")
    monkeypatch.setattr(logging_utils, "_WARNED_LEVELS", set())
    with pytest.warns(UserWarning, match="trace.*INFO.*debug"):
        log = logging_utils.get_logger("serve")
    # the bad token falls back to INFO
    assert log.level == logging.INFO
    # one-time: the same bad token does not warn again
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        logging_utils.get_logger("serve")
    assert not rec, [str(w.message) for w in rec]
    # a *different* bad token warns separately
    monkeypatch.setenv("FF_LOG", "search=loud")
    with pytest.warns(UserWarning, match="loud"):
        logging_utils.get_logger("search")
    # valid levels never warn
    monkeypatch.setenv("FF_LOG", "serve=debug,search=error")
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        assert logging_utils.get_logger("serve").level == logging.DEBUG
        assert logging_utils.get_logger("search").level == logging.ERROR
    assert not rec
    # leave the session's loggers as they started (quiet)
    logging.getLogger("flexflow_tpu.serve").setLevel(logging.WARNING)
    logging.getLogger("flexflow_tpu.search").setLevel(logging.WARNING)


def test_step_times_summary_p99_and_total():
    st = StepTimes()
    for ms in range(1, 101):  # 1..100 ms
        st.record(ms / 1e3)
    s = st.summary()
    assert s["p99_ms"] >= s["p90_ms"] >= s["p50_ms"]
    assert s["p99_ms"] == pytest.approx(99.01, abs=0.1)
    assert s["total_ms"] == pytest.approx(5050.0, abs=0.5)
    rep = st.report()
    assert "p99" in rep and "total" in rep
    assert StepTimes().summary() == {}


# ---------------------------------------------------------------------------
# satellite: the FF108 tracer-sync lint rule


def test_ff108_flags_device_syncs_in_tracer_args():
    from flexflow_tpu.analysis import lint_source

    bad = (
        "import jax\n"
        "import numpy as np\n"
        "class RM:\n"
        "    def step(self):\n"
        "        toks = self._toks\n"
        "        tr = self.tracer\n"
        "        if tr.enabled:\n"
        "            tr.event('decode', tok=toks.item())\n"
        "        self.tracer.event('x', v=np.asarray(toks)[0])\n"
        "        tr.span('s', first=jax.device_get(toks))\n"
    )
    findings = lint_source(bad, path="flexflow_tpu/serve/fake.py")
    assert [f.rule for f in findings].count("FF108") == 3, findings
    clean = (
        "class RM:\n"
        "    def step(self):\n"
        "        tr = self.tracer\n"
        "        if tr.enabled:\n"
        "            tr.event('decode', rows=int(self.n), kind='x')\n"
    )
    assert not lint_source(clean, path="flexflow_tpu/serve/fake.py")
    # outside the serve/obs trees the rule stays quiet
    assert not lint_source(bad, path="flexflow_tpu/train/fake.py")


def test_repo_has_no_ff108_findings():
    """The observability layer itself must never reintroduce the syncs
    PR 6 removed — covered repo-wide by test_ffcheck's clean-package
    guard; this pins the specific rule so a suppression sweep cannot
    silently disable it."""
    from flexflow_tpu.analysis import get_rules

    assert any(r.code == "FF108" for r in get_rules())
