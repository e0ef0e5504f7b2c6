"""The build log (ISSUE 56, ``flexflow_tpu/obs/builds.py``): what each
step program's build cost, by the program's name.

(a) on a tiny paged server every record of ``SchedulerStats.builds`` is
    named as ``program_name`` names a key of the engine's, its three
    parts are positive, and ``compiles`` counts the builds with no
    sanitizer set and under ``retrace`` alike;
(b) a forced retrace is a record of its own (``<name>#2``) and one
    ``retraces`` — and under the strict sentinel it still raises, and a
    trace that raises is no build;
(c) hit, miss, ``off`` and ``other`` attribution, on events fed through
    ``jax.monitoring`` by the test: nothing depends on a real cache's
    thresholds. A second lowering of a built program adds nothing;
(d) the first sampling request on a warm greedy server builds its
    head's programs inside a step: ``build_in_step_s``, a record named
    for the head with its step stamp, ``build.backend`` in an attached
    buffer and in the flight recorder's ring;
(e) a steady-state request after that: no event heard, the wrapper not
    entered, nothing kept from the log's frames, the same dispatches.

Tiny llama on the CPU in float32, ``kernels="xla"``.
"""
import dataclasses
import time
import tracemalloc

import jax
import jax.monitoring
import jax.numpy as jnp
import pytest

from flexflow_tpu.analysis import RetraceError
from flexflow_tpu.metrics import SchedulerStats
from flexflow_tpu.models import llama
from flexflow_tpu.obs import (
    FlightRecorder,
    attach_observability,
    builds as builds_mod,
    prometheus_text,
)
from flexflow_tpu.obs.tracer import BUILD_SPANS
from flexflow_tpu.serve import (
    GenerationConfig,
    InferenceEngine,
    RequestManager,
    ServingConfig,
)
from flexflow_tpu.serve.engine import program_name

TRACE, LOWER, BACKEND = builds_mod._PARTS
ASKED, HIT, MISS = builds_mod._CACHE_EVENTS
LOAD, SAVED = builds_mod._CACHE_SECONDS
PROMPT = [3, 17, 91, 42, 7, 9, 8, 7, 6, 5, 4]
TOPK = GenerationConfig(do_sample=True, temperature=0.9, topk=5, topp=2.0)


@pytest.fixture(scope="module")
def tiny():
    cfg = llama.LLaMAConfig.tiny(dtype=jnp.float32)
    return cfg, llama.init_params(jax.random.PRNGKey(0), cfg)


def _manager(tiny, **kw):
    cfg, params = tiny
    return RequestManager(InferenceEngine(llama, cfg, params, ServingConfig(
        max_requests_per_batch=4, max_sequence_length=48, prefill_chunk=8,
        max_spec_tree_tokens=8, cache_dtype=jnp.float32, kv_layout="paged",
        page_size=8, kernels="xla", **kw)))


def _serve(rm, gen=None, new=4):
    rid = rm.submit(PROMPT, gen, max_new_tokens=new)
    while rm.step():
        pass
    rm.drain()
    return rm.result(rid)


def _programs(stats):
    return {n: r for n, r in stats.builds.items() if n != builds_mod.OTHER}


# ---------------------------------------------------------------------------
# (a) attribution by name


@pytest.mark.parametrize("sanitizers", [(), ("retrace",)],
                         ids=["no-sanitizer", "retrace"])
def test_every_build_is_a_named_program(tiny, sanitizers):
    rm = _manager(tiny, sanitizers=sanitizers)
    assert _serve(rm).error is None
    eng, s = rm.engine, rm.stats
    recs = _programs(s)
    handed_out = {program_name(k) for k in eng._steps}
    assert recs and set(recs) <= handed_out, (set(recs), handed_out)
    assert "ff_step_c1" in recs and set(recs) == set(eng.build_log.records)
    for name, r in recs.items():
        assert r["ordinal"] == 1 and r["key"] in map(repr, eng._steps)
        assert min(r["trace_s"], r["lower_s"], r["backend_s"]) > 0, (name, r)
        # what was traced inside the trace is part of it
        assert 0 < r["inner_s"] < r["trace_s"] and r["inner"]
        # built inside the steps of the one live request
        assert r["in_step"] and r["step"] >= 0
        assert r["start"] <= time.perf_counter()
    assert s.compiles == len(recs) and s.retraces == 0
    assert s.build_trace_s == pytest.approx(
        sum(r["trace_s"] for r in recs.values()))
    assert s.build_lower_s == pytest.approx(
        sum(r["lower_s"] for r in recs.values()))
    assert s.build_backend_s == pytest.approx(
        sum(r["backend_s"] for r in recs.values()))
    assert s.build_in_step_s == pytest.approx(
        s.build_trace_s + s.build_lower_s + s.build_backend_s)
    if sanitizers:
        assert s.compiles == eng.retrace_guard.total_compiles
    # the other builds of the process are one record (the log's, from
    # the engine's construction) and one counter (the scheduler's)
    # (a later server of the process may find every helper built)
    other = s.builds.get(builds_mod.OTHER, eng.build_log.other)
    assert other == eng.build_log.other
    assert 0 <= s.build_other_s <= (
        other["trace_s"] + other["lower_s"] + other["backend_s"])
    assert (s.build_other_s > 0) == (builds_mod.OTHER in s.builds)
    # the snapshot carries the records without their by-name ``inner``,
    # and the exporter one labelled series of them
    snap = s.snapshot()
    assert set(snap["builds"]) == set(s.builds)
    assert all("inner" not in r for r in snap["builds"].values())
    text = prometheus_text(scheduler={"0": s})
    for part in BUILD_SPANS:
        assert ('flexflow_scheduler_build_seconds{part="%s",'
                'program="ff_step_c1",replica="0"}' % part) in text
    assert "flexflow_scheduler_build_in_step_s" in text
    assert f"compiles={s.compiles} retraces=0 build=" in s.report()


# ---------------------------------------------------------------------------
# (b) a retrace


@pytest.mark.parametrize("sanitizer", [None, "retrace-warn", "retrace"])
def test_a_forced_retrace(tiny, sanitizer):
    rm = _manager(tiny, sanitizers=(sanitizer,) if sanitizer else ())
    eng, s = rm.engine, rm.stats
    f = eng._jit(lambda x: x * 2, key="probe")
    f(jnp.zeros((4,), jnp.float32))
    f(jnp.ones((4,), jnp.float32))          # the same signature: no trace
    assert (s.compiles, s.retraces) == (1, 0)
    assert s.builds["ff_probe"]["step"] == -1    # nothing was scheduled
    assert not s.builds["ff_probe"]["in_step"] and s.build_in_step_s == 0
    if sanitizer == "retrace":
        with pytest.raises(RetraceError, match="RECOMPILED"):
            f(jnp.zeros((8,), jnp.float32))
        # a trace that raises is no build, and leaves nothing open
        assert (s.compiles, s.retraces) == (1, 0)
        assert set(_programs(s)) == {"ff_probe"}
        assert builds_mod._T.build is None
    else:
        f(jnp.zeros((8,), jnp.float32))     # shape drift: a second build
        assert (s.compiles, s.retraces) == (2, 1)
        assert set(_programs(s)) == {"ff_probe", "ff_probe#2"}
        again = s.builds["ff_probe#2"]
        assert again["ordinal"] == 2 and again["backend_s"] > 0
    # the next program is a first build either way
    g = eng._jit(lambda x: x + 1, key="probe2")
    g(jnp.zeros((2,), jnp.float32))
    assert s.builds["ff_probe2"]["ordinal"] == 1
    assert s.builds["ff_probe2"]["lower_s"] > 0


# ---------------------------------------------------------------------------
# (c) the cache's word and ``other``, on fed events


def _feed_part(event, seconds, fun):
    jax.monitoring.record_scalar(event, time.time(), fun_name=fun)
    jax.monitoring.record_event_duration_secs(event, seconds, fun_name=fun)


def _feed_build(eng, name, cache_events=(), load=None, saved=None):
    """One build's events in the order JAX fires them."""
    jax.monitoring.record_scalar(TRACE, time.time(), fun_name=name)
    with eng.build_log.tracing(name, ("fed", name)):
        _feed_part(TRACE, 0.25, "helper")        # traced inside the trace
    jax.monitoring.record_event_duration_secs(TRACE, 1.0, fun_name=name)
    _feed_part(LOWER, 0.5, f"jit({name})")
    jax.monitoring.record_scalar(BACKEND, time.time(), fun_name=f"jit({name})")
    for ev in cache_events:
        jax.monitoring.record_event(ev)
    if saved is not None:
        jax.monitoring.record_event_duration_secs(SAVED, saved)
    if load is not None:
        jax.monitoring.record_event_duration_secs(LOAD, load)
    jax.monitoring.record_event_duration_secs(
        BACKEND, 0.125, fun_name=f"jit({name})")


def test_cache_and_other_attribution_on_fed_events(tiny):
    rm = _manager(tiny)
    eng, s = rm.engine, rm.stats
    other0, other_s0 = dict(eng.build_log.other), s.build_other_s

    _feed_build(eng, "ff_fed_hit", (ASKED, HIT), load=0.375, saved=7.0)
    _feed_build(eng, "ff_fed_miss", (ASKED, MISS))
    _feed_build(eng, "ff_fed_off")
    hit, miss, off = (s.builds[n] for n in
                      ("ff_fed_hit", "ff_fed_miss", "ff_fed_off"))
    assert (hit["cache"], hit["cache_load_s"], hit["saved_s"]) == (
        "hit", 0.375, 7.0)
    assert (miss["cache"], miss["cache_load_s"], miss["saved_s"]) == (
        "miss", 0.0, 0.0)
    assert off["cache"] == "off"     # the hit before it was used up
    for r in (hit, miss, off):
        assert (r["trace_s"], r["lower_s"], r["backend_s"]) == (1.0, 0.5, 0.125)
        assert r["inner"] == {"helper": [1, 0.25]} and r["inner_s"] == 0.25
    assert (s.build_cache_hits, s.build_cache_misses) == (1, 1)
    assert (s.build_trace_s, s.build_lower_s, s.build_backend_s) == (
        3.0, 1.5, 0.375)
    assert s.compiles == 3 and s.build_other_s == other_s0

    # a program nobody named: ``other``, outermost parts only
    jax.monitoring.record_scalar(TRACE, time.time(), fun_name="reference")
    _feed_part(TRACE, 0.5, "add")                # inside reference's trace
    jax.monitoring.record_event_duration_secs(
        TRACE, 2.0, fun_name="reference")
    _feed_part(LOWER, 1.0, "jit(reference)")
    jax.monitoring.record_scalar(BACKEND, time.time(),
                                 fun_name="jit(reference)")
    jax.monitoring.record_event(ASKED)
    jax.monitoring.record_event(HIT)
    jax.monitoring.record_event_duration_secs(
        BACKEND, 4.0, fun_name="jit(reference)")
    other = s.builds[builds_mod.OTHER]
    assert other["count"] == other0["count"] + 1
    assert other["cache_hits"] == other0["cache_hits"] + 1
    assert other["trace_s"] == pytest.approx(other0["trace_s"] + 2.0)
    assert s.build_other_s == pytest.approx(other_s0 + 7.0)
    assert s.compiles == 3 and len(_programs(s)) == 3

    # a built program lowered again (step_program_texts): the tracing
    # cache answers, the wrapper does not run — no record, no counter
    before = dataclasses.replace(s)
    _feed_part(TRACE, 0.0, "ff_fed_hit")
    _feed_part(LOWER, 0.5, "jit(ff_fed_hit)")
    jax.monitoring.record_scalar(BACKEND, time.time(),
                                 fun_name="jit(ff_fed_hit)")
    jax.monitoring.record_event(ASKED)
    jax.monitoring.record_event(HIT)
    jax.monitoring.record_event_duration_secs(
        BACKEND, 0.25, fun_name="jit(ff_fed_hit)")
    assert s == before and eng.build_log.other == other
    assert builds_mod._T.cache == {} and builds_mod._T.depth == 0

    # asked, nothing said, and a directory to look in: a miss (a
    # program under the cache's thresholds misses every run)
    assert builds_mod._cache_word({"asked": True}) == "off"
    old = jax.config.jax_compilation_cache_dir
    try:
        jax.config.update("jax_compilation_cache_dir", "/nonexistent/ff")
        assert builds_mod._cache_word({"asked": True}) == "miss"
    finally:
        jax.config.update("jax_compilation_cache_dir", old)


def test_the_real_second_lowering_adds_nothing(tiny):
    rm = _manager(tiny)
    _serve(rm)
    eng, s = rm.engine, rm.stats
    before, records = dataclasses.replace(s), dict(eng.build_log.records)
    other = dict(eng.build_log.other)
    texts = eng.step_program_texts()
    assert set(texts) == set(records)
    assert s == before and eng.build_log.records == records
    assert eng.build_log.other == other
    assert [r.ordinal for r in records.values()] == [1] * len(records)


# ---------------------------------------------------------------------------
# (d) a build inside a step, (e) and the steady state after it


def test_the_first_sampling_request_builds_inside_a_step(tiny):
    rm = _manager(tiny)
    recorder = FlightRecorder(capacity=512)
    buf = attach_observability(rm, recorder=recorder)
    eng, s = rm.engine, rm.stats
    assert _serve(rm).error is None            # the greedy programs
    warm, warm_in_step = set(_programs(s)), s.build_in_step_s
    steps_before = rm._step_counter
    n_events = len(buf.events)

    d0 = eng.dispatch_count
    assert _serve(rm, TOPK).error is None
    first_dispatches = eng.dispatch_count - d0
    new = set(_programs(s)) - warm
    assert new and all(n.endswith("_topk8") for n in new), new
    assert "ff_step_c1_topk8" in new
    assert s.build_in_step_s > warm_in_step
    for n in new:
        r = s.builds[n]
        assert r["in_step"] and r["step"] >= max(1, steps_before)
    # each part of each build is an event of the engine's lane, between
    # the requests' own, and in the flight recorder's ring
    fresh = [e for e in buf.events[n_events:] if e["name"] in
             BUILD_SPANS.values()]
    assert {e["lane"] for e in fresh} == {"engine"}
    by_program = {}
    for e in fresh:
        by_program.setdefault(e["attrs"]["program"], []).append(e)
    assert set(by_program) == new
    for n, evs in by_program.items():
        assert [e["name"] for e in evs] == list(BUILD_SPANS.values())
        r = s.builds[n]
        assert [e["dur"] for e in evs] == [
            r["trace_s"], r["lower_s"], r["backend_s"]]
        assert all(e["step"] == r["step"] and e["attrs"]["ordinal"] == 1
                   for e in evs)
        assert evs[-1]["attrs"]["cache"] == r["cache"]
        assert evs[0]["t"] <= evs[1]["t"] <= evs[2]["t"]
    ring = [e for e in recorder.events("engine")
            if e["name"] == "build.backend"]
    assert {e["attrs"]["program"] for e in ring} >= new

    # (e) the same request again: every program is built
    heard = []

    def listen(event, *a, **kw):
        if event in builds_mod._PARTS:
            heard.append((event, kw))

    entered = []
    tracing = builds_mod.BuildLog.tracing

    def spy(self, name, *a, **kw):
        entered.append(name)
        return tracing(self, name, *a, **kw)

    jax.monitoring.register_event_duration_secs_listener(listen)
    builds_mod.BuildLog.tracing = spy
    before = dataclasses.replace(s)
    n_events, d0 = len(buf.events), eng.dispatch_count
    try:
        tracemalloc.start()
        assert _serve(rm, TOPK).error is None
        snap = tracemalloc.take_snapshot()
    finally:
        tracemalloc.stop()
        builds_mod.BuildLog.tracing = tracing
        jax.monitoring.unregister_event_duration_listener(listen)
    assert not heard and not entered
    kept = snap.filter_traces(
        [tracemalloc.Filter(True, "*obs*builds.py")]).statistics("filename")
    assert not kept, f"a steady-state step kept memory from the log: {kept}"
    assert eng.dispatch_count - d0 == first_dispatches
    assert not [e for e in buf.events[n_events:]
                if e["name"] in BUILD_SPANS.values()]
    for f in dataclasses.fields(SchedulerStats):
        if f.name.startswith("build") or f.name in ("compiles", "retraces"):
            assert getattr(s, f.name) == getattr(before, f.name), f.name
