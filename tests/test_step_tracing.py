"""The scheduler step on the profiler's clock (ISSUE 27): the six
``ff.step.*`` phase spans, the two ``ProfileInfo`` stamps that split
TTFT into queue wait + prefill dispatch + first-token lag, and the
stable names of the step programs.

A profiler session is one per process: the one test that opens one
opens it inside the test body and closes it there, and it lives in this
file alone (xdist's ``loadfile`` keeps a file on one worker).
"""
import glob
import os

import jax
import jax.numpy as jnp
import pytest

from flexflow_tpu.models import llama
from flexflow_tpu.obs import attach_observability, check_export_coverage
from flexflow_tpu.obs import export as obs_export
from flexflow_tpu.obs.tracer import STEP_SPANS
from flexflow_tpu.serve import (
    ClusterManager,
    InferenceEngine,
    RequestManager,
    ServingConfig,
)
from flexflow_tpu.serve.batch_config import GenerationConfig
from flexflow_tpu.serve.engine import program_name

CHUNK = 8
PROMPTS = [
    [(i * 7 + j * 3 + 1) % 250 for j in range(16 + 4 * i)] for i in range(4)
]


@pytest.fixture(scope="module")
def tiny():
    cfg = llama.LLaMAConfig.tiny(dtype=jnp.float32)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def serving(**kw):
    base = dict(
        max_requests_per_batch=4,
        max_sequence_length=64,
        prefill_chunk=CHUNK,
        max_spec_tree_tokens=8,
        cache_dtype=jnp.float32,
        kv_layout="paged",
        page_size=16,
    )
    base.update(kw)
    return ServingConfig(**base)


def make_rm(tiny, **kw):
    cfg, params = tiny
    return RequestManager(InferenceEngine(llama, cfg, params, serving(**kw)))


# ---------------------------------------------------------------------------
# (a) the spans, in the profiler's trace and in the buffer


def _host_events(logdir):
    """[(name, start_ns, end_ns)] of the ``ff.*`` events on the host
    plane of the one trace under ``logdir``."""
    (path,) = glob.glob(
        os.path.join(logdir, "plugins", "profile", "*", "*.xplane.pb")
    )
    data = jax.profiler.ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("ff."):
                    out.append(
                        (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                    )
    return out


def test_step_spans_reach_the_profiler_and_the_buffer(tiny, tmp_path):
    """One run under ONE profiler session, first with the null tracer
    (the session alone is the switch), then attached: the six
    ``ff.step.*`` names are on the host plane, every ``flush_wait``
    lies inside a ``flush``, and the attached buffer holds the same six
    with durations."""
    rm = make_rm(tiny)
    rm.generate(PROMPTS[:2], max_new_tokens=4)  # compile outside the trace
    steps_before = rm.stats.steps
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        assert not rm.tracer.enabled
        rm.generate(PROMPTS, max_new_tokens=6)
        buf = attach_observability(rm)
        rm.generate(PROMPTS, max_new_tokens=6)
    finally:
        jax.profiler.stop_trace()
    events = _host_events(str(tmp_path))
    by_name = {}
    for name, s, e in events:
        by_name.setdefault(name, []).append((s, e))
    assert set(by_name) == {"ff." + n for n in STEP_SPANS}
    # both halves of the run are there: one build and one dispatch a step
    traced_steps = rm.stats.steps - steps_before
    assert len(by_name["ff.step.dispatch"]) == traced_steps
    assert len(by_name["ff.step.build"]) == traced_steps
    flushes = by_name["ff.step.flush"]
    for s, e in by_name["ff.step.flush_wait"]:
        assert any(fs <= s and e <= fe for fs, fe in flushes), (
            "a flush_wait outside every flush"
        )
    assert len(by_name["ff.step.flush_wait"]) == len(flushes)
    # the buffer: the same six, as completed spans on perf_counter
    spans = [e for e in buf.events if e["name"] in STEP_SPANS]
    assert {e["name"] for e in spans} == set(STEP_SPANS)
    assert all(e["dur"] >= 0.0 and e["t"] > 0 for e in spans)
    assert any(e["dur"] > 0.0 for e in spans)
    waits = [e for e in spans if e["name"] == "step.flush_wait"]
    outer = [e for e in spans if e["name"] == "step.flush"]
    for w in waits:
        assert any(
            f["t"] <= w["t"] and w["t"] + w["dur"] <= f["t"] + f["dur"]
            for f in outer
        )
    # the per-step instants and lifecycle events stay what they were
    names = {e["name"] for e in buf.events}
    assert {"admit", "prefill_chunk", "flush", "first_token",
            "terminal", "dispatch", "mixed_step"} <= names


# ---------------------------------------------------------------------------
# (b) the stamps


def assert_ttft_split(profile):
    p = profile
    assert p.start_time > 0
    assert (
        p.start_time <= p.admit_time <= p.prefill_dispatched_time
        <= p.first_token_time <= p.finish_time
    ), p
    parts = p.queue_wait_s + p.prefill_dispatch_s + p.first_token_lag_s
    assert parts == pytest.approx(p.ttft_s, abs=1e-8)


@pytest.mark.parametrize("continuous_batching", [True, False],
                         ids=["pipelined", "sync"])
def test_stamps_order_through_preemption(tiny, continuous_batching):
    """start <= admit <= prefill_dispatched <= first_token <= finish for
    every request of a run whose tight pool preempts at least one; the
    first grant's stamp survives the re-admission."""
    rm = make_rm(tiny, max_cached_tokens=48,
                 continuous_batching=continuous_batching)
    admits = {}
    grant = rm._admit_pending

    def noting_first_grants():
        grant()
        for rid in rm.slots:
            if rid is not None:
                admits.setdefault(rid, rm.requests[rid].profile.admit_time)

    rm._admit_pending = noting_first_grants
    outs = rm.generate(PROMPTS, max_new_tokens=8)
    assert rm.stats.preemptions > 0, "pool was never oversubscribed"
    assert rm.stats.admitted > len(PROMPTS)  # somebody was re-admitted
    for o in outs:
        assert o.error is None
        assert_ttft_split(o.profile)
        assert o.profile.admit_time == admits[o.request_id]


def test_stamps_order_through_adopt_prefilled(tiny):
    """Disaggregated prefill -> decode: the decode replica adopts the
    request with the profile the prefill replica stamped."""
    cfg, params = tiny
    sc = serving(max_sequence_length=96, replicas=2, prefill_replicas=1,
                 decode_replicas=1)
    cm = ClusterManager.build(llama, cfg, params, sc)
    outs = cm.generate(PROMPTS, max_new_tokens=6)
    assert cm.cluster_stats()["migrations"] == len(PROMPTS)
    for o in outs:
        assert o.profile.replica_id == 1  # adopted by the decode home
        assert_ttft_split(o.profile)


def test_adopt_without_a_carried_stamp_is_admitted_now(tiny):
    rm = make_rm(tiny)
    rid = rm.adopt_prefilled([1, 2, 3, 4], 3, GenerationConfig())
    assert rm.requests[rid].profile.admit_time > 0


# ---------------------------------------------------------------------------
# (c) the names


@pytest.mark.parametrize("sanitizers", [(), ("retrace",)],
                         ids=["plain", "retrace-guard"])
def test_step_programs_are_named_from_their_keys(tiny, sanitizers):
    rm = make_rm(tiny, sanitizers=sanitizers)
    rm.generate(PROMPTS[:1], max_new_tokens=3)
    eng = rm.engine
    keys = {("mixed_fused", 1, False, "greedy", 0): "ff_step_c1",
            ("mixed_fused", CHUNK, False, "greedy", 0): f"ff_step_c{CHUNK}"}
    assert set(keys) <= set(eng._steps)
    if eng.retrace_guard is not None:
        eng.retrace_guard.strict = False  # lowering again traces again
    R = eng.num_slots
    for key, name in keys.items():
        C = key[1]
        assert program_name(key) == name
        lowered = eng._steps[key].lower(
            eng.params, eng.cache, jnp.zeros((R,), jnp.int32),
            jnp.zeros((R, C), jnp.int32), jnp.zeros((R,), jnp.bool_),
            jnp.zeros((R, C), jnp.int32), jnp.zeros((R,), jnp.int32),
            jax.random.PRNGKey(0), jnp.ones((R,), jnp.bool_),
            jnp.ones((R,), jnp.float32), jnp.ones((R,), jnp.float32),
            jnp.zeros((R,), jnp.int32), page_table=eng.page_table_device(),
        )
        assert f"module @jit_{name} " in lowered.as_text()


@pytest.mark.parametrize("kind, chunk", [("decode", 1), ("mixed", CHUNK)])
def test_one_step_program_a_scheduler_step(tiny, kind, chunk):
    """A scheduler step of the pipelined paged Pallas engine dispatches
    ONE device program, ``ff_step_c<chunk>``, compiled once: the
    in-suite twin of the benchmark's ``engine.programs_per_step`` (which
    counts the device's modules over those named ``jit_ff_step_*``)."""
    rm = make_rm(tiny, kernels="pallas", sanitizers=("retrace",))
    eng = rm.engine
    for p in PROMPTS:
        rm.submit(p, max_new_tokens=6)
    steps_of_kind = 0
    more = True
    while more:
        before = (eng.dispatch_count, getattr(rm.stats, f"{kind}_steps"))
        more = rm.step()
        if getattr(rm.stats, f"{kind}_steps") > before[1]:
            steps_of_kind += 1
            assert eng.dispatch_count - before[0] == 1
    rm.drain()
    assert steps_of_kind > 0
    counts = eng.retrace_guard.compile_counts()
    key = ("mixed_fused", chunk, False, "greedy", 0)
    assert counts.get(key) == 1, counts
    assert program_name(key) == f"ff_step_c{chunk}"
    assert eng.retrace_guard.retraces == 0


def test_program_names_are_distinct_and_stable():
    keys = ["commit", "copy_page", "reorder", (1, False, False),
            (8, True, True), ("mixed_fused", 1, False, "greedy", 0),
            ("mixed_fused", 1, True, "greedy", 0),
            ("mixed_fused", 1, False, "sample", 0),
            ("mixed_fused", 1, False, "topk", 64),
            ("step_sampled", 1, False, "greedy", 0, False),
            ("speculate", 2, 3)]
    names = [program_name(k) for k in keys]
    assert len(set(names)) == len(names)
    assert all(n.startswith("ff_") and n.replace("_", "").isalnum()
               for n in names)
    assert names[:4] == ["ff_commit", "ff_copy_page", "ff_reorder",
                         "ff_step_sync_c1"]
    assert names[4] == "ff_step_sync_c8_logits_mask"
    assert names[5:9] == ["ff_step_c1", "ff_step_c1_logits",
                          "ff_step_c1_sample", "ff_step_c1_topk64"]
    # every per-step program reads as a step
    assert all(n.startswith("ff_step_") for n in names[3:10])


# ---------------------------------------------------------------------------
# (d) the export drift guard


def test_export_covers_the_two_stamps(tiny):
    check_export_coverage()
    assert {"admit_time", "prefill_dispatched_time"} <= set(
        obs_export.PROFILE_EXCLUDED)
    rm = make_rm(tiny)
    outs = rm.generate(PROMPTS[:2], max_new_tokens=4)
    profiles = [o.profile for o in outs]
    text = obs_export.prometheus_text(profiles=profiles)
    got = {}
    for line in text.splitlines():
        if line.startswith("flexflow_request_") and "_seconds_sum" in line:
            name, value = line.split()
            got[name] = float(value)
    parts = sum(got[f"flexflow_request_{p}_seconds_sum"]
                for p in ("queue_wait", "prefill_dispatch",
                          "first_token_lag"))
    assert parts == pytest.approx(got["flexflow_request_ttft_seconds_sum"],
                                  abs=1e-6)
