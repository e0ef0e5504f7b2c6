"""The ten per-layer readers of PR 27: the three stamp readers and the
step-share counter on a hand-made ``Window``; the trace readers on
hand-made intervals and on a recorded slice of the chip
(``trace_sample_spans.json``: mixed steps of
``mistral-7b.prefill-closed`` with the ``ff.step.*`` spans and the
``jit_ff_step_*`` names, written by ``tools/phases.py --sample``), with
the expected values in the file; and ``tools/phases.py``'s arithmetic."""
import json
import os
import types

import pytest

from benchmarks.harness import reduce, spec
from benchmarks.harness.loop import Sent, Window
from benchmarks.tools import phases

HERE = os.path.dirname(os.path.abspath(__file__))
TRACE_READERS = ("sched.host_ms", "cache.reserve_ms", "engine.enqueue_ms",
                 "engine.host_busy_pct", "engine.programs_per_step",
                 "engine.run_ahead_ms")
STAMP_READERS = ("sched.queue_wait_ms", "sched.prefill_dispatch_ms",
                 "engine.first_token_lag_ms")


def read(name, ctx):
    return spec.load_module("per_layer", name).read(ctx)


def _ctx(trace=reduce.NoTrace(), samples=(), stats=None):
    win = Window()
    win.samples = list(samples)
    if stats:
        win.stats_open, win.stats_close = stats
    return reduce.Context(window=win, setup_s=0.0, cfg={},
                          engine_serving=None, peaks=None, trace=trace)


# ---------------------------------------------------------------------------
# stamps and counters


def _sample(due, admit, dispatched, first, **extra):
    profile = types.SimpleNamespace(
        admit_time=admit, prefill_dispatched_time=dispatched,
        first_token_time=first, finish_time=first + 1.0, **extra)
    return Sent(prompt=[1], max_new=2, due=due, profile=profile)


def test_stamp_readers_split_ttft():
    samples = [_sample(10.0, 10.001, 10.501, 11.101),
               _sample(20.0, 20.003, 20.803, 21.403),
               _sample(30.0, 30.002, 30.602, 31.302)]
    ctx = _ctx(samples=samples)
    assert read("sched.queue_wait_ms", ctx) == pytest.approx(2.0)
    assert read("sched.prefill_dispatch_ms", ctx) == pytest.approx(600.0)
    assert read("engine.first_token_lag_ms", ctx) == pytest.approx(600.0)
    for s in samples:  # the three parts ARE the sample's ttft
        p = s.profile
        parts = ((p.admit_time - s.due)
                 + (p.prefill_dispatched_time - p.admit_time)
                 + (p.first_token_time - p.prefill_dispatched_time))
        assert parts * 1e3 == pytest.approx(s.ttft_ms, abs=1e-6)


def test_stamp_readers_find_nothing_on_a_program_without_stamps():
    old = types.SimpleNamespace(first_token_time=11.0, finish_time=12.0)
    unset = _sample(10.0, 0.0, 0.0, 11.0)
    for samples in ([Sent(prompt=[1], max_new=2, due=10.0, profile=old)],
                    [unset], []):
        ctx = _ctx(samples=samples)
        assert [read(n, ctx) for n in STAMP_READERS] == [None] * 3


def test_mixed_step_share():
    stats = (types.SimpleNamespace(steps=100, mixed_steps=10),
             types.SimpleNamespace(steps=300, mixed_steps=16))
    ctx = _ctx(stats=stats)
    assert read("sched.mixed_step_share_pct", ctx) == pytest.approx(3.0)
    same = (stats[0], stats[0])
    assert read("sched.mixed_step_share_pct", _ctx(stats=same)) is None


# ---------------------------------------------------------------------------
# the trace readers on hand-made intervals


def _planes(modules, host, ops=()):
    ops = list(ops) or [("%fusion.1 = bf16[4]{0} fusion()", 0, 4000, {})]
    return {"/device:TPU:0": {"XLA Ops": ops, "XLA Modules": modules},
            "/host:CPU": {"python3": host}}


def test_trace_readers_on_hand_made_spans():
    host = [("bench.traced", 0, 4000, {}),
            # turn 1: a flush nested in the admission, counted once
            ("bench.step", 0, 1000, {}),
            ("ff.step.admit", 0, 300, {}),
            ("ff.step.flush", 100, 150, {}),
            ("ff.step.flush_wait", 120, 100, {}),
            ("ff.step.reserve", 300, 50, {}),
            ("ff.step.build", 350, 100, {}),
            ("ff.step.dispatch", 450, 200, {}),
            ("DoEnqueueProgram", 500, 5, {"run_id": 7}),
            ("ff.step.flush", 650, 350, {}),
            ("ff.step.flush_wait", 700, 250, {}),
            # turn 2: a reservation that had to flush
            ("bench.step", 2000, 1000, {}),
            ("ff.step.admit", 2000, 20, {}),
            ("ff.step.reserve", 2020, 280, {}),
            ("ff.step.flush", 2100, 100, {}),
            ("ff.step.flush_wait", 2110, 80, {}),
            ("ff.step.build", 2300, 100, {}),
            ("ff.step.dispatch", 2400, 400, {}),
            ("DoEnqueueProgram", 2450, 5, {"run_id": 8}),
            ("DoEnqueueProgram", 2460, 5, {"run_id": 9})]
    modules = [("jit_ff_step_c1(11)", 1500, 400, {"run_id": 7}),
               ("jit__threefry_split(5)", 2900, 10, {"run_id": 9}),
               ("jit_ff_step_c128(12)", 3000, 800, {"run_id": 8}),
               ("jit_ff_step_c128(12)", 3900, 800, {"run_id": 10})]  # cut
    ctx = _ctx(trace=reduce.Trace(_planes(modules, host)))
    # turn 1: admit 300 (holds a flush) + build 100 + flush 350 = 750,
    # less waits 100 + 250; turn 2: admit 20 + build 100 + flush 100,
    # less 80
    assert read("sched.host_ms", ctx) == pytest.approx((400 + 140) / 2 / 1e6)
    # 50, and 280 less the 100 of flush inside it
    assert read("cache.reserve_ms", ctx) == pytest.approx((50 + 180) / 2 / 1e6)
    assert read("engine.enqueue_ms", ctx) == pytest.approx(300e-6)
    assert read("engine.host_busy_pct", ctx) == pytest.approx(
        100 * (1 - 430 / 4000))
    assert read("engine.programs_per_step", ctx) == pytest.approx(3 / 2)
    assert read("engine.run_ahead_ms", ctx) == pytest.approx(
        ((1500 - 500) + (3000 - 2450)) / 2 / 1e6)
    # every moment of the window is under exactly one leaf
    leaves = phases.self_times(ctx.trace)
    assert sum(reduce.total(iv) for iv in leaves.values()) == 4000
    assert reduce.total(leaves["ff.step.admit"]) == 150 + 20
    assert reduce.total(leaves["ff.step.reserve"]) == 50 + 180
    assert reduce.total(leaves["ff.step.flush"]) == 50 + 100 + 20
    assert reduce.total(leaves["bench.step outside every phase"]) == 200
    assert reduce.total(leaves["outside the loop's spans"]) == 2000
    found = phases.report(ctx)
    assert found["modules"] == {"jit_ff_step_c1": 1, "jit__threefry_split": 1,
                                "jit_ff_step_c128": 1}
    assert sum(found["idle_s_by_phase"].values()) == pytest.approx(0.0)


def test_trace_readers_find_nothing_without_spans_or_names():
    """A program before PR 27 (the recorded decode slice is one), and a
    CPU rehearsal's ``NoTrace``: every reader returns None, none
    raises."""
    with open(os.path.join(HERE, "trace_sample.json")) as f:
        sample = json.load(f)
    planes = {p: {l: [tuple(ev) for ev in evs] for l, evs in lines.items()}
              for p, lines in sample["planes"].items()}
    for trace in (reduce.Trace(planes), reduce.NoTrace()):
        ctx = _ctx(trace=trace)
        assert [read(n, ctx) for n in TRACE_READERS] == [None] * 6
    leaves = phases.self_times(reduce.Trace(planes))
    assert reduce.total(leaves["ff.step.dispatch"]) == 0


# ---------------------------------------------------------------------------
# the trace readers on the recorded slice


@pytest.fixture(scope="module")
def recorded():
    planes, rest = phases.load_sample(
        os.path.join(HERE, "trace_sample_spans.json"))
    return reduce.Trace(planes), rest["expect"]


def test_recorded_spans_trace_reduces_as_before(recorded):
    """What PR 26's reduction reads is still there under the new names:
    the step program is found by its kernel's chunk extent, its host
    step by ``run_id``."""
    t, want = recorded
    assert t.window_s == pytest.approx(want["window_s"], rel=1e-9)
    assert t.busy_s == pytest.approx(want["busy_s"], rel=1e-9)
    assert {c: len(r) for c, r in t.programs.items()} == {
        int(c): n for c, n in want["programs"].items()}
    assert t.program_ms(128) == pytest.approx(want["mixed_ms"], rel=1e-9)
    assert t.dispatch_ms(128) == pytest.approx(want["dispatch_ms"], rel=1e-9)
    assert all(r[3] == want["layers"] for r in t.programs[128])


@pytest.mark.parametrize("name", TRACE_READERS)
def test_recorded_spans_readers(recorded, name):
    t, want = recorded
    got = read(name, _ctx(trace=t))
    assert got == pytest.approx(want["readers"][name], rel=1e-9)


def test_recorded_spans_names_and_cross_checks(recorded):
    t, want = recorded
    ctx = _ctx(trace=t)
    names = {n.split("(")[0] for n, *_ in t.modules}
    assert "jit_ff_step_c128" in names and "jit_traced" not in names
    kernels = {name for name, _, _, kernel, *_ in t.ops if kernel}
    assert kernels and all(k.startswith("ff_ragged_paged_c128")
                           for k in kernels)
    # the three layers' spans split what engine.dispatch_ms.* sums
    parts = sum(read(n, ctx) for n in
                ("sched.host_ms", "cache.reserve_ms", "engine.enqueue_ms"))
    assert parts == pytest.approx(t.dispatch_ms(128), rel=0.1)
    # about four steps in flight: three run ahead of the one executing
    assert 2.5 < read("engine.run_ahead_ms", ctx) / t.program_ms(128) < 3.1
    # (the sample leaves out the flush_wait its end cuts, hence under 10
    # and not the whole run's 2.8)
    assert 1.0 < read("engine.host_busy_pct", ctx) < 10.0
    leaves = phases.self_times(t)
    assert sum(reduce.total(iv) for iv in leaves.values()) == pytest.approx(
        t.hi - t.lo)
    # every flush_wait lies inside a flush
    flushes = reduce.union(t.spans("ff.step.flush"))
    waits = t.spans("ff.step.flush_wait")
    assert reduce.overlap(waits, flushes) == pytest.approx(reduce.total(waits))
