"""The trace reduction on hand-made intervals and on a small recorded
trace of the chip (``trace_sample.json``, cut from a traced run of
``mistral-7b.decode-closed`` by ``tools/trace_dump.py``)."""
import json
import os

import pytest

from benchmarks.harness import reduce

HERE = os.path.dirname(os.path.abspath(__file__))


def test_union_gaps_overlap():
    busy = reduce.union([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert busy == [[0, 3], [5, 8]]
    assert reduce.total(busy) == 6
    assert reduce.gaps(busy, 0, 10) == [(3, 5), (8, 10)]
    assert reduce.overlap([(3, 5), (8, 10)], [[4, 9]]) == 2
    assert reduce.clip([(0, 4), (6, 9)], 2, 7) == [(2, 4), (6, 7)]


def _planes(ops, modules, host):
    return {"/device:TPU:0": {"XLA Ops": ops, "XLA Modules": modules},
            "/host:CPU": {"python": host}}


def test_idle_programs_and_gap_attribution():
    K = ', custom_call_target="tpu_custom_call"'
    ops = [("%fusion.1 = bf16[16,14336]{1,0:T(8,128)} fusion(bf16[16,4096]{1,0} %p)", 100, 300, {}),
           ("%closed_call.3 = bf16[16,1,8,4,128]{4,3,2,1,0} custom-call(%q)" + K, 400, 100, {}),
           ('%custom-call.9 = bf16[2,8]{1,0} custom-call(), custom_call_target="AllocateBuffer"', 990, 5, {}),
           ("%while.1 = (s32[], bf16[16,128,4096]{2,1,0}) while(%t), body=%b", 1000, 800, {}),
           ("%fusion.1 = bf16[16,14336]{1,0:T(8,128)} fusion(bf16[16,4096]{1,0} %p)", 1000, 300, {}),
           ("%closed_call.4 = bf16[16,128,8,4,128]{4,3,2,1,0} custom-call(%q)" + K, 1300, 500, {})]
    modules = [("jit_step(1)", 100, 400, {"run_id": 7}),
               ("jit_step(2)", 1000, 800, {"run_id": 8})]
    # the host runs ahead: both programs are enqueued early in their steps
    host = [("bench.traced", 0, 2000, {}), ("bench.step", 0, 600, {}),
            ("DoEnqueueProgram", 10, 5, {"run_id": 7}),
            ("np.asarray(jax.Array)", 300, 250, {}),
            ("bench.submit", 600, 300, {}), ("bench.step", 900, 1000, {}),
            ("DoEnqueueProgram", 950, 5, {"run_id": 8}),
            ("np.asarray(jax.Array)", 1100, 700, {})]
    t = reduce.Trace(_planes(ops, modules, host))
    assert t.window_s == pytest.approx(2000e-9)
    assert t.busy_s == pytest.approx(1205e-9)
    assert t.idle_share == pytest.approx(1 - 1205 / 2000)
    assert t.program_ms(1) == pytest.approx(400e-6)
    assert t.program_ms(128) == pytest.approx(800e-6)
    assert t.kernel_call_ms(128) == pytest.approx(500e-6)
    assert t.dispatch_ms(1) == pytest.approx(350e-6)
    assert t.dispatch_ms(128) == pytest.approx(300e-6)
    idle = dict(t.idle_by_annotation())
    # gaps: [0,100) step, [500,990): 100 step + 300 submit + 90 step,
    # [995,1000) step, [1800,2000): 100 step + 100 uncovered
    assert idle["bench.step"] == pytest.approx(395e-9)
    assert idle["bench.submit"] == pytest.approx(300e-9)
    assert idle["bench.other"] == pytest.approx(100e-9)
    top = t.breakdown()["device_ops"]
    assert top[0] == ["fusion.1 fusion bf16[16,14336]", pytest.approx(600e-9)]
    assert not any(k.startswith("while") for k, _ in top)


def test_recorded_trace():
    with open(os.path.join(HERE, "trace_sample.json")) as f:
        sample = json.load(f)
    planes = {p: {l: [tuple(ev) for ev in evs] for l, evs in lines.items()}
              for p, lines in sample["planes"].items()}
    t = reduce.Trace(planes)
    want = sample["expect"]
    assert t.window_s == pytest.approx(want["window_s"], rel=1e-9)
    assert t.busy_s == pytest.approx(want["busy_s"], rel=1e-9)
    assert 0.0 <= t.idle_share < 0.2
    programs = t.programs
    assert {c: len(r) for c, r in programs.items()} == {
        int(c): n for c, n in want["programs"].items()}
    assert t.program_ms(1) == pytest.approx(want["decode_ms"], rel=1e-9)
    layers = want["layers"]
    assert all(r[3] == layers for runs in programs.values() for r in runs)
    assert t.dispatch_ms(1) == pytest.approx(want["dispatch_ms"], rel=1e-9)
    assert 1.0 < t.dispatch_ms(1) < 0.5 * t.program_ms(1)
    assert sum(v for _, v in t.idle_by_annotation()) == pytest.approx(
        t.window_s - t.busy_s, rel=1e-6)
