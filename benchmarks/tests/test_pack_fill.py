"""``step.pack_fill_pct`` and ``step.mixed_mean_ms`` (PR 32): the
readers against hand-made counters and a hand-made trace, nothing where
the program keeps none, and their declarations."""
import json
import os
import types

import pytest

from benchmarks.harness import reduce, spec
from benchmarks.harness.loop import Window
from benchmarks.tools import phases


def _ctx(stats):
    win = Window()
    win.stats_open, win.stats_close = stats
    return reduce.Context(
        window=win, setup_s=0.0, cfg={}, peaks=None, trace=reduce.NoTrace(),
        engine_serving=types.SimpleNamespace(mixed_chunk=128))


def _read(stats):
    return spec.load_module("per_layer", "step.pack_fill_pct").read(_ctx(stats))


def _stats(real, width, by):
    return types.SimpleNamespace(step_tokens_real=real,
                                 step_tokens_width=width, steps_by_width=by)


def test_real_tokens_over_dispatched_width():
    stats = (_stats(143, 512, {512: 1}),
             _stats(143 + 460 + 300 + 700, 512 + 512 + 512 + 1024,
                    {512: 3, 1024: 1}))
    assert _read(stats) == pytest.approx(100.0 * 1460 / 2048)
    assert _ctx(stats).steps_by_width() == {512: 2, 1024: 1}  # what run.py logs
    assert _read((stats[0], stats[0])) is None    # no mixed step in the window


def test_a_program_without_the_counters_reads_nothing():
    old = (types.SimpleNamespace(steps=1), types.SimpleNamespace(steps=2))
    assert _read(old) is None
    assert _ctx(old).steps_by_width() is None


def test_the_metric_is_declared_for_every_cell():
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry, = [m for m in bench["per_layer"] if m["name"] == "step.pack_fill_pct"]
    assert entry == dict(
        name="step.pack_fill_pct", unit="%", better="higher",
        source="program_counter", layer="model step", moves="out_tokens_per_s")
    mean = dict(name="step.mixed_mean_ms", unit="ms", better="lower",
                source="device_trace", layer="model step", moves="ttft_p50_ms")
    by_name = {m["name"]: m for m in bench["per_layer"]}
    assert by_name["step.mixed_mean_ms"] == mean  # looked up by name: later PRs append
    for cell in bench["workloads"]:
        cell = spec.Cell(cell["name"])
        assert entry in cell.per_layer
        assert (mean in cell.per_layer) == any(
            m["name"] == "ttft_p50_ms" for m in cell.end_to_end)


def _mean_ms(trace, log=lambda _msg: None):
    ctx = reduce.Context(
        window=Window(), setup_s=0.0, cfg={}, peaks=None, trace=trace,
        engine_serving=types.SimpleNamespace(mixed_chunk=128), log=log)
    return spec.load_module("per_layer", "step.mixed_mean_ms").read(ctx)


def test_mean_mixed_step_by_count_over_the_rungs():
    """Three steps at the 512 rung, one at 1024, a decode step between:
    the median of the four sits in the 512 group, the mean by count is
    what a step costs; the programs are found by their kernel's chunk
    and named in the log by their module."""
    K = ', custom_call_target="tpu_custom_call"'
    ops, modules, at = [], [], 0
    for name, chunk, dur in [("c128_t512", 128, 27), ("c128_t512", 128, 28),
                             ("c1", 1, 15), ("c128_t1024", 128, 55),
                             ("c128_t512", 128, 29)]:
        ops.append((f"%ff_ragged_paged_c{chunk}.1 = bf16[16,{chunk},8,4,128]"
                    "{4,3,2,1,0} custom-call(%q)" + K, at + 1, 5, {}))
        modules.append((f"jit_ff_step_{name}({at})", at, dur, {"run_id": at}))
        at += dur + 1
    planes = {"/device:TPU:0": {"XLA Ops": ops, "XLA Modules": modules},
              "/host:CPU": {"python": [("bench.traced", 0, at, {})]}}
    lines = []
    t = reduce.Trace(planes)
    assert _mean_ms(t, lines.append) == pytest.approx((27 + 28 + 55 + 29) / 4e6)
    assert t.program_ms(128) == pytest.approx(28.5e-6)
    assert ("jit_ff_step_c128_t1024 1 x 0.00, jit_ff_step_c128_t512 3 x 0.00"
            in lines[0])
    assert _mean_ms(reduce.NoTrace()) is None


def test_mean_mixed_step_of_a_recorded_trace_with_one_program():
    """On a program before PR 32 (the recorded hybrid slice) the mean
    is of its one mixed program, beside that program's median."""
    planes, rest = phases.load_sample(os.path.join(
        os.path.dirname(__file__), "trace_sample_hybrid.json"))
    t = reduce.Trace(planes)
    runs = t.programs[128]
    assert _mean_ms(t) == pytest.approx(
        sum((e - s) / 1e6 for s, e, *_ in runs) / len(runs))
    assert _mean_ms(t) == pytest.approx(
        rest["expect"]["program_ms_128"], rel=0.05)
