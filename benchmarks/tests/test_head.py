"""``head.greedy_steps_pct`` (PR 41): the reader against hand-made
counters, nothing where the program keeps none, and its declaration."""
import json
import os
import types

import pytest

from benchmarks.harness import reduce, spec
from benchmarks.harness.loop import Window


def _read(stats):
    win = Window()
    win.stats_open, win.stats_close = stats
    ctx = reduce.Context(
        window=win, setup_s=0.0, cfg={}, peaks=None, trace=reduce.NoTrace(),
        engine_serving=types.SimpleNamespace(mixed_chunk=128))
    return spec.load_module("per_layer", "head.greedy_steps_pct").read(ctx)


def _stats(steps, greedy):
    return types.SimpleNamespace(head_steps=steps, head_greedy_steps=greedy)


def test_greedy_steps_over_pipelined_steps():
    assert _read((_stats(7, 7), _stats(2907, 2907))) == 100.0
    assert _read((_stats(10, 4), _stats(50, 14))) == pytest.approx(25.0)
    assert _read((_stats(7, 7), _stats(7, 7))) is None   # no step in the window


def test_a_program_without_the_counters_reads_nothing():
    old = (types.SimpleNamespace(steps=1), types.SimpleNamespace(steps=2))
    assert _read(old) is None


def test_the_metric_is_declared_last_and_for_every_cell():
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry, = [m for m in bench["per_layer"]
              if m["name"] == "head.greedy_steps_pct"]
    assert entry == dict(
        name="head.greedy_steps_pct", unit="%", better="higher",
        source="program_counter", layer="engine", moves="out_tokens_per_s")
    for cell in bench["workloads"]:
        assert entry in spec.Cell(cell["name"]).per_layer
