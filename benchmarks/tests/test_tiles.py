"""``moe.tiles_per_expert`` (PR 51): the reader on hand-made counters,
its entry in ``BENCHMARK.json``, and the counter it reads
(``SchedulerStats.note_expert_counts``) on hand-made tokens per
expert."""
import json
import os
import types

import numpy as np
import pytest

from benchmarks.harness import reduce, spec
from benchmarks.harness.loop import Window

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = "moe.tiles_per_expert"


def _read(stats):
    win = Window()
    win.stats_open, win.stats_close = stats
    ctx = reduce.Context(
        window=win, setup_s=0.0, cfg={}, peaks=None, trace=reduce.NoTrace(),
        engine_serving=types.SimpleNamespace(mixed_chunk=128))
    return spec.load_module("per_layer", NAME).read(ctx)


def test_the_reader_divides_tiles_by_experts_hit():
    a = types.SimpleNamespace(moe_tiles=100, moe_experts_hit=40)
    b = types.SimpleNamespace(moe_tiles=650, moe_experts_hit=140)
    assert _read((a, b)) == pytest.approx(5.5)
    assert _read((a, a)) is None          # no routed step in the window
    # the parent keeps no such counter: nothing, and no error
    old = (types.SimpleNamespace(moe_experts_hit=1),
           types.SimpleNamespace(moe_experts_hit=9))
    assert _read(old) is None


def test_the_benchmark_lists_it_in_the_four_routed_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry, = (m for m in bench["per_layer"] if m["name"] == NAME)
    hit, = (m for m in bench["per_layer"] if m["name"] == "moe.experts_hit_pct")
    assert sorted(entry["workloads"]) == sorted(hit["workloads"])
    assert (entry["unit"], entry["better"], entry["source"], entry["layer"],
            entry["moves"]) == ("count", "lower", "program_counter", "kernels",
                                "out_tokens_per_s")


def test_the_counter_counts_tiles_under_the_steps_tile():
    stats_type = pytest.importorskip("flexflow_tpu.metrics").SchedulerStats
    if not hasattr(stats_type, "note_expert_counts"):
        pytest.skip("a program with no expert counters")
    stats = stats_type()
    counts = np.array([[0, 1, 16, 17, 96], [0, 0, 0, 0, 0]])
    try:
        stats.note_expert_counts(counts, 16)
    except TypeError:
        pytest.skip("a program before PR 51: the counts take no tile")
    assert (stats.moe_experts_hit, stats.moe_tiles) == (4, 1 + 1 + 2 + 6)
    stats.note_expert_counts(counts, 64)
    assert (stats.moe_experts_hit, stats.moe_tiles) == (8, 10 + 1 + 1 + 1 + 2)
