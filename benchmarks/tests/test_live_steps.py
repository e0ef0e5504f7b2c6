"""``kernel.live_steps_pct`` (PR 30): the reader against hand-made
counters, and nothing where the program keeps none."""
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
    return spec.load_module("per_layer", "kernel.live_steps_pct").read(ctx)


def test_live_steps_share_of_the_grid():
    stats = (types.SimpleNamespace(attn_steps_grid=256, attn_steps_live=80),
             types.SimpleNamespace(attn_steps_grid=2816, attn_steps_live=820))
    assert _read(stats) == pytest.approx(100.0 * 740 / 2560)
    assert _read((stats[0], stats[0])) is None       # no step in the window


def test_a_program_without_the_counters_reads_nothing():
    old = (types.SimpleNamespace(steps=1), types.SimpleNamespace(steps=2))
    assert _read(old) is None


def test_the_metric_is_declared_for_every_ttft_cell():
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        entry, = [m for m in json.load(f)["per_layer"]
                  if m["name"] == "kernel.live_steps_pct"]
    assert entry == dict(
        name="kernel.live_steps_pct", unit="%", better="higher",
        source="program_counter", layer="kernels", moves="ttft_p50_ms")
    for cell in ("mistral-7b.prefill-closed", "mixtral-8x7b.prefill-closed",
                 "minicpm-sala.longdoc-closed"):
        assert entry in spec.Cell(cell).per_layer
    assert entry not in spec.Cell("mistral-7b.decode-closed").per_layer
