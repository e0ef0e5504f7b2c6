"""The six ``setup.*`` metrics (PR 56): each reader against a hand-made
opening snapshot — a warm process, a cold one, a server that keeps no
build log (the parent: nothing, and no raise) — and their declarations,
found in ``BENCHMARK.json`` by name."""
import json
import os
import types

import pytest

from benchmarks.harness import reduce, spec
from benchmarks.harness.loop import Window

NAMES = {
    "setup.programs": ("count", "lower"),
    "setup.trace_s": ("s", "lower"),
    "setup.lower_s": ("s", "lower"),
    "setup.backend_s": ("s", "lower"),
    "setup.cache_hit_pct": ("%", "higher"),
    "setup.other_build_s": ("s", "lower"),
}


def _record(ordinal=1, cache="hit", **kw):
    return dict(dict(
        key="('mixed_fused', 1, False, 'greedy', 0)", ordinal=ordinal,
        trace_s=0.5, lower_s=0.25, backend_s=0.125, cache=cache,
        cache_load_s=0.1, saved_s=12.0, start=103.0, step=0, in_step=True,
        inner_s=0.2, inner={"_ragged_call": [1, 0.15], "add": [40, 0.01]}),
        **kw)


def _stats(hits, misses, backend_s, other=True, retrace=False):
    cache = "hit" if hits else "miss"
    builds = {"ff_step_c1": _record(cache=cache),
              "ff_step_c128": _record(cache=cache, step=2)}
    if retrace:
        builds["ff_step_c1#2"] = _record(ordinal=2, cache=cache)
    if other:
        builds["other"] = dict(count=9, trace_s=1.0, lower_s=2.0,
                               backend_s=4.0, cache_hits=3, cache_misses=6)
    return types.SimpleNamespace(
        builds=builds, compiles=len(builds) - other, retraces=int(retrace),
        build_trace_s=1.0, build_lower_s=0.5, build_backend_s=backend_s,
        build_cache_hits=hits, build_cache_misses=misses,
        build_in_step_s=1.75, build_other_s=7.0 if other else 0.0)


def _read(name, stats_open, log=None):
    win = Window()
    win.stats_open = stats_open
    win.stats_close = stats_open
    win.opened = 150.0
    ctx = reduce.Context(
        window=win, setup_s=50.0, cfg={}, peaks=None, trace=reduce.NoTrace(),
        engine_serving=types.SimpleNamespace(mixed_chunk=128),
        log=log or (lambda line: None))
    return spec.load_module("per_layer", name).read(ctx)


WARM = _stats(hits=2, misses=0, backend_s=0.25)
COLD = _stats(hits=0, misses=2, backend_s=40.0)


@pytest.mark.parametrize("name, warm, cold", [
    ("setup.programs", 2, 2),
    ("setup.trace_s", 1.0, 1.0),
    ("setup.lower_s", 0.5, 0.5),
    ("setup.backend_s", 0.25, 40.0),
    ("setup.cache_hit_pct", 100.0, 0.0),
    ("setup.other_build_s", 7.0, 7.0),
])
def test_a_warm_and_a_cold_process(name, warm, cold):
    assert _read(name, WARM) == warm
    assert _read(name, COLD) == cold


@pytest.mark.parametrize("name", sorted(NAMES))
def test_a_server_without_the_log_reads_nothing(name):
    parent = types.SimpleNamespace(steps=3, compiles=5, retraces=0)
    assert _read(name, parent) is None


def test_programs_counts_builds_and_not_other():
    assert _read("setup.programs", _stats(2, 0, 0.25, retrace=True)) == 3
    assert _read("setup.programs", _stats(2, 0, 0.25, other=False)) == 2


def test_hit_share_is_of_the_builds_that_asked():
    assert _read("setup.cache_hit_pct", _stats(3, 1, 1.0)) == 75.0
    # no build asked the cache: 0.0, and not nothing
    assert _read("setup.cache_hit_pct", _stats(0, 0, 1.0)) == 0.0


def test_trace_s_logs_a_line_a_program_and_the_sums():
    lines = []
    assert _read("setup.trace_s", _stats(2, 0, 0.25, retrace=True),
                 lines.append) == 1.0
    assert all(line.startswith("[builds] ") for line in lines)
    c1, = [line for line in lines if line.startswith("[builds] ff_step_c1 ")]
    # the start against the process's own (window opened at 150 after a
    # set-up of 50: the process began at 100), the step, the parts, the
    # cache's word, the longest inner names
    assert "#1 at +3.0s step 0: trace 0.50s (inner 0.20s) lower 0.25s " in c1
    assert "backend 0.12s cache hit (load 0.10s, saved 12.00s)" in c1
    assert c1.endswith("inner _ragged_call 1 x 0.15s, add 40 x 0.01s")
    assert any(line.startswith("[builds] ff_step_c1#2 #2 ") for line in lines)
    assert any(line.startswith("[builds] other: 9 programs") for line in lines)
    assert lines[-1] == ("[builds] step programs: trace 1.00s + lower 0.50s "
                         "+ backend 0.25s, other builds 7.00s, of setup_s "
                         "50.00s")
    assert len(lines) == 5


def test_the_six_are_declared_by_name_for_every_cell():
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    by_name = {}
    for m in bench["per_layer"]:
        by_name.setdefault(m["name"], []).append(m)
    for name, (unit, better) in NAMES.items():
        entry, = by_name[name]
        assert entry == dict(
            name=name, unit=unit, better=better, source="program_counter",
            layer="engine", moves="setup_s"), entry
        assert "workloads" not in entry
        for cell in bench["workloads"]:
            assert entry in spec.Cell(cell["name"]).per_layer
    # nothing else moves setup_s, and setup_s is every cell's
    assert {m["name"] for m in bench["per_layer"]
            if m["moves"] == "setup_s"} == set(NAMES)
    setup, = [m for m in bench["end_to_end"] if m["name"] == "setup_s"]
    assert "workloads" not in setup
