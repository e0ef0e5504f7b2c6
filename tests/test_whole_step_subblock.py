"""Whole-step sub-block weight streaming + whole-step mixed walk.

The contract under test (the PR that makes the megakernel the DEFAULT
path, not the small-model path):

* when a layer's working set prices over the VMEM budget, the engine's
  gate (serve/engine._whole_step_vmem_gate) picks a sub-block TILE
  COUNT — the walk streams each projection weight in output-column
  sub-tiles (serve/kernels._whole_step_decode_tiled) — instead of
  falling back to the per-layer path; the tiled walk stays BITWISE the
  unfused ``kernels="xla"`` step over fp/int8/int4 pools;
* the walk also serves the (R, C) chunked-prefill MIXED step: one
  dispatched program per mixed step, bitwise the unfused run;
* a malformed FF_WHOLE_STEP_VMEM_MB raises a ValueError NAMING the env
  var at engine construction — never a bare float() traceback;
* the gate's telemetry (whole_step_fallbacks, whole_step_vmem_est) is
  mirrored into SchedulerStats and aggregates through ClusterStats;
* 7B-class layer geometry (>12 MB/layer — the shape PR 15 used to FALL
  BACK on) now auto-picks tiles>1 under the DEFAULT budget and runs
  the walk BITWISE the unfused step over fp/int8/int4 pools — asserted
  in a single-device subprocess, because the 8-virtual-device CPU's
  width-dependent GEMM thread blocking is a host-interpreter artifact
  (see test_7b_class_subblock_bitwise) — with zero steady-state
  recompiles (slow-marked; premerge gate 13 runs them unfiltered).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.models import llama
from flexflow_tpu.serve import (
    InferenceEngine,
    RequestManager,
    ServingConfig,
)
from flexflow_tpu.serve import kernels as pk
from flexflow_tpu.serve.batch_config import GenerationConfig
from flexflow_tpu.serve.request_manager import RequestStatus


@pytest.fixture(scope="module")
def tiny():
    cfg = llama.LLaMAConfig.tiny(dtype=jnp.float32)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _sc(fused, *, slots=4, **kw):
    return ServingConfig(
        max_requests_per_batch=slots,
        max_sequence_length=48,
        prefill_chunk=8,
        max_spec_tree_tokens=8,
        cache_dtype=jnp.float32,
        kv_layout="paged",
        page_size=8,
        kernels="xla",
        fused_decode=fused,
        sanitizers=("retrace",),
        **kw,
    )


PROMPTS = [[(i * 7 + j * 3 + 1) % 256 for j in range(5 + i)]
           for i in range(4)]
GENS = [
    GenerationConfig(),
    GenerationConfig(do_sample=True, topk=5, temperature=0.8, topp=2.0),
    GenerationConfig(),
    GenerationConfig(do_sample=True, topk=17, temperature=1.2, topp=2.0),
]


def _generate(rm, n_new=6):
    rids = [rm.submit(p, g, max_new_tokens=n_new)
            for p, g in zip(PROMPTS, GENS)]
    while rm.step():
        pass
    rm.drain()
    return [list(rm.requests[r].output_tokens) for r in rids]


def _squeeze_mb(eng):
    """A budget (MB) BETWEEN the first sub-block tiling's working set
    and the untiled one, priced exactly the way the engine's gate
    prices — forces tiles>1 without tripping the floor fallback."""
    cfg = eng.cfg
    la, ha = eng.model.whole_step_weight_layout(eng.params, cfg)
    roles = eng.model.whole_step_tile_roles(cfg)
    S = eng.serving.pages_per_slot * eng.serving.page_size
    R = eng.num_slots

    def est(tiles, C):
        x0 = np.zeros((R, C, cfg.hidden_size), jnp.dtype(cfg.dtype))
        m = np.zeros((R, C, S), np.bool_)
        return pk.whole_step_vmem_bytes(
            la, ha, eng.cache, x0, m, cfg.num_attention_heads,
            tiles=tiles, tile_roles=roles,
        )

    force = next(t for t in pk.whole_step_tile_candidates(la, roles)
                 if t > 1)
    lo = max(est(force, 1), est(force, eng.serving.prefill_chunk))
    hi = est(1, 1)
    assert lo < hi, (lo, hi)
    return (lo + hi) / 2 / (1024 * 1024)


# ---------------------------------------------------------------------------
# satellite: FF_WHOLE_STEP_VMEM_MB parsing


def test_vmem_env_malformed_raises(tiny, monkeypatch):
    """A budget override that float() cannot parse fails LOUDLY at
    engine construction, naming the env var — not a bare ValueError
    from inside the gate."""
    cfg, params = tiny
    monkeypatch.setenv("FF_WHOLE_STEP_VMEM_MB", "twelve")
    with pytest.raises(ValueError, match="FF_WHOLE_STEP_VMEM_MB"):
        InferenceEngine(llama, cfg, params, _sc(("whole_step",)))


@pytest.mark.parametrize("bad", ["0", "-3"])
def test_vmem_env_nonpositive_raises(tiny, monkeypatch, bad):
    cfg, params = tiny
    monkeypatch.setenv("FF_WHOLE_STEP_VMEM_MB", bad)
    with pytest.raises(ValueError, match="FF_WHOLE_STEP_VMEM_MB"):
        InferenceEngine(llama, cfg, params, _sc(("whole_step",)))


def test_vmem_env_valid_and_default(tiny, monkeypatch):
    """The happy directions: unset resolves the kernel default; a
    well-formed override resolves to MB; a generous override keeps the
    walk on at tiles=1."""
    cfg, params = tiny
    monkeypatch.delenv("FF_WHOLE_STEP_VMEM_MB", raising=False)
    assert (InferenceEngine._whole_step_vmem_budget()
            == pk.WHOLE_STEP_VMEM_BUDGET)
    monkeypatch.setenv("FF_WHOLE_STEP_VMEM_MB", "14.5")
    assert (InferenceEngine._whole_step_vmem_budget()
            == int(14.5 * 1024 * 1024))
    eng = InferenceEngine(llama, cfg, params, _sc(("whole_step",)))
    assert eng.whole_step_on and eng.whole_step_tiles == 1
    assert eng.whole_step_fallbacks == 0


# ---------------------------------------------------------------------------
# pricing + tile selection units


def test_tile_candidates_are_gcd_divisors(tiny):
    cfg, params = tiny
    la, _ = llama.whole_step_weight_layout(params, cfg)
    roles = llama.whole_step_tile_roles(cfg)
    cands = pk.whole_step_tile_candidates(la, roles)
    assert cands[0] == 1 and list(cands) == sorted(cands)
    for t in cands:
        for wname, _b in roles.values():
            assert la[wname].shape[-1] % t == 0, (t, wname)


def test_pick_tiles_squeezed_and_floor(tiny):
    """pick_tiles: huge budget -> 1; a budget between the first
    sub-block tiling and the untiled set -> that tiling; a budget
    below the irreducible floor -> (None, best_est)."""
    cfg, params = tiny
    la, ha = llama.whole_step_weight_layout(params, cfg)
    roles = llama.whole_step_tile_roles(cfg)
    cache = llama.init_paged_kv_cache(cfg, 6, 8)
    x0 = np.zeros((2, 1, cfg.hidden_size), np.float32)
    mask = np.zeros((2, 1, 32), np.bool_)
    args = (la, ha, cache, x0, mask, cfg.num_attention_heads)
    t1, est1 = pk.whole_step_pick_tiles(
        *args, tile_roles=roles, budget=1 << 40)
    assert t1 == 1 and est1 == pk.whole_step_vmem_bytes(*args)
    force = next(t for t in pk.whole_step_tile_candidates(la, roles)
                 if t > 1)
    estf = pk.whole_step_vmem_bytes(*args, tiles=force, tile_roles=roles)
    assert estf < est1, "tiling must shrink a weights-dominated set"
    tf, _ = pk.whole_step_pick_tiles(
        *args, tile_roles=roles, budget=(estf + est1) // 2)
    assert tf == force
    tn, floor_est = pk.whole_step_pick_tiles(
        *args, tile_roles=roles, budget=64)
    assert tn is None and floor_est > 64


# ---------------------------------------------------------------------------
# forced sub-block walk: bitwise the unfused step


def _pair(cfg, params, kv_quant, tiles):
    """Prefill through the unfused XLA step, then ONE decode step both
    ways — the unfused step vs the TILED whole-step walk."""
    rng = np.random.RandomState(0)
    ps, NP, Pp = 8, 4, 6
    cache = llama.init_paged_kv_cache(cfg, Pp, ps, kv_quant=kv_quant)
    R = 2
    pt = jnp.asarray([[0, 1, Pp, Pp], [2, 3, Pp, Pp]], jnp.int32)
    ptoks = jnp.asarray(rng.randint(0, cfg.vocab_size, (R, 5)), jnp.int32)
    ppos = jnp.broadcast_to(jnp.arange(5, dtype=jnp.int32), (R, 5))
    step = functools.partial(
        llama.serve_step_paged, cfg=cfg, cache_len=NP * ps - 1,
        kernels="xla", kv_quant=kv_quant,
    )
    _, cache = jax.jit(step)(
        params, cache, ptoks, ppos, jnp.full((R,), 4, jnp.int32),
        None, None, pt,
    )
    dtok = jnp.asarray(rng.randint(0, cfg.vocab_size, (R, 1)), jnp.int32)
    dpos = jnp.full((R, 1), 5, jnp.int32)
    dlidx = jnp.zeros((R,), jnp.int32)
    ul, uc = jax.jit(step)(params, cache, dtok, dpos, dlidx,
                           None, None, pt)
    whole = functools.partial(
        llama.serve_step_whole, cfg=cfg, cache_len=NP * ps - 1,
        kv_quant=kv_quant, tiles=tiles,
    )
    wl, wt, wc = jax.jit(whole)(params, cache, dtok, dpos, dlidx, pt)
    return (ul, uc), (wl, wt, wc), Pp


@pytest.mark.parametrize("tiles", [2, 4])
def test_subblock_walk_bitwise_vs_unfused(tiny, tiles):
    """The tiled walk computes each projection in output-column tiles,
    and under jax 0.9 XLA:CPU's dot picks its accumulation blocking by
    output width: ``x @ W[:, tile]`` is not always bitwise the tile's
    columns of ``x @ W`` (a (4, 64) x (64, 64) float32 product cut into
    16-wide tiles differs in the last bits; tiles=1 stays bitwise). That
    is the order of one float32 sum, so the walk is held to a few
    float32 roundings of the largest value — measured 2.3e-7 of it —
    and to the same greedy tokens, not to the bytes."""
    cfg, params = tiny
    (ul, uc), (wl, wt, wc), scratch = _pair(cfg, params, None, tiles)

    def close(ref, got):
        ref, got = np.asarray(ref, np.float32), np.asarray(got, np.float32)
        atol = 8 * np.finfo(np.float32).eps * np.abs(ref).max()
        np.testing.assert_allclose(got, ref, rtol=0, atol=atol)

    close(ul, wl)
    assert bool(jnp.all(
        wt == jnp.argmax(ul.astype(jnp.float32), -1).astype(jnp.int32)
    ))
    for name in uc:
        close(uc[name][:, :scratch], wc[name][:, :scratch])


@pytest.mark.slow  # quantized pools through the tiled interpret walk
# (~4s); premerge gate 13 runs them unfiltered
@pytest.mark.parametrize("kv_quant", ["int8", "int4"])
def test_subblock_walk_bitwise_quantized_pools(tiny, kv_quant):
    cfg, params = tiny
    (ul, uc), (wl, wt, wc), scratch = _pair(cfg, params, kv_quant, 2)
    assert bool(jnp.all(ul == wl))
    assert bool(jnp.all(
        wt == jnp.argmax(ul.astype(jnp.float32), -1).astype(jnp.int32)
    ))
    for name in uc:
        assert bool(jnp.all(uc[name][:, :scratch] == wc[name][:, :scratch]))


# ---------------------------------------------------------------------------
# engine integration: squeezed budget -> tiles>1, not a fallback


@pytest.fixture(scope="module")
def wide():
    """tiny, widened so a squeeze interval EXISTS: the tiny config's
    weights are so small that the mixed step's accumulator floor at
    C=8 already exceeds the untiled decode working set — no budget can
    force tiles>1 there. 128/384-wide weights dominate the floor."""
    cfg = llama.LLaMAConfig.tiny(
        hidden_size=128, intermediate_size=384, dtype=jnp.float32
    )
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def test_engine_squeezed_budget_picks_tiles(wide, monkeypatch):
    """Under a budget between the tiled and untiled working sets the
    gate MUST pick a sub-block tile count (the old PR-15 behavior was
    a fallback) and generations stay bitwise the unfused scheduler."""
    cfg, params = wide
    probe = InferenceEngine(llama, cfg, params, _sc(()))
    monkeypatch.setenv("FF_WHOLE_STEP_VMEM_MB", repr(_squeeze_mb(probe)))
    eng = InferenceEngine(llama, cfg, params, _sc(("whole_step",)))
    assert eng.whole_step_on, "squeezed budget must NOT fall back"
    assert eng.whole_step_tiles > 1
    assert eng.whole_step_mixed_on and eng.whole_step_mixed_tiles > 1
    assert eng.whole_step_fallbacks == 0
    assert eng.whole_step_vmem_est > 0
    outs = _generate(RequestManager(eng))
    monkeypatch.delenv("FF_WHOLE_STEP_VMEM_MB")
    assert outs == _generate(RequestManager(probe))
    assert eng.retrace_guard.retraces == 0


def test_mixed_walk_one_dispatch_per_step(tiny):
    """Sync scheduler: with the whole-step MIXED walk on, every step
    that admits or prefills is ONE dispatched program — and the whole
    run dispatches strictly fewer programs than the unfused manager."""
    cfg, params = tiny
    counts = {}
    for fused in ((), ("whole_step",)):
        rm = RequestManager(InferenceEngine(llama, cfg, params, _sc(fused)))
        rm.supports_fast_decode = False
        eng = rm.engine
        rids = [rm.submit(p, g, max_new_tokens=6)
                for p, g in zip(PROMPTS, GENS)]
        mixed_d, n_mixed = 0, 0
        while True:
            mixed = bool(rm.pending
                         or rm._active(RequestStatus.PREFILLING))
            d0 = eng.dispatch_count
            if not rm.step():
                break
            if mixed:
                mixed_d += eng.dispatch_count - d0
                n_mixed += 1
        rm.drain()
        counts[fused] = (
            [list(rm.requests[r].output_tokens) for r in rids],
            mixed_d, n_mixed, eng.dispatch_count,
        )
        if fused:
            assert eng.whole_step_mixed_on
            assert n_mixed > 0 and mixed_d == n_mixed, (
                "whole-step mixed steps must dispatch ONE program",
                mixed_d, n_mixed,
            )
        assert eng.retrace_guard.retraces == 0
    assert counts[()][0] == counts[("whole_step",)][0]
    assert counts[("whole_step",)][3] < counts[()][3]


# ---------------------------------------------------------------------------
# satellite: gate telemetry through SchedulerStats / ClusterStats


def test_gate_telemetry_mirrored(tiny, monkeypatch):
    """whole_step_fallbacks / whole_step_vmem_est reach SchedulerStats
    (the scheduler's stats chokepoint) and SUM through ClusterStats'
    replica aggregation."""
    from flexflow_tpu.metrics import ClusterStats

    cfg, params = tiny
    rm = RequestManager(
        InferenceEngine(llama, cfg, params, _sc(("whole_step",)))
    )
    _generate(rm, n_new=2)
    s = rm.stats.snapshot()
    assert s["whole_step_fallbacks"] == 0
    assert s["whole_step_vmem_est"] == rm.engine.whole_step_vmem_est > 0
    # a budget below the floor flips the path off and counts ONE fallback
    monkeypatch.setenv("FF_WHOLE_STEP_VMEM_MB", "0.001")
    rm2 = RequestManager(
        InferenceEngine(llama, cfg, params, _sc(("whole_step",)))
    )
    _generate(rm2, n_new=2)
    s2 = rm2.stats.snapshot()
    assert not rm2.engine.whole_step_on
    assert s2["whole_step_fallbacks"] == 1
    agg = ClusterStats().snapshot([rm.stats, rm2.stats])["replicas"]
    assert agg["whole_step_fallbacks"] == 1
    assert (agg["whole_step_vmem_est"]
            == s["whole_step_vmem_est"] + s2["whole_step_vmem_est"])


# ---------------------------------------------------------------------------
# 7B-class geometry: over-budget layers auto-pick tiles (premerge gate 13)

_7B = dict(
    # scaled 7B-class projection geometry: 4 * 512x512 attention mats +
    # 3 * 512x1536 MLP mats = ~13.6 MB/layer f32 — OVER the default
    # 12 MB budget, the shape PR 15 fell back on
    vocab_size=128,
    hidden_size=512,
    intermediate_size=1536,
    num_hidden_layers=2,
    num_attention_heads=8,
    num_key_value_heads=8,
    max_position_embeddings=128,
)


@pytest.fixture(scope="module")
def sevenb():
    cfg = llama.LLaMAConfig(dtype=jnp.float32, **_7B)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


@pytest.mark.slow  # 512-wide interpret walk (premerge gate 13 unfiltered)
def test_7b_class_geometry_auto_picks_tiles(sevenb):
    """NO env override: the default budget prices the layer over 12 MB
    and the gate picks a sub-block tile count — the megakernel is the
    default path for big-layer geometry, not a fallback."""
    cfg, params = sevenb
    la, ha = llama.whole_step_weight_layout(params, cfg)
    roles = llama.whole_step_tile_roles(cfg)
    cache = llama.init_paged_kv_cache(cfg, 6, 8)
    x0 = np.zeros((2, 1, cfg.hidden_size), np.float32)
    mask = np.zeros((2, 1, 32), np.bool_)
    args = (la, ha, cache, x0, mask, cfg.num_attention_heads)
    assert pk.whole_step_vmem_bytes(*args) > pk.WHOLE_STEP_VMEM_BUDGET
    tiles, est = pk.whole_step_pick_tiles(
        *args, tile_roles=roles, budget=pk.WHOLE_STEP_VMEM_BUDGET)
    assert tiles is not None and tiles > 1
    assert est <= pk.WHOLE_STEP_VMEM_BUDGET
    eng = InferenceEngine(llama, cfg, params, _sc(("whole_step",)))
    assert eng.whole_step_on and eng.whole_step_tiles > 1
    assert eng.whole_step_fallbacks == 0


# Run inside a SINGLE-DEVICE subprocess (see the test below for why):
# auto-pick the tile count under the DEFAULT budget and assert the
# tiled walk bitwise the unfused step — logits, greedy tokens, pool
# bytes. argv[1] is the pool mode ("fp" | "int8" | "int4").
_7B_BITWISE_CHILD = r"""
import sys

sys.path.insert(0, sys.argv[2])
import jax

jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp, numpy as np
import test_whole_step_subblock as T
from flexflow_tpu.models import llama
from flexflow_tpu.serve import kernels as pk

assert jax.device_count() == 1, jax.devices()
kvq = None if sys.argv[1] == "fp" else sys.argv[1]
cfg = llama.LLaMAConfig(dtype=jnp.float32, **T._7B)
params = llama.init_params(jax.random.PRNGKey(0), cfg)
la, ha = llama.whole_step_weight_layout(params, cfg)
roles = llama.whole_step_tile_roles(cfg)
cache = llama.init_paged_kv_cache(cfg, 6, 8, kv_quant=kvq)
x0 = np.zeros((2, 1, cfg.hidden_size), np.float32)
mask = np.zeros((2, 1, 32), np.bool_)
args = (la, ha, cache, x0, mask, cfg.num_attention_heads)
assert pk.whole_step_vmem_bytes(*args) > pk.WHOLE_STEP_VMEM_BUDGET
tiles, _ = pk.whole_step_pick_tiles(
    *args, tile_roles=roles, budget=pk.WHOLE_STEP_VMEM_BUDGET)
assert tiles is not None and tiles > 1, tiles
(ul, uc), (wl, wt, wc), scratch = T._pair(cfg, params, kvq, tiles)
assert bool(jnp.all(ul == wl)), "tiled walk logits diverge"
assert bool(jnp.all(
    wt == jnp.argmax(ul.astype(jnp.float32), -1).astype(jnp.int32)
)), "greedy tokens diverge"
for n in uc:
    assert bool(jnp.all(uc[n][:, :scratch] == wc[n][:, :scratch])), n
print("BITWISE_OK tiles=%d" % tiles)
"""


@pytest.mark.slow  # subprocess jax startup + ~13 MB of weights through
# the tiled interpret walk per pool mode (premerge gate 13 unfiltered)
@pytest.mark.parametrize("kv_quant", ["fp", "int8", "int4"])
def test_7b_class_subblock_bitwise(kv_quant):
    """The auto-picked sub-block walk on the over-budget geometry is
    BITWISE the unfused XLA step — logits, greedy tokens, pool bytes —
    over fp/int8/int4 pools. Runs in a single-device subprocess:
    conftest forces 8 virtual CPU devices, which splits XLA:CPU's GEMM
    thread blocking by OUTPUT WIDTH, so a column slice of a 512-wide
    weight sums its (never-split) contraction in a different order
    than the full matmul (~1e-7 drift) — a host-interpreter artifact,
    not a property of the walk. On one device (and on the MXU, whose
    accumulation order per output tile is width-independent) the tiled
    walk is bitwise, which is what this asserts."""
    import os
    import subprocess
    import sys

    here = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = ""  # drop the 8-virtual-device force
    proc = subprocess.run(
        [sys.executable, "-c", _7B_BITWISE_CHILD, kv_quant, here],
        cwd=os.path.dirname(here), env=env,
        capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, (proc.stdout, proc.stderr)
    assert "BITWISE_OK" in proc.stdout, proc.stdout


@pytest.mark.slow  # two tile-count keys through the engine (~6s);
# premerge gate 13 unfiltered
def test_tile_count_retrace_guard(wide, monkeypatch):
    """Different tile counts are DIFFERENT step keys, each compiled
    once: a squeezed-budget engine and a default-budget engine both
    finish whole generations with zero steady-state recompiles."""
    cfg, params = wide
    probe = InferenceEngine(llama, cfg, params, _sc(()))
    outs = []
    for mb in (None, _squeeze_mb(probe)):
        if mb is None:
            monkeypatch.delenv("FF_WHOLE_STEP_VMEM_MB", raising=False)
        else:
            monkeypatch.setenv("FF_WHOLE_STEP_VMEM_MB", repr(mb))
        eng = InferenceEngine(llama, cfg, params, _sc(("whole_step",)))
        if mb is None:
            assert eng.whole_step_tiles == 1
        else:
            assert eng.whole_step_tiles > 1
        rm = RequestManager(eng)
        outs.append(_generate(rm))
        # steady state: run a SECOND batch on the same engine — every
        # step key is warm, nothing recompiles
        outs.append(_generate(rm))
        assert eng.retrace_guard.retraces == 0
    # corresponding batches match across tile counts (successive
    # batches on ONE engine legitimately differ: the sampled rows
    # draw fresh per-request seeds)
    assert outs[0] == outs[2] and outs[1] == outs[3]
