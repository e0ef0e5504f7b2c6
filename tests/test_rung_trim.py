"""The scheduler keeps a mixed step under a rung (ISSUE 61): served, a
kind of page pool each. A closed loop whose steps give prompt tokens up
(``SchedulerStats.rung_trims``), so that chunks start off a page
boundary and span two pages, generates, greedy, the tokens of the same
requests served one at a time; no step of the run sits in a rung that
it passes the next narrower one by no more than the slots; and a
prompt's row left with no token is not in its step at all.

Tiny presets on the CPU, float32, every packed family that a cell
runs: a llama-shaped family (K/V lines), ``deepseek_v3`` (latent lines,
the rope keys written by whole pages), ``smallthinker`` and ``laguna``
(a second class of page whose table rolls behind a window),
``longcat_flash`` (two latent lines a layer), and the families that
keep a state a slot beside the pool, which a trimmed chunk splits at
any token: ``olmo_hybrid``, ``qwen3_next`` and ``granite_hybrid`` (a
recurrent state and a convolution's tail) and ``lfm2_moe`` (a
convolution's tail). Each on the Pallas kernels its cell runs (in
interpret mode; the llama-shaped family on XLA's too).
4 slots x chunk 16 on pages of 16 lines: the ladder is (16, 32, 64),
DeepSeek's cell at an eighth of its extents, and three decoding rows
beside one prompt's chunk hold 19 tokens, two beside two 34. The rule
itself is held as a function in tests/test_packed_step.py.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.models import (
    deepseek_v3, granite_hybrid, laguna, lfm2_moe, longcat_flash, mistral,
    olmo_hybrid, qwen3_next, smallthinker)
from flexflow_tpu.serve import InferenceEngine, RequestManager, ServingConfig
from flexflow_tpu.serve.request_manager import RequestStatus

PAGE, CHUNK, SLOTS, MAX_SEQ = 16, 16, 4, 128
FAMILIES = {"mistral": mistral, "deepseek_v3": deepseek_v3,
            "olmo_hybrid": olmo_hybrid, "smallthinker": smallthinker,
            "laguna": laguna, "longcat_flash": longcat_flash,
            "granite_hybrid": granite_hybrid, "qwen3_next": qwen3_next,
            "lfm2_moe": lfm2_moe}
# the families that keep a state a slot beside the page pool
STATE = {"olmo_hybrid", "granite_hybrid", "qwen3_next", "lfm2_moe"}


def _engine(family, kernels, slots=SLOTS, chunk=CHUNK):
    mod = FAMILIES[family]
    cfg = mod.tiny(dtype=jnp.float32)
    params = mod.init_params(jax.random.PRNGKey(0), cfg)
    return InferenceEngine(mod, cfg, params, ServingConfig(
        kv_layout="paged", kernels=kernels, page_size=PAGE,
        max_requests_per_batch=slots, max_sequence_length=MAX_SEQ,
        prefill_chunk=chunk, cache_dtype=jnp.float32))


def _prompts(n, least=40, spread=37, vocab=250):
    """Prompts of three to five chunks that end inside one, unless
    told other lengths."""
    rng = np.random.default_rng(61)
    return [rng.integers(1, vocab, least + (i * 11) % spread).tolist()
            for i in range(n)]


def _watch(rm):
    """Every mixed step of ``rm`` from here on: (real tokens, width,
    tokens given up, the rows' first positions and real queries)."""
    steps, eng = [], rm.engine
    run, scratch = eng.run_mixed, eng.scratch_pos

    def run_mixed(last, tokens, use_last, positions, *a, **kw):
        positions = np.asarray(positions)
        if positions.shape[1] > 1:  # not the decode step
            steps.append(dict(first=positions[:, 0].copy(),
                              count=(positions != scratch).sum(1)))
        return run(last, tokens, use_last, positions, *a, **kw)

    def note(real, width, trimmed=0):
        steps[-1].update(real=real, width=width, trimmed=trimmed)
        type(rm.stats).note_step_tokens(rm.stats, real, width, trimmed)

    eng.run_mixed = run_mixed
    rm.stats.note_step_tokens = note
    return steps


def _finish(rm):
    while rm.step():
        pass
    rm.drain()


def _served(eng, prompts, new):
    """The outputs of ``prompts`` served one at a time, then all at
    once (a closed loop over the engine's slots), and the second run's
    steps."""
    rm = RequestManager(eng)
    alone = []
    for i, p in enumerate(prompts):
        rid = rm.submit(p, max_new_tokens=new(i))
        _finish(rm)
        alone.append(list(rm.requests[rid].output_tokens))
    assert rm.stats.rung_trims == 0 and rm.stats.mixed_steps > 0
    steps = _watch(rm)
    rids = [rm.submit(p, max_new_tokens=new(i)) for i, p in enumerate(prompts)]
    _finish(rm)
    return rm, alone, [list(rm.requests[r].output_tokens) for r in rids], steps


@pytest.mark.parametrize("family, kernels", [
    ("mistral", "xla"), ("mistral", "pallas"), ("deepseek_v3", "pallas"),
    ("olmo_hybrid", "pallas"), ("smallthinker", "pallas"),
    ("laguna", "pallas"), ("longcat_flash", "pallas"),
    ("granite_hybrid", "pallas"), ("qwen3_next", "pallas"),
    ("lfm2_moe", "pallas")])
def test_trimmed_steps_generate_what_one_request_at_a_time_does(family, kernels):
    eng = _engine(family, kernels)
    ladder = eng.pack_ladder(CHUNK)
    assert ladder == (16, 32)
    rm, alone, together, steps = _served(
        eng, _prompts(9), new=lambda i: 6 + (i * 5) % 9)
    assert together == alone
    s = rm.stats
    assert s.rung_trims > 0 and s.rung_trim_tokens >= s.rung_trims
    assert s.rung_trims == sum(st["trimmed"] > 0 for st in steps)
    assert s.rung_trim_tokens == sum(st["trimmed"] for st in steps)
    assert s.failed == 0 and s.preemptions == 0
    for st in steps:
        # on the narrowest program that holds it, and over no rung by
        # the few tokens that decoding rows add
        assert st["width"] == eng.pack_width(st["real"], CHUNK)
        assert st["real"] == st["count"].sum()
        assert not any(0 < st["real"] - w <= SLOTS for w in ladder), st
        if st["trimmed"]:
            assert st["real"] in ladder
    # the 19- and 34-token steps of DeepSeek's cell, an eighth the size
    assert {st["real"] + st["trimmed"] for st in steps if st["trimmed"]} >= {19, 34}
    # chunks that start off a page boundary and span two pages
    spans = [(int(f), int(n)) for st in steps
             for f, n in zip(st["first"], st["count"]) if n > 1 and f % PAGE]
    assert any(f // PAGE != (f + n - 1) // PAGE for f, n in spans), spans

    if family not in STATE:
        return
    # the 64-slot cells' step: prompts of ONE chunk, 13 to 16 tokens,
    # admitted beside three decoding rows. The chunk is the prompt's
    # last and is split all the same, at whatever token the rung says,
    # and the state beside the pool is carried across the split
    rm, alone, together, steps = _served(
        eng, _prompts(8, least=13, spread=4), new=lambda i: 4 + (i * 3) % 4)
    assert together == alone and rm.stats.rung_trims > 0
    assert any(f > 0 and n > 1 and f + n <= CHUNK for st in steps
               for f, n in zip(st["first"], st["count"])), steps


def test_a_row_left_with_no_token_is_not_in_the_step():
    """6 slots x chunk 4, ladder (6, 12, 24): two decoding rows beside
    four prompts' chunks hold 18 tokens, six over the rung at 12, and
    the newest prompt gives its whole chunk up. Its request stays as
    it was: no row, no pipeline reference, no entry in the flush."""
    eng = _engine("mistral", "xla", slots=6, chunk=4)
    assert eng.pack_ladder(4) == (6, 12)
    rm = RequestManager(eng)
    steps = _watch(rm)
    prompts = _prompts(6)
    first = [rm.submit(p[:5], max_new_tokens=30) for p in prompts[:2]]
    while any(rm.requests[r].status is not RequestStatus.DECODING for r in first):
        assert rm.step()
    rids = [rm.submit(p, max_new_tokens=3) for p in prompts[2:]]
    assert rm.step()
    st = steps[-1]
    assert (st["real"], st["trimmed"], st["width"]) == (12, 6, 12)
    reqs = [rm.requests[r] for r in rids]
    assert [r.n_sched for r in reqs] == [4, 4, 2, 0]
    left = reqs[-1]
    assert left.status is RequestStatus.PREFILLING and left.slot >= 0
    assert st["count"][left.slot] == 0 and left.pipeline_refs == 0
    assert all(rid != left.request_id for rid, *_ in rm._inflight[-1][1])
    _finish(rm)
    got = [list(rm.requests[r].output_tokens) for r in first + rids]

    trims, want = rm.stats.rung_trims, []
    for p, n in [(p[:5], 30) for p in prompts[:2]] + [(p, 3) for p in prompts[2:]]:
        rid = rm.submit(p, max_new_tokens=n)
        _finish(rm)
        want.append(list(rm.requests[rid].output_tokens))
    assert got == want and trims > 0
    assert rm.stats.rung_trims == trims  # one at a time: nothing to give up


def test_no_ladder_no_trim():
    """An engine whose mixed step is not packed (here the fused RoPE
    prologue; the dense layout, the ring and a family without
    ``PACKED_STEP`` likewise) hands every prompt its whole chunk."""
    mod = FAMILIES["mistral"]
    cfg = mod.tiny(dtype=jnp.float32)
    eng = InferenceEngine(mod, cfg, mod.init_params(jax.random.PRNGKey(0), cfg),
                          ServingConfig(
        kv_layout="paged", kernels="pallas", page_size=PAGE,
        max_requests_per_batch=SLOTS, max_sequence_length=MAX_SEQ,
        prefill_chunk=CHUNK, cache_dtype=jnp.float32,
        fused_decode=("rope_kv_write",)))
    assert eng.pack_ladder(CHUNK) == ()
    rm = RequestManager(eng)
    steps = _watch(rm)
    for i, p in enumerate(_prompts(6)):
        rm.submit(p, max_new_tokens=6 + i)
    _finish(rm)
    s = rm.stats
    assert s.rung_trims == 0 and s.rung_trim_tokens == 0
    assert set(s.steps_by_width) == {SLOTS * CHUNK}
    assert any(st["real"] in (19, 34) for st in steps)
    snap = s.snapshot()
    assert snap["rung_trims"] == 0 and snap["rung_trim_tokens"] == 0
    assert " trims=0/0tok" in s.report()
