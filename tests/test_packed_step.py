"""The packed token axis of the mixed step (ISSUE 32): every rung of
the engine's ladder gives each real row's logits and each written K/V
line equal to the padded program's, the scheduler generates the same
tokens with and without it, every rung compiles once and none inside
serving, and a caller that passes no packing traces the padded step.

Tiny Mistral (with a window) and Mixtral on the CPU, ``kernels="xla"``
and the Pallas kernel in interpret mode; float32, where the padded and
the packed step agree to rounding of differently tiled matmuls (the
stated tolerance below; bitwise on this backend in practice).
"""
import collections
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.metrics import SchedulerStats
from flexflow_tpu.models import mistral, mixtral, transformer
from flexflow_tpu.serve import InferenceEngine, RequestManager, ServingConfig
from flexflow_tpu.serve.engine import pack_widths, program_name
from flexflow_tpu.serve.request_manager import trim_to_rung

R, C, PS = 6, 8, 8           # ladder (12, 24, 48): two packed rungs
TOL = dict(rtol=2e-5, atol=2e-5)  # a few float32 ulp of logits of order 1


def _family(name):
    if name == "mistral":
        return mistral, mistral.tiny(dtype=jnp.float32, sliding_window=12)
    return mixtral, mixtral.tiny(dtype=jnp.float32)


@pytest.fixture(scope="module", params=["mistral", "mixtral"])
def model(request):
    mod, cfg = _family(request.param)
    return mod, cfg, mod.init_params(jax.random.PRNGKey(0), cfg)


def _engine(model, kernels="xla", pack=True, slots=R, chunk=C, **kw):
    mod, cfg, params = model
    sc = ServingConfig(
        max_requests_per_batch=slots, max_sequence_length=56,
        prefill_chunk=chunk, max_spec_tree_tokens=8,
        cache_dtype=jnp.float32, kv_layout="paged",
        page_size=PS, kernels=kernels, **kw,
    )
    eng = InferenceEngine(mod, cfg, params, sc)
    if not pack:
        eng.pack_ladder = lambda chunk: ()  # the padded program at every fill
    # the width of each mixed step's program, from the step key the
    # dispatch hands the donation hook (R x C: the padded program)
    eng.ran = []
    hook = eng._poison_donated

    def spy(donated, key):
        if isinstance(key, tuple) and key[0].startswith("mixed"):
            eng.ran.append(key[2] if key[0] == "mixed_packed"
                           else eng.num_slots * key[1])
        hook(donated, key)

    eng._poison_donated = spy
    return eng


def _run(eng, feed, done, seqs):
    """One (slots, chunk) mixed step with logits: ``feed`` row -> new
    tokens."""
    R, C = eng.num_slots, eng.serving.mixed_chunk
    toks = np.zeros((R, C), np.int32)
    pos = np.full((R, C), eng.scratch_pos, np.int32)
    idx = np.zeros((R,), np.int32)
    for row, n in feed.items():
        lo = done[row]
        toks[row, :n] = seqs[row][lo:lo + n]
        pos[row, :n] = np.arange(lo, lo + n)
        idx[row] = n - 1
        assert eng.pager.ensure(row, lo + n)
    ones = np.ones(R, np.float32)
    _, logits = eng.run_mixed(
        np.zeros(R, np.int32), toks, np.zeros(R, bool), pos, idx,
        jax.random.PRNGKey(0), np.ones(R, bool), ones, ones,
        np.zeros(R, np.int32), with_logits=True,
    )
    return np.asarray(logits)


def _lines(eng, rows_done):
    """Every cached K/V line (and window position) of the given rows,
    read through the page table."""
    out = []
    for row, n in rows_done.items():
        pages = eng.pager.table[row]
        for name in ("k", "v", "pos"):
            if name not in eng.cache:
                continue
            pool = np.asarray(eng.cache[name])
            for line in range(n):
                at = (pages[line // PS], line % PS)
                out.append(pool[(slice(None),) + at] if name != "pos"
                           else pool[at])
    return out


# the schedule: feeds that land on each rung of (12, 24, 48), one that
# exactly fills a rung, one decoding row alone, and one that fills R x C
FEEDS = [
    ({0: 8, 1: 4}, 12),                       # exactly fills the first rung
    ({0: 1}, 12),                             # a single decoding row
    ({0: 1, 1: 1, 2: 8, 3: 5}, 24),
    ({0: 1, 1: 1, 2: 8, 3: 8, 4: 8, 5: 3}, 48),
    ({r: 8 for r in range(R)}, 48),           # fills R x C
    ({0: 1, 3: 1, 5: 7}, 12),
]

# 12 slots x chunk 16, whose ladder (32, 48, 96, 192) has the ADMISSION
# rung (ISSUE 45: slots + chunk = 28, rounded up, under the quarter's
# 48): feeds that fill it exactly, sit well inside it, go one token
# over it, and the step it is named for (every other slot on one token
# beside one prompt's chunk); then one that fills a rung above
WIDE = (12, 16)
FEEDS_WIDE = [
    ({0: 16, 1: 16}, 32),                     # exactly fills the admission rung
    ({0: 1, 1: 1, 2: 5}, 32),                 # well inside it
    ({0: 1, 1: 1, 2: 1, 3: 16, 4: 14}, 48),   # one token over it
    ({**{r: 1 for r in range(11)}, 11: 16}, 32),
    ({r: 8 for r in range(12)}, 96),
]


@pytest.mark.parametrize("kernels", ["xla", "pallas"])
@pytest.mark.parametrize("geometry, feeds, ladder", [
    ((R, C), FEEDS, (12, 24, 48)), (WIDE, FEEDS_WIDE, (32, 48, 96, 192))],
    ids=["6x8", "12x16-admission"])
def test_every_rung_matches_the_padded_program(model, kernels, geometry,
                                               feeds, ladder):
    R, C = geometry
    assert pack_widths(R, C) == ladder
    packed = _engine(model, kernels, slots=R, chunk=C)
    padded = _engine(model, kernels, False, slots=R, chunk=C)
    assert packed.pack_ladder(C) == ladder[:-1]
    assert packed.pack_ladder(1) == ()
    rng = np.random.default_rng(3)
    seqs = [list(rng.integers(1, 250, 56)) for _ in range(R)]
    done = {r: 0 for r in range(R)}
    for feed, width in feeds:
        a = _run(packed, feed, done, seqs)
        b = _run(padded, feed, done, seqs)
        real = sum(feed.values())
        assert packed.pack_width(real, C) == packed.ran[-1] == width
        assert padded.pack_width(real, C) == padded.ran[-1] == R * C
        for row, n in feed.items():
            done[row] += n
        rows = sorted(feed)
        np.testing.assert_allclose(a[rows], b[rows], **TOL)
        for x, y in zip(_lines(packed, done), _lines(padded, done)):
            np.testing.assert_allclose(x, y, **TOL)
    names = {program_name(k) for k in packed._steps}
    # a rung is ONE program for the probe and the server; the padded
    # step keeps its sibling with the logits returned
    ran = {w for _, w in feeds}
    padded_ran = {f"ff_step_c{C}_logits"} if R * C in ran else set()
    assert names == {f"ff_step_c{C}_t{w}" for w in ran - {R * C}} | padded_ran
    assert all(n.startswith(f"ff_step_c{C}") for n in names)


def test_quantized_pool_rungs_match(model):
    """The quantizing line write takes the packed lines too."""
    packed = _engine(model, kv_quant="int8")
    padded = _engine(model, pack=False, kv_quant="int8")
    rng = np.random.default_rng(5)
    seqs = [list(rng.integers(1, 250, 56)) for _ in range(R)]
    done = {r: 0 for r in range(R)}
    for feed, _ in FEEDS[:4]:
        a = _run(packed, feed, done, seqs)
        b = _run(padded, feed, done, seqs)
        for row, n in feed.items():
            done[row] += n
        rows = sorted(feed)
        np.testing.assert_allclose(a[rows], b[rows], rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# the scheduler: same generations, every rung compiled once, no retrace


def _prompts(n, vocab=250):
    out = []
    for i in range(n):
        shared = [(i % 3 * 17 + j * 5 + 1) % vocab for j in range(10)]
        out.append(shared + [(i * 37 + j * 11 + 3) % vocab
                             for j in range(2 + (i * 5) % 17)])
    return out


def _serve(model, pack, kernels="xla", n=20, new=lambda i: 5 + i % 4, **kw):
    eng = _engine(model, kernels, pack, sanitizers=("retrace",), **kw)
    rm = RequestManager(eng)
    rm.mixed = []  # each mixed step's (prompt tokens, decoding rows)
    record = rm.stats.record_step

    def record_step(kind, **kw):
        if kind == "mixed":
            rm.mixed.append((kw["prefill_tokens"], kw["decode_tokens"]))
        record(kind, **kw)

    rm.stats.record_step = record_step
    rids = [rm.submit(p, max_new_tokens=new(i))
            for i, p in enumerate(_prompts(n))]
    while rm.step():
        pass
    rm.drain()
    return rm, [list(rm.requests[r].output_tokens) for r in rids]


def _assert_real_tokens_are_the_mixed_steps_own(rm):
    """``step_tokens_real`` is what the mixed steps were handed: their
    prompt tokens and their decoding rows' one token each, as
    ``record_step`` was told them. (Equal to the padded engine's it is
    no longer, ISSUE 61: a trimmed step's tokens go out a step later,
    beside that step's decoding rows.)"""
    s = rm.stats
    assert len(rm.mixed) == s.mixed_steps and s.sync_steps == 0
    assert sum(p for p, _ in rm.mixed) == s.prefill_tokens
    assert s.step_tokens_real == s.prefill_tokens + sum(d for _, d in rm.mixed)


@pytest.mark.parametrize("kernels", ["xla", "pallas"])
def test_scheduler_generations_equal_the_padded_engine(model, kernels):
    """Greedy generations over admissions in waves, preemption (a pool
    too small for every slot's worst case) and prefix hits."""
    kw = dict(max_cached_tokens=R * 24, prefix_caching=True)
    rm, outs = _serve(model, True, kernels, **kw)
    rm0, outs0 = _serve(model, False, kernels, **kw)
    assert outs == outs0
    s = rm.stats
    assert s.preemptions > 0 and s.prefix_hits > 0 and s.admitted >= 20
    # the packed engine ran narrower steps; the padded one never, and
    # has no rung to keep a step under
    assert s.rung_trims > 0 and rm0.stats.rung_trims == 0
    _assert_real_tokens_are_the_mixed_steps_own(rm)
    _assert_real_tokens_are_the_mixed_steps_own(rm0)
    assert set(rm0.stats.steps_by_width) == {R * C}
    assert set(s.steps_by_width) <= {12, 24, 48} and min(s.steps_by_width) < 48
    assert s.step_tokens_width < rm0.stats.step_tokens_width
    assert sum(s.steps_by_width.values()) == s.mixed_steps
    # and counts each step at the width of the program that ran it
    assert s.steps_by_width == collections.Counter(
        w for w in rm.engine.ran if w != R)           # R: a decode step

    guard = rm.engine.retrace_guard
    guard.assert_one_compile_per_key()
    assert guard.retraces == 0
    counts = guard.compile_counts()
    # every rung of the serving ladder, asked for or not, compiled once
    # (of the argmax head's ladder: every request is greedy)
    g = ("greedy", 0)
    assert counts == {("mixed_packed", C, 12, *g): 1,
                      ("mixed_packed", C, 24, *g): 1,
                      ("mixed_fused", C, False, *g): 1,
                      ("mixed_fused", 1, False, *g): 1,
                      "copy_page": 1}, counts


def test_a_house_of_decoding_rows_admits_on_the_admission_rung(model):
    """A closed loop of decoding rows (ISSUE 45): once the first wave's
    prompts are in, a mixed step holds one admitted prompt's chunk
    beside the other slots' single tokens, slots + chunk places at
    most, and runs at the admission rung; the generations are the
    padded engine's, every rung compiled once and none after the first
    mixed dispatch."""
    R, C = WIDE
    new = lambda i: 9 + (i * 7) % 11    # answers end one at a time
    rm, outs = _serve(model, True, n=30, new=new, slots=R, chunk=C)
    rm0, outs0 = _serve(model, False, n=30, new=new, slots=R, chunk=C)
    assert outs == outs0
    s = rm.stats
    assert set(rm0.stats.steps_by_width) == {R * C}
    assert s.prefill_tokens == rm0.stats.prefill_tokens  # no preemption
    _assert_real_tokens_are_the_mixed_steps_own(rm)
    _assert_real_tokens_are_the_mixed_steps_own(rm0)
    assert set(s.steps_by_width) <= set(pack_widths(R, C))
    assert s.steps_by_width[32] > s.mixed_steps / 2
    assert s.steps_by_width == collections.Counter(
        w for w in rm.engine.ran if w != R)           # R: a decode step
    guard = rm.engine.retrace_guard
    guard.assert_one_compile_per_key()
    assert guard.retraces == 0
    g = ("greedy", 0)
    assert guard.compile_counts() == {
        **{("mixed_packed", C, w, *g): 1 for w in (32, 48, 96)},
        ("mixed_fused", C, False, *g): 1, ("mixed_fused", 1, False, *g): 1}


@pytest.mark.parametrize("kw", [
    dict(prefill_chunk=1), dict(max_tokens_per_step=1)])
def test_a_chunk_of_one_is_served_and_counted(model, kw):
    """``mixed_chunk == 1``: the mixed step IS the (R, 1) decode
    program, with no ladder; its steps count R places."""
    mod, cfg, params = model
    eng = InferenceEngine(mod, cfg, params, ServingConfig(**{**dict(
        max_requests_per_batch=R, max_sequence_length=56, prefill_chunk=C,
        max_spec_tree_tokens=8, cache_dtype=jnp.float32, kv_layout="paged",
        page_size=PS, kernels="xla"), **kw}))
    assert eng.serving.mixed_chunk == 1 and eng.pack_width(3, 1) == R
    rm = RequestManager(eng)
    prompts = _prompts(8)
    rids = [rm.submit(p, max_new_tokens=4) for p in prompts]
    while rm.step():
        pass
    rm.drain()
    assert all(len(rm.requests[r].output_tokens) == 4 for r in rids)
    s = rm.stats
    assert s.steps_by_width == {R: s.mixed_steps} and s.mixed_steps > 0
    assert s.prefill_tokens < s.step_tokens_real <= (
        s.prefill_tokens + s.decode_tokens)
    _, outs = _serve(model, True, n=8, new=lambda i: 4)
    assert [list(rm.requests[r].output_tokens) for r in rids] == outs


def test_no_rung_is_lowered_after_the_first_mixed_step(model):
    """What the benchmark's window demands: the first mixed dispatch
    lowers and compiles the whole ladder; later steps on other rungs
    lower nothing."""
    import jax.monitoring

    lowered, listening = [], [True]

    def on(name, _secs, **kw):
        if listening and name == (
                "/jax/core/compile/jaxpr_to_mlir_module_duration"):
            lowered.append(kw.get("fun_name"))

    jax.monitoring.register_event_duration_secs_listener(on)
    eng = _engine(model)
    rm = RequestManager(eng)
    rm.submit(_prompts(1)[0], max_new_tokens=3)   # one mixed, then decode
    while rm.step():
        pass
    rm.drain()
    assert set(rm.stats.steps_by_width) == {12}
    before = [n for n in lowered if n and n.startswith("jit(ff_step_")]
    assert {f"jit(ff_step_c{C}_t12)", f"jit(ff_step_c{C}_t24)",
            f"jit(ff_step_c{C})"} <= set(before)
    for i, p in enumerate(_prompts(12)):          # every rung now runs
        rm.submit(p, max_new_tokens=4)
    while rm.step():
        pass
    rm.drain()
    assert {12, 24, 48} == set(rm.stats.steps_by_width)
    after = [n for n in lowered if n and n.startswith("jit(ff_step_")]
    assert after == before
    listening.clear()  # a listener cannot be taken off again


# ---------------------------------------------------------------------------
# a caller that hands no packing traces the padded step


def _jaxpr_of_step(eng, chunk, with_pack_kw):
    """The jaxpr of the engine's serving step function at ``chunk``."""
    Rr = eng.num_slots
    fn = eng._serve_step_fn(all_logits=False)
    if with_pack_kw:
        import functools

        fn = functools.partial(fn, pack=None)
    args = (eng.params, eng.cache, jnp.zeros((Rr, chunk), jnp.int32),
            jnp.zeros((Rr, chunk), jnp.int32), jnp.zeros((Rr,), jnp.int32),
            None, None, eng.page_table_device())
    return str(jax.make_jaxpr(fn)(*args))


@pytest.mark.parametrize("kernels", ["xla", "pallas"])
def test_unpacked_callers_trace_the_padded_step(model, kernels, monkeypatch):
    """``pack=None`` adds no operation: the step's jaxpr equals the one
    traced with the two packing hooks of the block replaced by what the
    parent's block did in their place (the query as it is, the
    attention result reshaped to (R, C, -1))."""
    eng = _engine(model, kernels)
    for chunk in (1, C):
        ours = _jaxpr_of_step(eng, chunk, True)
        with monkeypatch.context() as m:
            m.setattr(transformer, "_spread_queries", lambda q, pack: q)
            m.setattr(transformer, "_gather_attended",
                      lambda a, pack: a.reshape(*a.shape[:2], -1))
            m.setattr(transformer, "_pack_tokens", None)
            parents = _jaxpr_of_step(eng, chunk, False)
        assert ours == parents
        # (a routed expert layer's grouping has a cumsum of its own, and
        # so has the Pallas attention call's work list, kernels.ragged_work)
        assert ("cumsum" not in ours or model[1].num_local_experts
                or kernels == "pallas")
    # and the decode step, the dense layout and the fused prologue have no ladder
    assert eng.pack_ladder(1) == ()
    mod, cfg, params = model
    dense = InferenceEngine(mod, cfg, params, ServingConfig(
        max_requests_per_batch=R, max_sequence_length=56, prefill_chunk=C,
        max_spec_tree_tokens=8, cache_dtype=jnp.float32))
    assert dense.pack_ladder(C) == ()
    fused = _engine(model, "pallas", fused_decode=("rope_kv_write",))
    assert fused.pack_ladder(C) == ()


def test_packed_step_refuses_what_it_cannot_serve(model):
    mod, cfg, params = model
    eng = _engine(model)
    fn = eng._serve_step_fn(all_logits=False, pack=12)
    z = jnp.zeros((R, C), jnp.int32)
    with pytest.raises(ValueError, match="packed token axis"):
        fn(params, eng.cache, z, z, jnp.zeros((R,), jnp.int32),
           jnp.ones((R, C, eng.serving.cache_len + 1), bool), None,
           eng.page_table_device())


# ---------------------------------------------------------------------------
# the ladder and the counters


@pytest.mark.parametrize("slots, chunk, want", [
    (16, 128, (256, 512, 1024, 2048)),       # the admission rung (ISSUE 45)
    (4, 128, (128, 256, 512)),               # 256 is not under its quarter
    (64, 128, (256, 2048, 4096, 8192)),
    (12, 16, (32, 48, 96, 192)),
    (6, 8, (12, 24, 48)),
    (3, 8, (12, 24)),
    (2, 8, (8, 16)),
    (1, 8, (8,)),
    (16, 1, (16,)),
])
def test_ladder_follows_from_slots_and_chunk(slots, chunk, want):
    got = pack_widths(slots, chunk)
    assert got == want
    assert got[-1] == slots * chunk and list(got) == sorted(set(got))
    assert all(w >= chunk for w in got)
    # by halves from the top, and under the quarter rung at most ONE
    # rung, which holds every slot decoding beside one prompt's chunk
    quarter = -(-slots * chunk // 4)
    below = [w for w in got if w < quarter]
    assert set(got) - set(below) <= {quarter, -(-slots * chunk // 2),
                                     slots * chunk}
    assert len(below) <= 1 and all(
        slots + chunk <= w < 2 * (slots + chunk) for w in below)


def _whole_chunks(k, chunk=128):
    return [chunk] * k, [False] * k


# the cells' geometries and their just-over steps (ISSUE 61): slots,
# chunk, decoding rows, prefilling rows' chunks oldest first, the width
# the step runs at (None: the rule leaves it alone)
JUST_OVER = [
    (4, 128, 3, 1, 128),        # DeepSeek's cell: 131 -> 128
    (4, 128, 2, 2, 256),        # 258 -> 256
    (4, 128, 1, 3, None),       # 385: 129 over 256
    (16, 128, 12, 4, 512),      # the long-prompt cells: 524 -> 512
    (16, 128, 8, 8, 1024),      # 1032 -> 1024 (had run the padded 2048)
    (16, 128, 11, 5, None),     # 651
    (16, 128, 14, 2, 256),      # 270 -> 256, the admission rung
    (16, 128, 10, 6, None),     # 778
    (8, 128, 4, 4, 512),        # SmallThinker's cell: 516 -> 512
    (8, 128, 6, 2, 256),        # 262 -> 256
    (8, 128, 2, 6, None),       # 770
    (64, 128, 62, 2, 256),      # the 64-slot cells: 318 -> 256
    (64, 128, 48, 16, 2048),    # 2096 -> 2048
    (64, 128, 63, 1, None),     # 191: under the narrowest rung
    (6, 8, 5, 1, 12),           # this file's geometry: 13 -> 12
    (12, 16, 11, 1, None),      # 27 sits in the admission rung's 32
]


@pytest.mark.parametrize("slots, chunk, d, k, width", JUST_OVER, ids=[
    f"{s}x{c}-{d + k * c}" for s, c, d, k, _ in JUST_OVER])
def test_a_step_just_over_a_rung_gives_the_tokens_up(slots, chunk, d, k, width):
    ladder = pack_widths(slots, chunk)[:-1]
    chunks, final = _whole_chunks(k, chunk)
    got = trim_to_rung(ladder, slots, d, chunks, final)
    if width is None:
        assert got == chunks
        return
    assert width in ladder and d + sum(got) == width
    # the newest prompt alone pays (chunk > slots: one row has enough)
    assert got[:-1] == chunks[:-1] and got[-1] == chunk - (d + k * chunk - width)
    # and the step now runs at the rung it was over, not the next
    nxt = next((w for w in pack_widths(slots, chunk) if w >= d + k * chunk))
    assert nxt > width


def test_a_step_further_over_than_the_slots_is_left_alone():
    # 64 x 128: 2113 is 65 over 2048 (a partial chunk beside whole ones)
    ladder = pack_widths(64, 128)[:-1]
    chunks = [128] * 16 + [17]
    assert trim_to_rung(ladder, 64, 48, chunks, [False] * 16 + [True]) == chunks
    chunks[-1] = 16                                  # 2112: 64 over
    got = trim_to_rung(ladder, 64, 48, chunks, [False] * 16 + [True])
    assert 48 + sum(got) == 2048 and got[-1] == 16   # the final chunk kept
    assert got[-2] == 64 and got[:-2] == [128] * 15


@pytest.mark.parametrize("ladder", [(), (512,)], ids=["no-ladder", "under-every-rung"])
def test_no_rung_under_the_step_nothing_given_up(ladder):
    chunks = [128, 128, 7]
    assert trim_to_rung(ladder, 16, 13, chunks, [False, False, True]) == chunks
    assert trim_to_rung(ladder, 16, 0, [], []) == []


def test_the_order_of_giving_up():
    ladder = (6, 12)                     # 6 slots x chunk 4
    # newest first: the row admitted last gives before the one before it
    assert trim_to_rung(ladder, 6, 5, [4, 4], [False, False]) == [4, 3]
    # a row emptied leaves the step, and the next newest gives the rest
    assert trim_to_rung(ladder, 6, 2, [4, 4, 4, 4], [False] * 4) == [4, 4, 2, 0]
    # a final chunk gives last, however new its row
    assert trim_to_rung(ladder, 6, 3, [4, 4, 3], [False, False, True]) == [4, 2, 3]
    assert trim_to_rung(ladder, 6, 3, [4, 3, 4], [False, True, False]) == [4, 3, 2]
    # ... but gives where the others have nothing left
    assert trim_to_rung(ladder, 6, 5, [3, 4], [True, False]) == [1, 0]
    assert trim_to_rung(ladder, 6, 4, [2, 2], [True, True]) == [2, 0]
    # the input is not written to
    chunks = [4, 4]
    trim_to_rung(ladder, 6, 5, chunks, [False, False])
    assert chunks == [4, 4]


def test_a_rung_the_decoding_rows_fill_is_no_rung_to_stop_at():
    # 16 slots x chunk 2, ladder (8, 16): nine decoding rows beside one
    # chunk are 11, 3 over 8, but 8 places would hold no prompt token
    assert pack_widths(16, 2)[:-1] == (8, 16)
    assert trim_to_rung((8, 16), 16, 9, [2], [False]) == [2]
    assert trim_to_rung((8, 16), 16, 8, [2], [False]) == [2]
    assert trim_to_rung((8, 16), 16, 7, [2], [False]) == [1]   # one stays


@pytest.mark.parametrize("slots, chunk", [(4, 128), (16, 128), (8, 128),
                                          (64, 128), (6, 8), (12, 16), (4, 16)])
def test_every_step_of_a_geometry_keeps_the_invariant(slots, chunk):
    """Every (decoding rows, prefilling rows, last chunk's length) of a
    geometry: where the rule fires the step sits ON a rung, no row
    gains a token, and some prompt keeps one; where it does not the
    chunks are untouched; after it no step is over a rung by the slots
    or less unless that rung held no prompt token."""
    ladder = pack_widths(slots, chunk)[:-1]
    fired = 0
    for d in range(slots):
        for k in range(1, slots - d + 1):
            for last in sorted({1, 2, chunk // 2, chunk - 1, chunk}):
                chunks = [chunk] * (k - 1) + [last]
                final = [False] * (k - 1) + [last < chunk]
                got = trim_to_rung(ladder, slots, d, chunks, final)
                real, now = d + sum(chunks), d + sum(got)
                assert all(0 <= g <= n for g, n in zip(got, chunks))
                if got != chunks:
                    fired += 1
                    assert now in ladder and 0 < real - now <= slots
                    assert sum(got) > 0
                else:
                    assert not any(0 < real - w <= slots and w > d
                                   for w in ladder)
    assert fired


def test_step_token_counters_against_a_hand_counted_schedule():
    s = SchedulerStats()
    at_open = dataclasses.replace(s)
    for real, width in [(143, 512), (460, 512), (513, 1024), (300, 512),
                        (2048, 2048)]:
        s.note_step_tokens(real, width)
    assert s.step_tokens_real == 143 + 460 + 513 + 300 + 2048
    assert s.step_tokens_width == 512 + 512 + 1024 + 512 + 2048
    assert s.steps_by_width == {512: 3, 1024: 1, 2048: 1}
    assert at_open.steps_by_width == {}      # a copy keeps its own moment
    snap = s.snapshot()
    assert snap["step_tokens_real"] == 3464 and snap["step_tokens_width"] == 4608
    assert snap["pack_fill"] == round(3464 / 4608, 4)
    assert snap["steps_by_width"] == {512: 3, 1024: 1, 2048: 1}
    assert "pack=3464/4608 by width 512:3,1024:1,2048:1 trims=0/0tok" in s.report()
    assert "by width - trims=0/0tok" in SchedulerStats().report()
    # the steps that gave tokens up to stay on a rung (ISSUE 61)
    s.note_step_tokens(512, 512, 12)
    s.note_step_tokens(1024, 1024, 8)
    s.note_step_tokens(300, 512, 0)
    assert (s.rung_trims, s.rung_trim_tokens) == (2, 20)
    assert (at_open.rung_trims, at_open.rung_trim_tokens) == (0, 0)
    snap = s.snapshot()
    assert (snap["rung_trims"], snap["rung_trim_tokens"]) == (2, 20)
    assert "512:5,1024:2,2048:1 trims=2/20tok" in s.report()


@pytest.mark.parametrize("key, name", [
    (("mixed_packed", 128, 512, "greedy", 0), "ff_step_c128_t512"),
    (("mixed_packed", 128, 1024, "greedy", 0), "ff_step_c128_t1024"),
    (("mixed_fused", 128, False, "greedy", 0), "ff_step_c128"),
    (("mixed_fused", 1, False, "greedy", 0), "ff_step_c1"),
    (("mixed_fused", 128, True, "greedy", 0), "ff_step_c128_logits"),
    (("mixed_packed", 128, 512, "topk", 8), "ff_step_c128_t512_topk8"),
    (("mixed_packed", 128, 512, "sample", 0), "ff_step_c128_t512_sample"),
    (("mixed_fused", 128, False, "full", 0), "ff_step_c128_full"),
    (("mixed_fused", 1, False, "topk", 64), "ff_step_c1_topk64"),
])
def test_program_names_of_the_rungs(key, name):
    """The greedy head is the unmarked one (the names the benchmark's
    readers match are the argmax programs'); a head that samples names
    its extra work."""
    assert program_name(key) == name


@pytest.mark.parametrize("family", [
    "falcon", "gemma", "gpt2", "llama", "mistral", "mixtral", "mpt", "opt",
    "phi", "qwen2", "qwen2_moe", "starcoder"])
def test_generic_decoder_families_declare_the_packed_step(family):
    """A family that re-exports the generic decoder's step re-exports
    its declaration too, or it would be served the padded program in
    silence; MiniCPM-SALA has a step of its own that takes no packed
    axis and declares nothing."""
    import importlib

    from flexflow_tpu.models import minicpm_sala

    mod = importlib.import_module(f"flexflow_tpu.models.{family}")
    assert mod.serve_step_paged is transformer.serve_step_paged
    assert mod.PACKED_STEP is True
    assert not hasattr(minicpm_sala, "PACKED_STEP")
