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
    rids = [rm.submit(p, max_new_tokens=new(i))
            for i, p in enumerate(_prompts(n))]
    while rm.step():
        pass
    rm.drain()
    return rm, [list(rm.requests[r].output_tokens) for r in rids]


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
    # the packed engine ran narrower steps; the padded one never
    assert s.step_tokens_real == rm0.stats.step_tokens_real
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
    assert s.step_tokens_real == rm0.stats.step_tokens_real
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
        # (a routed expert layer's grouping has a cumsum of its own)
        assert "cumsum" not in ours or model[1].num_local_experts
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
    assert "pack=3464/4608 by width 512:3,1024:1,2048:1" in s.report()
    assert "by width -" in SchedulerStats().report()


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
