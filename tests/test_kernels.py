"""Pallas serving-kernel tests (interpret mode on the CPU backend): the
ragged paged kernel must match its XLA reference — the TPU analog of
the reference's op kernel tests (tests/ops/, SURVEY.md §4)."""
import functools

import numpy as np
import jax
import jax.numpy as jnp
import pytest

H, KV, dk = 8, 4, 16


def test_llama_generation_pallas_equals_xla():
    """End-to-end: the pallas-kernel serving path must produce the same
    greedy tokens as the XLA path (reference kernel-vs-reference parity,
    tests/ops + inference equivalence suites)."""
    from flexflow_tpu.models import llama
    from flexflow_tpu.serve import LLM, ServingConfig

    cfg = llama.LLaMAConfig.tiny(dtype=jnp.float32)
    params = llama.init_params(jax.random.PRNGKey(3), cfg)
    prompts = [[7, 8, 9], [20, 21, 22, 23]]

    outs = {}
    for kern in ("xla", "pallas"):
        m = LLM(llama, cfg, params, tokenizer=None)
        m.compile(ServingConfig(max_requests_per_batch=2,
                                max_sequence_length=64, prefill_chunk=4,
                                cache_dtype=jnp.float32, kv_layout="paged",
                                page_size=8, kernels=kern))
        outs[kern] = [r.output_tokens for r in m.generate(prompts, max_new_tokens=6)]
    assert outs["xla"] == outs["pallas"], outs


# ---------------------------------------------------------------------------
# The ragged paged kernel told how many leading columns of a row are
# real (``q_len``): padding columns keep no page alive, a row of a few
# real queries takes the narrow body, real queries come out the same.

#: variant -> what differs from the plain case (float32 pools of KV=4
#: heads of 16, groups of 2, one mask a row). From "int4" on: the body
#: in the layout its matmuls give (PR 55) at each family's group, head
#: size and pool, the cases of ISSUE 55
_VARIANTS = {
    "plain": {}, "group_mask": dict(group_mask=True), "int8": dict(quant=1),
    "int4": dict(quant=2),
    "g1": dict(H=4),                                    # Olmo's: a head a group
    "g4-dk128": dict(H=8, KV=2, dk=128),                # Mistral's
    "g8-dk256": dict(H=8, KV=1, dk=256),                # Qwen3-Next's
    "g8-group_mask": dict(H=16, KV=2, group_mask=True),  # the sparse call
    "merged-dk64": dict(dk=64, merged=True),            # LFM2's rank-3 pool
    "window": dict(window=12, tag="_win"),              # a window layer's call
    "bf16": dict(dtype=jnp.bfloat16),                   # the cells' pools
    "bf16-merged-g1": dict(H=4, dk=64, merged=True, dtype=jnp.bfloat16),
    # Mistral's group and head size in the cells' dtype
    "bf16-g4-dk128": dict(H=8, KV=2, dk=128, dtype=jnp.bfloat16),
}
_PS, _NP = 8, 6                       # page size, logical pages a row
_CACHE_LEN = _PS * _NP - 1            # the scratch position
#: |kernel - reference| allowed, the reference in float32 on the same
#: values: float32 pools to the file's 2e-5 (they read 1.7e-6 at most,
#: to the digit what the float32 dots of the body before PR 55 read);
#: bf16 pools to 2e-2 (the result's own rounding to bf16 is 2**-9 of
#: values up to 2.9: bf16 C=1 reads 3.9e-3, C=16 7.5e-3,
#: bf16-merged-g1 7.3e-3, and so did the body before PR 55; CHANGES.md)
_ATOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


def _atol(variant):
    return _ATOL[_VARIANTS[variant].get("dtype", jnp.float32)]


def _mixed_rows(C):
    """(first position, real queries) a row, shaped like a mixed step:
    whole chunks, a prompt's tail above and under the narrow extent, one
    query rows (one of them on a page boundary), idle rows."""
    if C == 1:
        return [(20, 1), (0, 0), (16, 1), (7, 1), (0, 0), (46, 1)]
    return [(16, C), (8, 11), (24, 5), (20, 1), (0, 0), (16, 1), (0, C),
            (30, 8), (0, 0), (45, 1)]


@functools.lru_cache(maxsize=None)
def _q_len_case(variant, C):
    """One batch through the kernel with and without ``q_len``, with
    the step's work list (``work``: every other call runs every entry
    of the table, the grid before PR 63) and through the XLA reference
    (in float32, on the values the pools hold): (positions, q_len,
    mask, outputs)."""
    from flexflow_tpu.serve import kernels as K

    v = dict(dict(H=H, KV=KV, dk=dk, dtype=jnp.float32, quant=0, merged=False,
                  group_mask=False, window=0, tag=""), **_VARIANTS[variant])
    heads, kv, d, dtype = v["H"], v["KV"], v["dk"], v["dtype"]
    rng = np.random.default_rng(C + len(variant))
    rows = _mixed_rows(C)
    R, P = len(rows), len(rows) * _NP
    pos = np.full((R, C), _CACHE_LEN, np.int32)
    for r, (first, n) in enumerate(rows):
        pos[r, :n] = np.arange(first, first + n)
    pos = jnp.asarray(pos)
    q = jnp.asarray(rng.normal(size=(R, C, heads, d)), dtype)
    pt = jnp.asarray(rng.permutation(P).reshape(R, _NP), jnp.int32)
    mask = K.paged_serve_mask(None, pos, _NP, _PS, _CACHE_LEN)   # (R, C, S)
    if v["window"]:  # a window layer: no key further back than the window
        keys = jnp.arange(_NP * _PS)
        mask = mask & (keys[None, None, :] > pos[:, :, None] - v["window"])
    kw = {}
    if v["quant"]:  # int8 codes, or int4's two to a byte
        pack = v["quant"]
        codes = (dict(low=-127, high=128, dtype=np.int8) if pack == 1
                 else dict(low=0, high=256, dtype=np.uint8))
        kp, vp = (jnp.asarray(rng.integers(size=(P + 1, _PS, kv, d // pack), **codes))
                  for _ in range(2))
        kw = dict(k_scale=jnp.asarray(rng.random((P + 1, kv)) * 0.02, jnp.float32),
                  v_scale=jnp.asarray(rng.random((P + 1, kv)) * 0.02, jnp.float32))
    else:
        kp, vp = (jnp.asarray(rng.normal(size=(P + 1, _PS, kv, d)), dtype)
                  for _ in range(2))
    f32 = lambda x: x.astype(jnp.float32) if x.dtype == jnp.bfloat16 else x
    reference = functools.partial(K.ragged_paged_attention_xla, f32(q), f32(kp),
                                  f32(vp), pt, **kw)
    if v["merged"]:  # a line's heads side by side on the minor axis
        kp, vp = (x.reshape(P + 1, _PS, kv * d) for x in (kp, vp))
    if v["group_mask"]:
        # a mask a KV group: each real query keeps its own page and a
        # random half of the others; a padding query keeps every key,
        # as models/minicpm_sala.choose_blocks leaves it
        keep = rng.random((R, kv, C, _NP)) < 0.5
        own = (np.asarray(pos) // _PS)[:, None, :, None] == np.arange(_NP)
        keep = keep | own | (np.asarray(pos) >= _CACHE_LEN)[:, None, :, None]
        mask = mask[:, None] & jnp.asarray(np.repeat(keep, _PS, axis=-1))
        call = functools.partial(K.sparse_paged_attention, q, kp, vp, pt, mask)
        ref = jnp.stack([
            reference(mask[:, g]).reshape(R, C, kv, heads // kv, d)[:, :, g]
            for g in range(kv)], axis=2).reshape(R, C, heads, d)
    else:
        call = functools.partial(K.ragged_paged_attention, q, kp, vp, pt, mask,
                                 tag=v["tag"], **kw)
        ref = reference(mask)
    q_len = K.real_query_lengths(pos, _CACHE_LEN)
    outs = dict(
        none=call(), q_len=call(q_len=q_len),
        full=call(q_len=jnp.full((R,), C, jnp.int32)), ref=ref,
        work=call(q_len=q_len, work=K.step_work(pos, q_len, _PS, _NP,
                                                v["window"])),
    )
    return (np.asarray(pos), np.asarray(q_len), np.asarray(mask),
            {k: np.asarray(f32(x)) for k, x in outs.items()})


# the cases before PR 55 at the decode step's chunk and a mixed step's,
# PR 55's at the mixed step's (the narrow body beside the chunk-wide
# one) and the bf16 pool at the decode step's too
_Q_LEN_CASES = [(v, C) for v in _VARIANTS for C in (1, 16)
                if C == 16 or v in ("plain", "group_mask", "int8", "bf16")]
q_len_cases = pytest.mark.parametrize("variant, C", _Q_LEN_CASES)


@q_len_cases
def test_q_len_is_the_rows_real_queries(variant, C):
    pos, q_len, _, _ = _q_len_case(variant, C)
    assert q_len.tolist() == [n for _, n in _mixed_rows(C)]
    assert q_len.dtype == np.int32


@q_len_cases
def test_q_len_real_queries_unchanged(variant, C):
    """Every real query's row is bitwise the ``q_len=None`` kernel's
    (interpret mode), at the chunk's extent and at the narrow one, and
    the XLA reference's within this file's tolerance."""
    _, q_len, _, outs = _q_len_case(variant, C)
    for r, n in enumerate(q_len):
        np.testing.assert_array_equal(outs["q_len"][r, :n], outs["none"][r, :n])
        np.testing.assert_allclose(outs["q_len"][r, :n], outs["ref"][r, :n],
                                   atol=_atol(variant))


@q_len_cases
def test_q_len_padding_queries_are_zero(variant, C):
    _, q_len, _, outs = _q_len_case(variant, C)
    assert np.isfinite(outs["q_len"]).all()
    for r, n in enumerate(q_len):
        assert not outs["q_len"][r, n:].any()


@q_len_cases
def test_q_len_none_is_every_column(variant, C):
    """``q_len=None`` is the kernel as it was: no third prefetched
    scalar, every column attends, and ``q_len = C`` (every column real)
    gives its result to the bit, padding columns' too."""
    _, _, _, outs = _q_len_case(variant, C)
    np.testing.assert_array_equal(outs["full"], outs["none"])
    np.testing.assert_allclose(outs["none"], outs["ref"], atol=_atol(variant))


def _jitted_kernel_calls(jaxpr):
    """The equations of ``jaxpr`` that call a jitted function holding a
    ``pallas_call`` (``kernels._ragged_call``'s)."""
    return [e for e in jaxpr.eqns if e.primitive.name in ("pjit", "jit")
            and "pallas_call" in str(e.params["jaxpr"])]


def test_q_len_none_traces_the_old_operands():
    """A caller that passes no ``q_len`` gets the ``pallas_call``
    without it: the work list's two prefetched scalars and the table
    (one more with a row offset), no per-row branch; and ONE grid axis
    whose length is a value, whatever is passed."""
    from flexflow_tpu.serve import kernels as K

    def prefetched(**kw):
        jaxpr = jax.make_jaxpr(functools.partial(K.ragged_paged_attention, **kw))(
            jnp.zeros((2, 16, H, dk)), jnp.zeros((5, _PS, KV, dk)),
            jnp.zeros((5, _PS, KV, dk)), jnp.zeros((2, _NP), jnp.int32),
            jnp.zeros((2, 16, _NP * _PS), bool))
        jitted, = _jitted_kernel_calls(jaxpr)  # the call is jitted a shape
        call, = [e for e in jitted.params["jaxpr"].eqns
                 if e.primitive.name == "pallas_call"]
        grid, = call.params["grid_mapping"].grid
        assert not isinstance(grid, int)
        return call.params["grid_mapping"].num_index_operands

    assert prefetched() == 3
    assert prefetched(row_offset=3) == 4
    assert prefetched(row_offset=3, q_len=jnp.zeros((2,), jnp.int32)) == 5
    work = K.ragged_work(jnp.zeros((2,), jnp.int32), jnp.ones((2,), jnp.int32), _NP)
    assert prefetched(row_offset=3, work=work) == 4


def test_ragged_calls_are_traced_once_a_shape(monkeypatch):
    """A step program calls the ragged paged kernel once an attention
    layer, and a process holds a program a rung, head variant and probe
    sibling: every call site of one shape, in one jitted function or in
    the next, shares ONE cached jitted call and ONE traced jaxpr of the
    body (``kernels._ragged_call``; the twin of
    ``test_moe.py::test_grouped_calls_are_traced_once_a_shape``).
    Another name (a tag, a chunk) or ``_interpret()`` is another entry
    of the cache; ``q_len`` given or not is another trace of the same
    entry."""
    from flexflow_tpu.serve import kernels as K

    built, builder = [], K._build_ragged_paged_kernel
    monkeypatch.setattr(K, "_build_ragged_paged_kernel",
                        lambda **kw: built.append(kw) or builder(**kw))
    K._ragged_call.cache_clear()   # and with it every trace kept so far
    args = lambda C: (
        jnp.zeros((2, C, H, dk)), jnp.zeros((5, _PS, KV, dk)),
        jnp.zeros((5, _PS, KV, dk)), jnp.zeros((2, _NP), jnp.int32),
        jnp.zeros((2, C, _NP * _PS), bool), jnp.zeros((2,), jnp.int32))

    def layers(q, kp, vp, pt, mask, q_len, use=True, **kw):
        return sum(K.ragged_paged_attention(
            q + l, kp, vp, pt, mask, q_len=q_len if use else None,
            row_offset=jnp.int32(l), **kw) for l in range(2))

    def calls(fn, C=16, **kw):
        return _jitted_kernel_calls(
            jax.make_jaxpr(functools.partial(fn, **kw))(*args(C)))

    def seen():
        info = K._ragged_call.cache_info()
        return info.misses, info.hits, len(built)

    sites = calls(layers) + calls(lambda *a: 2 * layers(*a))  # two programs
    assert len(sites) == 4
    assert len({id(e.params["jaxpr"]) for e in sites}) == 1
    assert seen() == (1, 3, 1)
    calls(layers, use=False)               # no q_len: the entry's second trace
    assert seen() == (1, 5, 2)
    calls(layers, tag="_win")
    assert seen() == (2, 6, 3)
    calls(layers, C=8)
    assert seen() == (3, 7, 4)
    monkeypatch.setattr(K, "_interpret", lambda: False)   # what `chip` patches
    other = calls(layers)
    assert seen() == (4, 8, 5)
    assert id(other[0].params["jaxpr"]) != id(sites[0].params["jaxpr"])


@q_len_cases
@pytest.mark.parametrize("window", [0, 12])
def test_attn_step_counters_equal_the_masks_count(variant, C, window):
    """``SchedulerStats.note_attn_steps`` counts a step's grid from the
    rows' first positions and query counts; the same count made from the
    causal mask itself: a page is live when some REAL query of the row
    sees a key of it."""
    from flexflow_tpu.metrics import SchedulerStats
    from flexflow_tpu.serve import kernels as K

    pos, q_len, _, _ = _q_len_case(variant, C)
    mask = np.asarray(K.paged_serve_mask(None, jnp.asarray(pos), _NP, _PS, _CACHE_LEN))
    if window:
        keys = np.arange(_NP * _PS)
        mask = mask & (keys[None, None, :] > pos[:, :, None] - window)
    narrow = K.narrow_query_extent(C)
    pages = mask.reshape(mask.shape[0], C, _NP, _PS).any(axis=-1)   # (R, C, NP)
    live = np.array([pages[r, :n].any(axis=0).sum() for r, n in enumerate(q_len)])
    stats = SchedulerStats()
    for _ in range(2):  # counters add up over steps
        stats.note_attn_steps(pos[:, 0], q_len, _PS, _NP, narrow, window)
    # what the call runs: the live entries, one step a row that has none
    assert stats.attn_steps_grid == 2 * np.maximum(live, 1).sum()
    assert stats.attn_steps_live == 2 * live.sum()
    assert stats.attn_steps_narrow == 2 * live[q_len <= narrow].sum()
    assert 0 < stats.attn_steps_live < stats.attn_steps_grid


def test_paged_pallas_step_counts_its_attention_grid():
    """A paged Pallas engine serving requests counts every pipelined
    step's grid, and generates what the XLA path generates."""
    from flexflow_tpu.models import mistral
    from flexflow_tpu.serve import LLM, ServingConfig

    cfg = mistral.tiny(dtype=jnp.float32)  # sliding window 8: one page
    params = mistral.init_params(jax.random.PRNGKey(5), cfg)
    prompts = [[7, 8, 9, 10, 11, 12, 13], [20, 21, 22], list(range(30, 50))]
    outs, stats = {}, {}
    for kern in ("xla", "pallas"):
        m = LLM(mistral, cfg, params, tokenizer=None)
        m.compile(ServingConfig(max_requests_per_batch=4, max_sequence_length=64,
                                prefill_chunk=16, cache_dtype=jnp.float32,
                                kernels=kern, kv_layout="paged", page_size=8))
        outs[kern] = [r.output_tokens for r in m.generate(prompts, max_new_tokens=6)]
        stats[kern] = m.rm.stats
    assert outs["xla"] == outs["pallas"], outs
    s = stats["pallas"]
    # three prompts in four slots: every step runs its live entries and
    # a step for each idle row, far fewer than the tables hold
    assert s.attn_steps_live + s.steps <= s.attn_steps_grid
    assert s.attn_steps_grid < s.steps * 4 * m.engine.serving.pages_per_slot
    assert 0 < s.attn_steps_narrow <= s.attn_steps_live < s.attn_steps_grid


# --- the call's grid as a work list made on the device (PR 63) ---------------


def _work_loop(first, count, num_pages):
    """``kernels.ragged_work`` as a loop: (steps, rows, entries)."""
    rows, entries = [], []
    for r, (f, n) in enumerate(zip(first, count)):
        for j in range(max(int(n), 1)):
            rows.append(r)
            entries.append(int(f) + j)
    tail = len(first) * num_pages + 1 - len(rows)
    return len(rows), rows + [len(first)] * tail, entries + [0] * tail


def _check_work_list(what):
    """``ragged_work`` against the loop; the ranges of ``live_pages``
    against the entries a query of the row sees through the mask."""
    from flexflow_tpu.serve import kernels as K

    if what == "rolling-window":
        # a window class's table: entry 0 is the page of ``start``, not
        # of line 0 (serve/paging.py); ps 8, window 12, steps of 16
        # lines: ``window_table_pages`` gives 5 entries a row
        ps, NP, window = 8, 5, 12
        first = np.array([100, 57, 40, 0, 3], np.int32)
        last = np.array([100, 72, 39, -1, 9], np.int32)     # rows 2, 3: none
        start = np.array([80, 40, 16, 0, 0], np.int32)
        lo, n = K.live_pages(first, last, ps, NP, window, start)
        for r in range(len(first)):
            seen = {(k - start[r]) // ps for q in range(first[r], last[r] + 1)
                    for k in range(max(q - window + 1, 0), q + 1)}
            assert sorted(seen) == list(range(lo[r], lo[r] + n[r])), r
        assert min(lo) >= 0 and max(lo + n) <= NP and lo[0] > 0
    else:
        NP = 4
        lo, n = {"idle-rows": ([0, 0, 2, 3], [3, 0, 1, 0]),
                 "whole-table": ([0, 0, 0], [NP, NP, NP])}[what]
    steps, rows, entries = K.ragged_work(jnp.asarray(lo, jnp.int32),
                                         jnp.asarray(n, jnp.int32), NP)
    want = _work_loop(lo, n, NP)
    assert (int(steps), rows.tolist(), entries.tolist()) == want
    assert rows.dtype == entries.dtype == jnp.int32 and steps.dtype == jnp.int32


def _check_work_steps(what):
    """The device's ``steps`` of a step's list is the host's
    ``attn_steps_grid`` for the same positions and real queries, and
    the list's live places its ``attn_steps_live``."""
    from flexflow_tpu.metrics import SchedulerStats
    from flexflow_tpu.serve import kernels as K

    C, window = what
    start, NP = None, _NP
    rows = _mixed_rows(C)
    if window == "rolling":  # long contexts over a rolling table
        window = 12
        NP = -(-(window + C) // _PS) + 1    # paging.window_table_pages
        rows = [(f + 40 * r, n) for r, (f, n) in enumerate(rows)]
        start = np.array([max(f + n - 1 - window + 1 - C, 0) // _PS * _PS
                          for f, n in rows], np.int32)
    pos = np.full((len(rows), C), 1 << 20, np.int32)
    for r, (first, n) in enumerate(rows):
        pos[r, :n] = np.arange(first, first + n)
    q_len = np.array([n for _, n in rows], np.int32)
    stats = SchedulerStats()
    stats.note_attn_steps(pos[:, 0], q_len, _PS, NP, K.narrow_query_extent(C),
                          window)
    steps, row, _ = K.step_work(
        jnp.asarray(pos), jnp.asarray(q_len), _PS, NP, window,
        None if start is None else jnp.asarray(start))
    run = np.asarray(row)[:int(steps)]
    assert len(run) == stats.attn_steps_grid
    assert len(run) - int((q_len == 0).sum()) == stats.attn_steps_live
    assert (np.diff(run) >= 0).all() and set(run) == set(range(len(rows)))
    assert 0 < stats.attn_steps_live < stats.attn_steps_grid < len(rows) * NP


def _check_work_kernel(what):
    """The kernel over the step's work list (the entries a row's real
    queries may see) is the kernel over every entry of the table, the
    grid before PR 63, TO THE BIT, padding columns and idle rows too,
    and the XLA reference's within this file's tolerance."""
    variant, C = what
    _, q_len, _, outs = _q_len_case(variant, C)
    np.testing.assert_array_equal(outs["work"], outs["q_len"])
    for r, n in enumerate(q_len):
        np.testing.assert_allclose(outs["work"][r, :n], outs["ref"][r, :n],
                                   atol=_atol(variant))


_WORK_CHECKS = {"list": _check_work_list, "steps": _check_work_steps,
                "kernel": _check_work_kernel}
_WORK_CASES = (
    [("list", w) for w in ("idle-rows", "whole-table", "rolling-window")]
    + [("steps", (C, w)) for C in (1, 16) for w in (0, 12, "rolling")]
    + [("kernel", case) for case in _Q_LEN_CASES])


@pytest.mark.parametrize(
    "kind, what", _WORK_CASES,
    ids=[f"{k}-{w if isinstance(w, str) else '-'.join(map(str, w))}"
         for k, w in _WORK_CASES])
def test_the_work_list(kind, what):
    _WORK_CHECKS[kind](what)


# --- the Mamba-2 recurrence of the decode step (ff_ssm_recur_c1) -------------


@pytest.mark.parametrize("layer", [0, 2])
@pytest.mark.parametrize("R, H, P, N", [(4, 3, 8, 16), (4, 64, 64, 128)],
                         ids=["tiny", "published"])
def test_ssm_recur_c1_is_the_scan_at_one_column(R, H, P, N, layer):
    """``granite_hybrid.recurrence_c1`` (the kernel on the whole stack,
    the layer a traced index) against ``selective_scan`` at one column
    on that layer's states, at the tests' tiny shape and at a row block
    of the published one (a row's 64 heads, 2 MiB of state): the
    addressed layer's rows to float32 rounding, the other
    layers bitwise untouched, a row with no real token bitwise
    untouched, a fresh row as from a zero state."""
    from flexflow_tpu.models import granite_hybrid as fam
    from flexflow_tpu.serve import kernels as K

    assert K.ssm_recur_block(H, P, N) == H
    rng = np.random.default_rng(11)
    draw = lambda *shape: jnp.asarray(rng.standard_normal(shape).astype(np.float32))
    states = draw(3, R, H, P, N)
    xs, B, C = draw(R, H, P), draw(R, N), draw(R, N)
    dt = jnp.asarray(rng.uniform(0.05, 0.5, (R, H)).astype(np.float32))
    consts = dict(A=jnp.asarray(rng.uniform(0.5, 2.0, (H,)).astype(np.float32)),
                  D=draw(H))
    count = jnp.asarray([1, 0, 1, 1], jnp.int32)
    fresh = jnp.asarray([False, False, True, False])
    want_y, want_s = fam.selective_scan(
        xs[:, None], B[:, None], C[:, None], dt[:, None], states[layer],
        count, fresh, **consts)
    y, got = jax.jit(functools.partial(fam.recurrence_c1, **consts))(
        xs, B, C, dt, states, jnp.int32(layer), count, fresh)
    assert y.shape == (R, H, P) and y.dtype == got.dtype == jnp.float32
    tol = dict(rtol=0, atol=2e-6 * float(jnp.abs(want_y).max()))
    np.testing.assert_allclose(y, want_y[:, 0], **tol)
    np.testing.assert_allclose(got[layer], want_s, rtol=0,
                               atol=2e-6 * float(jnp.abs(want_s).max()))
    for other in {0, 1, 2} - {layer}:
        np.testing.assert_array_equal(got[other], states[other])
    np.testing.assert_array_equal(got[layer, 1], states[layer, 1])
    # the fresh row: what a zero state gives, whatever the slot held
    _, zero = fam.selective_scan(
        xs[2:3, None], B[2:3, None], C[2:3, None], dt[2:3, None],
        jnp.zeros((1, H, P, N)), count[2:3], fresh[2:3], **consts)
    np.testing.assert_allclose(got[layer, 2], zero[0], **tol)


def test_ssm_recur_c1_result_leads_with_y_and_aliases_the_stack():
    """What the callers and the benchmark's trace reduction lean on: y
    is the call's FIRST result and [slots, 1, ...], the stack its
    second, aliased to the stack it was handed."""
    from flexflow_tpu.serve import kernels as K

    R, H, P, N = 4, 3, 8, 16
    z = lambda *shape: jnp.zeros(shape, jnp.float32)
    jaxpr = jax.make_jaxpr(K.ssm_recur_c1)(
        z(3, R, H, P, N), jnp.int32(1), z(R, H), z(R, H, P), z(R, N), z(R, N),
        jnp.ones((R,), jnp.int32), jnp.zeros((R,), bool))
    call, = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert call.params["name"] == "ff_ssm_recur_c1"
    y, stack = call.outvars
    assert y.aval.shape[:2] == (R, 1) and stack.aval.shape == (3, R, H, P, N)
    assert tuple(call.params["input_output_aliases"]) == ((7, 1),)
    assert call.invars[7].aval.shape == stack.aval.shape


# --- the gated delta rule of the decode step (ff_gdn_recur_c1) ---------------


@pytest.mark.parametrize("layer", [0, 2])
@pytest.mark.parametrize("R, H, dk, dv", [
    (4, 3, 8, 16), (4, 2, 8, 64), (4, 4, 16, 64), (4, 30, 96, 192)],
    ids=["tiny-p1", "pair-p2", "two-pairs-p2", "published-p2"])
def test_gdn_recur_c1_is_the_delta_rule_at_one_column(R, H, dk, dv, layer):
    """``olmo_hybrid.recurrence_c1`` (the kernel on the whole stack as
    the cache holds it, ``lane_pack`` heads side by side on the lanes,
    the layer a traced index) against ``gated_delta`` at one column on
    that layer's states with the heads apart, at the tests' tiny shape
    (no packing), at widths that pack in pairs and at a row of the
    published one (15 pairs of 96 x 384, 2.2 MB): the addressed layer's
    rows to float32 rounding (the sums over dk take another order), the
    other layers bitwise untouched, a row with no real token bitwise
    untouched, a fresh row as from a zero state over a stale one."""
    from flexflow_tpu.models import olmo_hybrid as fam
    from flexflow_tpu.serve import kernels as K

    p = fam.lane_pack(H, dv)
    assert p == (1 if dv == 16 else 2)
    assert K.ssm_recur_block(H // p, dk, p * dv) == H // p
    rng = np.random.default_rng(13)
    draw = lambda *shape: jnp.asarray(rng.standard_normal(shape).astype(np.float32))
    plain = draw(3, R, H, dk, dv)
    states = fam.pack_heads(plain, p)
    assert states.shape == (3, R, H // p, dk, p * dv)
    np.testing.assert_array_equal(fam.unpack_heads(states, p), plain)
    q, k = fam._l2norm(draw(R, H, dk)) * dk ** -0.5, fam._l2norm(draw(R, H, dk))
    v = draw(R, H, dv)
    g = jnp.log(jnp.asarray(rng.uniform(0.5, 1.0, (R, H)).astype(np.float32)))
    b = jnp.asarray(rng.uniform(0.0, 2.0, (R, H)).astype(np.float32))
    count = jnp.asarray([1, 0, 1, 1], jnp.int32)
    fresh = jnp.asarray([False, False, True, False])
    column = lambda *xs: tuple(x[:, None] for x in xs)
    want_o, want_s = fam.gated_delta(
        *column(q, k, v, g, b), plain[layer], count, fresh)
    o, got = jax.jit(fam.recurrence_c1)(
        q, k, v, g, b, states, jnp.int32(layer), count, fresh)
    assert o.shape == (R, H, dv) and o.dtype == got.dtype == jnp.float32
    assert got.shape == states.shape
    np.testing.assert_allclose(o, want_o[:, 0], rtol=0,
                               atol=2e-6 * float(jnp.abs(want_o).max()))
    tol = dict(rtol=0, atol=2e-6 * float(jnp.abs(want_s).max()))
    np.testing.assert_allclose(fam.unpack_heads(got[layer], p), want_s, **tol)
    for other in {0, 1, 2} - {layer}:
        np.testing.assert_array_equal(got[other], states[other])
    np.testing.assert_array_equal(got[layer, 1], states[layer, 1])
    # the fresh row: what a zero state gives, whatever the slot held
    _, zero = fam.gated_delta(
        *column(q[2:3], k[2:3], v[2:3], g[2:3], b[2:3]),
        jnp.zeros((1, H, dk, dv)), count[2:3], fresh[2:3])
    np.testing.assert_allclose(fam.unpack_heads(got[layer, 2], p), zero[0], **tol)
    # XLA's rule on the packed form is the rule on the heads apart
    packed_o, packed_s = fam.gated_delta(
        *column(q, k, v, g, b), states[layer], count, fresh)
    np.testing.assert_allclose(packed_o, want_o, rtol=0, atol=tol["atol"])
    np.testing.assert_allclose(fam.unpack_heads(packed_s, p), want_s, **tol)


def test_gdn_recur_c1_result_leads_with_o_and_aliases_the_stack():
    """What the callers and the benchmark's trace reduction lean on: o
    is the call's FIRST result and [slots, 1, ...] ([slots, 1, H, dv]
    where no heads share a row of lanes), the stack its second, aliased
    to the stack it was handed."""
    from flexflow_tpu.serve import kernels as K

    z = lambda *shape: jnp.zeros(shape, jnp.float32)
    for R, H, dk, dv, p in ((4, 3, 8, 16, 1), (4, 4, 8, 64, 2)):
        jaxpr = jax.make_jaxpr(K.gdn_recur_c1)(
            z(3, R, H // p, dk, p * dv), jnp.int32(1), z(R, H, dk), z(R, H, dk),
            z(R, H, dv), z(R, H), z(R, H), jnp.ones((R,), jnp.int32),
            jnp.zeros((R,), bool))
        call, = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
        assert call.params["name"] == "ff_gdn_recur_c1"
        o, stack = call.outvars
        assert o.aval.shape == (R, 1, H // p, p * dv)
        assert stack.aval.shape == (3, R, H // p, dk, p * dv)
        assert tuple(call.params["input_output_aliases"]) == ((5, 1),)
        assert call.invars[5].aval.shape == stack.aval.shape


def test_gdn_recur_c1_refuses_a_state_that_does_not_hold_the_heads():
    from flexflow_tpu.serve import kernels as K

    z = lambda *shape: jnp.zeros(shape, jnp.float32)
    with pytest.raises(ValueError, match="does not hold 4 heads"):
        K.gdn_recur_c1(z(3, 2, 2, 8, 64), 0, z(2, 4, 8), z(2, 4, 8), z(2, 4, 64),
                       z(2, 4), z(2, 4), jnp.ones((2,), jnp.int32),
                       jnp.zeros((2,), bool))
