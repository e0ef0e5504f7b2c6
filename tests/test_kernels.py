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

_VARIANTS = ("plain", "group_mask", "int8")
_PS, _NP = 8, 6                       # page size, logical pages a row
_CACHE_LEN = _PS * _NP - 1            # the scratch position


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
    """One batch through the kernel with and without ``q_len`` and
    through the XLA reference: (positions, q_len, mask, outputs)."""
    from flexflow_tpu.serve import kernels as K

    rng = np.random.default_rng(C + len(variant))
    rows = _mixed_rows(C)
    R, P = len(rows), len(rows) * _NP
    pos = np.full((R, C), _CACHE_LEN, np.int32)
    for r, (first, n) in enumerate(rows):
        pos[r, :n] = np.arange(first, first + n)
    pos = jnp.asarray(pos)
    q = jnp.asarray(rng.normal(size=(R, C, H, dk)), jnp.float32)
    pt = jnp.asarray(rng.permutation(P).reshape(R, _NP), jnp.int32)
    mask = K.paged_serve_mask(None, pos, _NP, _PS, _CACHE_LEN)   # (R, C, S)
    kw = {}
    if variant == "int8":
        kp, vp = (jnp.asarray(rng.integers(-127, 128, size=(P + 1, _PS, KV, dk)),
                              jnp.int8) for _ in range(2))
        kw = dict(k_scale=jnp.asarray(rng.random((P + 1, KV)) * 0.02, jnp.float32),
                  v_scale=jnp.asarray(rng.random((P + 1, KV)) * 0.02, jnp.float32))
    else:
        kp, vp = (jnp.asarray(rng.normal(size=(P + 1, _PS, KV, dk)), jnp.float32)
                  for _ in range(2))
    if variant == "group_mask":
        # a mask a KV group: each real query keeps its own page and a
        # random half of the others; a padding query keeps every key,
        # as models/minicpm_sala.choose_blocks leaves it
        keep = rng.random((R, KV, C, _NP)) < 0.5
        own = (np.asarray(pos) // _PS)[:, None, :, None] == np.arange(_NP)
        keep = keep | own | (np.asarray(pos) >= _CACHE_LEN)[:, None, :, None]
        mask = mask[:, None] & jnp.asarray(np.repeat(keep, _PS, axis=-1))
        call = functools.partial(K.sparse_paged_attention, q, kp, vp, pt, mask)
        ref = jnp.stack([
            K.ragged_paged_attention_xla(q, kp, vp, pt, mask[:, g])
            .reshape(R, C, KV, H // KV, dk)[:, :, g] for g in range(KV)
        ], axis=2).reshape(R, C, H, dk)
    else:
        call = functools.partial(K.ragged_paged_attention, q, kp, vp, pt, mask, **kw)
        ref = K.ragged_paged_attention_xla(q, kp, vp, pt, mask, **kw)
    q_len = K.real_query_lengths(pos, _CACHE_LEN)
    outs = dict(
        none=call(), q_len=call(q_len=q_len),
        full=call(q_len=jnp.full((R,), C, jnp.int32)), ref=ref,
    )
    return (np.asarray(pos), np.asarray(q_len), np.asarray(mask),
            {k: np.asarray(v) for k, v in outs.items()})


_Q_LEN_CASES = [(v, C) for v in _VARIANTS for C in (1, 16)]
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
                                   atol=2e-5)


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
    np.testing.assert_allclose(outs["none"], outs["ref"], atol=2e-5)


def test_q_len_none_traces_the_old_operands():
    """A caller that passes no ``q_len`` gets the old ``pallas_call``:
    one prefetched scalar (two with a row offset), no per-row branch."""
    from flexflow_tpu.serve import kernels as K

    def prefetched(**kw):
        jaxpr = jax.make_jaxpr(functools.partial(K.ragged_paged_attention, **kw))(
            jnp.zeros((2, 16, H, dk)), jnp.zeros((5, _PS, KV, dk)),
            jnp.zeros((5, _PS, KV, dk)), jnp.zeros((2, _NP), jnp.int32),
            jnp.zeros((2, 16, _NP * _PS), bool))
        call, = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
        return call.params["grid_mapping"].num_index_operands

    assert prefetched() == 1
    assert prefetched(row_offset=3) == 2
    assert prefetched(row_offset=3, q_len=jnp.zeros((2,), jnp.int32)) == 3


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
    assert stats.attn_steps_grid == 2 * len(q_len) * _NP
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
    assert s.attn_steps_grid == s.steps * 4 * m.engine.serving.pages_per_slot
    assert 0 < s.attn_steps_narrow <= s.attn_steps_live < s.attn_steps_grid


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
