"""Granite 4.0-H on the paged serving path (models/granite_hybrid.py)
against its plain reference (benchmarks/references/granite_hybrid.py,
the one copy; imported by path), and the reference against the
published modelling code (``transformers``' ``GraniteMoeHybridForCausalLM``),
at a tiny size on the CPU with the family's own seeded weights (the
published initialisation: ``A`` = 1..H, ``dt`` log-uniform in (0.001,
0.1), ``D`` = 1; taps of order 1/sqrt(4), a convolution bias of 0.2; the
query and key projections at 0.5, so that the softmax is not flat):
three heads (no power of two) of P = 8 over a state of N = 16 (P != N),
one group, GQA 4/2, ``layer_types`` mamba, mamba, attention, mamba,
attention (both transitions).

Tolerances, each with its reason.

SCAN: the chunk form against the recurrence token by token in float64,
max|d| / max|want| of the outputs and of the final state under 1e-5.
Sound float32 reads 1e-6 at worst (the sums in another order; decay 0.5
a token at C = 128, where exp(-G) would overflow); the state rounded to
bfloat16 between two chunks reads 9e-4, and is held to fail below.

LOGITS: rms(served - reference) / rms(reference) a judged row. Float32
model, pool and state: under 2e-6; sound reads 1e-7 to 2e-7 on both
attention paths. Left out, each part reads (my CPU readings, PR 46): the
softmax scale (the default 1/sqrt(d) in its place) 0.025, the
convolution's bias 0.17, the skip 0.18, the residual multiplier 0.54,
the embedding's 1.2, the logits' divisor 7.0: the parametrised case
below holds each over 1e-3, five hundred limits; a state or a chunk sum
in bfloat16 reads 9e-4 on the scan's own output (above). bfloat16
model, pool and convolution states (the recurrent state stays float32):
under 0.02; sound reads 0.003 to 0.006 a row over two seeds, bfloat16's
own rounding of every activation; the norm BEFORE each sublayer keeps it
from growing with depth (Olmo's tiny model, normed AFTER, reads 0.02 to
0.10). At this size the reference computed in int8 reads 0.001 to
0.003, UNDER bfloat16's: the bfloat16 case holds the served dtypes to
the structure, the float32 cases hold the arithmetic.

PUBLISHED CODE: the reference's float32 logits against
``GraniteMoeHybridForCausalLM``'s (float32, eager attention, the naive
chunked scan of ``torch_forward``; every vector drawn, none left at its
constant) on the same seeded weights: under 2e-6, sound reads 2e-7 (3e-7
the worst position), and the served path on the same weights 2e-7; with
the gate moved behind the norm in the published class the same
comparison reads 0.18.

TOKENS: greedy tokens through ``RequestManager`` are the reference's
argmax at every position (teacher-forced).
"""
import dataclasses
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.models import granite_hybrid as fam
from flexflow_tpu.models import transformer
from flexflow_tpu.serve.engine import InferenceEngine

from family_cases import *  # noqa: F401,F403 (the cases every family answers)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCAN_LIMIT = 1e-5
LOGITS_LIMIT = {jnp.float32: 2e-6, jnp.bfloat16: 0.02}
PAGE, CHUNK, SLOTS, MAX_SEQ = 16, 16, 4, 128   # the tiny serving configuration's (conftest.py)
H, HP, N = 3, 8, 16


def _reference():
    path = os.path.join(ROOT, "benchmarks", "references", "granite_hybrid.py")
    spec = importlib.util.spec_from_file_location("reference_granite_hybrid", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


reference = _reference()


def _file_config(cfg):
    """The tiny preset as a configuration FILE's keys: what the
    reference reads."""
    return dict(
        num_hidden_layers=cfg.num_hidden_layers, layer_types=list(cfg.layer_types),
        rms_norm_eps=cfg.norm_eps, num_attention_heads=cfg.num_attention_heads,
        num_key_value_heads=cfg.num_key_value_heads,
        mamba_n_heads=cfg.mamba_n_heads, mamba_d_head=cfg.mamba_d_head,
        mamba_d_state=cfg.mamba_d_state, mamba_n_groups=1,
        embedding_multiplier=cfg.embedding_multiplier,
        residual_multiplier=cfg.residual_multiplier,
        attention_multiplier=cfg.attention_multiplier,
        logits_scaling=cfg.logits_scaling)


def _sharp(params):
    """``wq`` and ``wk`` times 25: at a draw of 0.02 every score is near
    0 and the softmax flat at any scale; at 0.5 the scores are of order
    one at the published scale and of order ten at the default one."""
    attn = params["attn"]
    return dict(params, attn=dict(attn, wq=attn["wq"] * 25, wk=attn["wk"] * 25))


def _draw(key, cfg):
    return _sharp(fam.init_params(key, cfg))


FAMILIES = {"granite_hybrid": Family(fam, ALWAYS | {"ff.mixer"}, draw=_draw)}


@pytest.fixture(scope="module")
def tiny(tiny_servers):
    return tiny_servers.params(fam, draw=_draw)


@pytest.fixture
def served(tiny_servers):
    """kernels -> the file's kept server (a server is a set of compiled
    step programs): under ``pallas`` its C=1 program runs the recurrence
    kernel, its mixed programs XLA's recurrence."""
    return lambda kernels="xla", **kw: tiny_servers(
        fam, draw=_draw, kernels=kernels, **kw)


@pytest.fixture
def shared(served):
    return served().llm


def _release(eng):
    for r in range(eng.num_slots):
        eng.pager.release(r)


def _feed(eng, rows, chunk):
    """One ``run_mixed`` step: ``rows`` maps slot -> (tokens, first
    position). Returns the logits (slots, vocab) at each row's last
    token."""
    R = eng.num_slots
    toks = np.zeros((R, chunk), np.int32)
    pos = np.full((R, chunk), eng.scratch_pos, np.int32)
    idx = np.zeros((R,), np.int32)
    for r, (t, lo) in rows.items():
        toks[r, :len(t)] = t
        pos[r, :len(t)] = np.arange(lo, lo + len(t))
        idx[r] = len(t) - 1
        assert eng.pager.ensure(r, lo + len(t))
    ones = np.ones(R, np.float32)
    _, logits = eng.run_mixed(
        np.zeros(R, np.int32), toks, np.zeros(R, bool), pos, idx,
        jax.random.PRNGKey(0), np.ones(R, bool), ones, ones,
        np.zeros(R, np.int32), with_logits=True)
    return np.asarray(logits, np.float32)


def _rms_share(got, want):
    return float(np.sqrt(np.mean((got - want) ** 2) / np.mean(want ** 2)))


def _drive(eng, seqs):
    """Chunked prefill of row 0 (a ragged last chunk), mixed steps in
    which it decodes while row 2 prefills, then pure decode steps.
    -> {(row, position): logits} of every row the server would sample
    from."""
    judged, done = {}, {0: 0, 2: 0}

    def step(chunk, feed):
        rows = {r: (seqs[r][done[r]:done[r] + n], done[r]) for r, n in feed.items()}
        logits = _feed(eng, rows, chunk)
        for r, n in feed.items():
            done[r] += n
            judged[(r, done[r] - 1)] = logits[r]

    while done[0] < 39:                        # row 0 prefills alone: 16, 16, 7
        step(CHUNK, {0: min(CHUNK, 39 - done[0])})
    while done[2] < 45:                        # row 0 decodes, row 2 prefills
        step(CHUNK, {0: 1, 2: min(CHUNK, 45 - done[2])})
    for _ in range(4):                         # both decode
        step(1, {0: 1, 2: 1})
    _release(eng)
    return judged


def _worst(judged, want):
    return max(_rms_share(got, want[r // 2, t]) for (r, t), got in judged.items())


# --- (a) the chunk form against the recurrence -------------------------------


def _scan_inputs(rng, R, T, decay):
    f32 = np.float32
    xs = rng.standard_normal((R, T, H, HP)).astype(f32)
    B = rng.standard_normal((R, T, N)).astype(f32)
    C = rng.standard_normal((R, T, N)).astype(f32)
    A = np.asarray([0.5, 1.0, 2.0], f32)
    # a = exp(-A dt): the middle head decays by ``decay`` a token
    dt = (-np.log(decay) * rng.uniform(0.5, 1.5, (R, T, H))).astype(f32)
    D = rng.standard_normal((H,)).astype(f32)
    return xs, B, C, dt, A, D


def _token_by_token(xs, B, C, dt, A, D, S, n):
    """The recurrence of one row's first ``n`` tokens in float64, in
    the reference's order. -> (y (n, H, P), the state after them)."""
    S = S.astype(np.float64).copy()
    y = np.zeros((n,) + xs.shape[1:])
    for t in range(n):
        a = np.exp(-A.astype(np.float64) * dt[t])
        S = a[:, None, None] * S + (dt[t][:, None] * xs[t])[:, :, None] * B[t]
        y[t] = np.einsum("hpn,n->hp", S, C[t]) + D[:, None] * xs[t]
    return y, S


@pytest.mark.parametrize("decay", [0.98, 0.5], ids=["near-1", "near-half"])
@pytest.mark.parametrize("C", [16, 128])
def test_the_chunk_form_is_the_recurrence(C, decay):
    """Ragged real lengths: a full row that carries its state, a fresh
    row (its stale state is not read), a row with no real token (its
    state bitwise unchanged), a row of one token. At C = 128 and a decay
    of 0.5 a token ``exp(-G)`` passes float32's range inside the chunk:
    every exponent has to be a difference on the triangle."""
    rng = np.random.default_rng(C)
    xs, B, Cm, dt, A, D = _scan_inputs(rng, 4, C, decay)
    state = rng.standard_normal((4, H, HP, N)).astype(np.float32)
    count = np.asarray([C, C * 5 // 8 + 1, 0, 1], np.int32)
    fresh = np.asarray([False, True, False, False])
    y, s = map(np.asarray, fam.selective_scan(
        *map(jnp.asarray, (xs, B, Cm, dt, state, count, fresh)),
        A=jnp.asarray(A), D=jnp.asarray(D)))
    assert np.isfinite(y).all() and np.isfinite(s).all()
    np.testing.assert_array_equal(s[2], state[2])
    for r in (0, 1, 3):
        n = count[r]
        s0 = np.zeros_like(state[r]) if fresh[r] else state[r]
        want_y, want_s = _token_by_token(xs[r], B[r], Cm[r], dt[r], A, D, s0, n)
        assert np.abs(y[r, :n] - want_y).max() / np.abs(want_y).max() < SCAN_LIMIT
        assert np.abs(s[r] - want_s).max() / np.abs(want_s).max() < SCAN_LIMIT


def test_one_token_a_row_is_the_recurrence_and_bfloat16_state_is_not():
    """C = 1 (the decode step's form) a token at a time against float64,
    and the limit's other side: the same chunks with the state rounded
    to bfloat16 between them fail it."""
    rng = np.random.default_rng(3)
    T = 48
    xs, B, Cm, dt, A, D = _scan_inputs(rng, 2, T, 0.9)
    token = (xs, B, Cm, dt)
    consts = dict(A=jnp.asarray(A), D=jnp.asarray(D))
    zero = jnp.zeros((2, H, HP, N), jnp.float32)
    ones, fresh = jnp.ones((2,), jnp.int32), jnp.zeros((2,), bool)
    s, outs = zero, []
    for t in range(T):
        y, s = fam.selective_scan(*(jnp.asarray(x[:, t:t + 1]) for x in token),
                                  s, ones, fresh, **consts)
        outs.append(np.asarray(y)[:, 0])
    got = np.stack(outs, axis=1)
    worst = {}
    for rounded in (False, True):
        s, chunks = zero, []
        for lo in range(0, T, 16):
            y, s = fam.selective_scan(*(jnp.asarray(x[:, lo:lo + 16]) for x in token),
                                      s, 16 * ones, fresh, **consts)
            if rounded:
                s = s.astype(jnp.bfloat16).astype(jnp.float32)
            chunks.append(np.asarray(y))
        chunked = np.concatenate(chunks, axis=1)
        errs = []
        for r in range(2):
            want, _ = _token_by_token(xs[r], B[r], Cm[r], dt[r], A, D,
                                      np.zeros((H, HP, N)), T)
            errs.append(np.abs(chunked[r] - want).max() / np.abs(want).max())
            if not rounded:
                assert np.abs(got[r] - want).max() / np.abs(want).max() < SCAN_LIMIT
        worst[rounded] = max(errs)
    assert worst[False] < SCAN_LIMIT < 3e-4 < worst[True], worst


# --- (b) the served path against the reference ------------------------------


@pytest.fixture(scope="module")
def seqs(tiny):
    rng = np.random.default_rng(1)
    return {r: rng.integers(0, tiny[0].vocab_size, 70).tolist() for r in (0, 2)}


@pytest.fixture(scope="module")
def wanted(tiny, seqs):
    cfg, params = tiny
    return reference.forward(params, _file_config(cfg),
                             np.asarray([seqs[0], seqs[2]]))


@pytest.mark.parametrize("kernels, dtype", [
    ("xla", jnp.float32), ("pallas", jnp.float32), ("pallas", jnp.bfloat16)],
    ids=["xla-f32", "pallas-f32", "pallas-bf16"])
def test_served_logits_match_the_reference(tiny, served, seqs, wanted, kernels, dtype):
    """Chunked prefill of one row (a ragged last chunk), mixed steps in
    which it decodes while another prefills (packed rungs of the
    ladder: the recurrence for the row of one token, the chunk form for
    the other), then pure decode steps: every row the server would
    sample from, against the reference's full forward pass."""
    cfg, params = tiny
    want = wanted
    if dtype == jnp.bfloat16:
        cfg = dataclasses.replace(cfg, dtype=dtype)
        params = _sharp(fam.init_params(jax.random.PRNGKey(0), cfg))
        want = reference.forward(params, _file_config(cfg),
                                 np.asarray([seqs[0], seqs[2]]))
        eng = served(kernels, cfg=cfg, params=params, cache_dtype=dtype).engine
    else:
        eng = served(kernels).engine
    assert eng.pack_ladder(CHUNK) == (16, 32)
    assert eng.cache["state"].dtype == jnp.float32 and eng.cache["conv"].dtype == dtype
    assert eng.cache["state"].shape == (3, SLOTS, H, HP, N)
    assert eng.cache["k"].shape[0] == 2        # the attention layers' pool only
    judged = _drive(eng, seqs)
    worst = _worst(judged, want)
    assert len(judged) == 3 + 2 * 3 + 2 * 4 and worst < LOGITS_LIMIT[dtype], worst


def _without(name):
    """(config changes, the weights zeroed) that leave ``name`` out of
    the served model."""
    d = fam.tiny().head_dim
    return {
        "embedding_multiplier": (dict(embedding_multiplier=1.0), ()),
        "residual_multiplier": (dict(residual_multiplier=1.0), ()),
        # the kernel's and the XLA path's default scale
        "attention_multiplier": (dict(attention_multiplier=d ** -0.5), ()),
        "logits_scaling": (dict(logits_scaling=1.0), ()),
        "skip": ({}, ("D",)),
        "conv_bias": ({}, ("conv_bias",)),
    }[name]


@pytest.mark.parametrize("name", [
    "embedding_multiplier", "residual_multiplier", "attention_multiplier",
    "logits_scaling", "skip", "conv_bias"])
def test_a_part_left_out_fails_the_comparison(tiny, served, seqs, wanted, name):
    """The four multipliers, the skip ``D xs`` and the convolution's
    bias each move the logits by over five hundred times the float32
    limit: none hides inside it."""
    cfg, params = tiny
    changes, zeroed = _without(name)
    params = dict(params, ssm={
        k: jnp.zeros_like(v) if k in zeroed else v for k, v in params["ssm"].items()})
    eng = served(cfg=dataclasses.replace(cfg, **changes), params=params).engine
    judged = _drive(eng, seqs)
    assert _worst(judged, wanted) > 500 * LOGITS_LIMIT[jnp.float32]


def test_a_packed_rung_is_the_padded_step(tiny, served, monkeypatch):
    """The same mixed steps with and without the packed token axis: the
    logits and both states agree to float32 rounding (matmuls of another
    extent sum in another order)."""
    cfg, _ = tiny
    rng = np.random.default_rng(2)
    seq = {r: rng.integers(0, cfg.vocab_size, 30).tolist() for r in (1, 3)}
    out = []
    for packed in (True, False):
        monkeypatch.setattr(fam, "PACKED_STEP", packed)
        eng = served(fresh=True).engine   # both states are compared whole
        assert bool(eng.pack_ladder(CHUNK)) == packed
        _feed(eng, {1: (seq[1][:CHUNK], 0)}, CHUNK)
        logits = _feed(eng, {1: (seq[1][CHUNK:CHUNK + 1], CHUNK), 3: (seq[3][:11], 0)}, CHUNK)
        out.append((logits[[1, 3]], np.asarray(eng.cache["state"]),
                    np.asarray(eng.cache["conv"])))
    for a, b in zip(*out):
        np.testing.assert_allclose(a, b, rtol=0, atol=2e-5 * np.abs(a).max())


def _is_the_references_greedy(tiny, prompt, output):
    cfg, params = tiny
    want = reference.forward(params, _file_config(cfg), np.asarray([prompt + output]))[0]
    return output == want[len(prompt) - 1:-1].argmax(-1).tolist()


def test_greedy_tokens_through_generate_are_the_references(tiny, shared):
    """And the counters beside them: a reset a request, the recurrent
    updates of every real token the pipelined steps held (a request's
    prompt and all its answer's tokens but the last, which is sampled
    and never fed), counted here by hand, times the three mamba
    layers."""
    cfg, _ = tiny
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab_size, n).tolist() for n in (21, 40, 9)]
    before = dataclasses.replace(shared.rm.stats)
    outs = shared.generate(prompts, max_new_tokens=6)
    for prompt, out in zip(prompts, outs):
        assert _is_the_references_greedy(tiny, prompt, out.output_tokens)
    stats = shared.rm.stats
    assert stats.state_resets - before.state_resets == 3
    assert stats.slot_state_bytes == shared.engine.slot_state_bytes() == sum(
        int(shared.engine.cache[n].nbytes) for n in ("state", "conv"))
    fed = (21 + 40 + 9) + 3 * 5
    assert cfg.count("ssm") == 3
    assert stats.recurrent_updates - before.recurrent_updates == 3 * fed


# --- (c) slot reuse and recompute preemption ---------------------------------


def test_a_reused_slot_starts_from_zero_state(tiny, served):
    """One slot, two requests one after the other: the second's logits
    are the reference's for it alone, whatever the first left behind."""
    cfg, params = tiny
    rng = np.random.default_rng(5)
    first, second = (rng.integers(0, cfg.vocab_size, n).tolist() for n in (50, 37))
    used = served(fresh=True, max_requests_per_batch=1).llm
    used.generate([first], max_new_tokens=4)
    for name in ("state", "conv"):
        assert np.abs(np.asarray(used.engine.cache[name])).max() > 0
    again = used.generate([second], max_new_tokens=6)[0].output_tokens
    assert _is_the_references_greedy(tiny, second, again)
    assert used.rm.stats.state_resets == 2
    # and by the logits: the stale states of slot 0 reach nothing
    eng = used.engine
    _feed(eng, {0: (second[:CHUNK], 0)}, CHUNK)
    _feed(eng, {0: (second[CHUNK:2 * CHUNK], CHUNK)}, CHUNK)
    got = _feed(eng, {0: (second[2 * CHUNK:], 2 * CHUNK)}, CHUNK)[0]
    want = reference.forward(params, _file_config(cfg), np.asarray([second]))[0, -1]
    assert _rms_share(got, want) < LOGITS_LIMIT[jnp.float32]


def test_a_preempted_request_recomputes_to_the_same_tokens(tiny, shared, served):
    """An oversubscribed pool preempts and re-admits (recompute from
    position 0, which resets the states): no output changes."""
    cfg, _ = tiny
    rng = np.random.default_rng(6)
    prompts = [rng.integers(0, cfg.vocab_size, 40 + 8 * i).tolist() for i in range(4)]
    want = [o.output_tokens for o in shared.generate(prompts, max_new_tokens=8)]
    tight = served(fresh=True, max_sequence_length=96, max_cached_tokens=128).llm
    outs = tight.generate(prompts, max_new_tokens=8)
    assert [o.output_tokens for o in outs] == want
    assert tight.rm.stats.preemptions > 0, "the pool was never oversubscribed"
    assert tight.rm.stats.state_resets > len(prompts)
    tight.engine.pager.check_no_leaks()


# --- (d) padding leaves the states alone -------------------------------------


@pytest.mark.parametrize("chunk, kernels", [
    (CHUNK, "xla"), (1, "xla"), (CHUNK, "pallas"), (1, "pallas")],
    ids=["16", "1", "16-pallas", "1-pallas"])
def test_a_padded_row_keeps_its_states_bitwise(tiny, served, chunk, kernels):
    cfg, _ = tiny
    eng = served(kernels).engine
    rng = np.random.default_rng(7)
    _feed(eng, {1: (rng.integers(0, cfg.vocab_size, CHUNK).tolist(), 0)}, CHUNK)
    before = (np.asarray(eng.cache["state"])[:, 1], np.asarray(eng.cache["conv"])[:, :, 1])
    assert all(np.abs(a).max() > 0 for a in before)
    _feed(eng, {0: (rng.integers(0, cfg.vocab_size, chunk).tolist(), 0)}, chunk)
    np.testing.assert_array_equal(before[0], np.asarray(eng.cache["state"])[:, 1])
    np.testing.assert_array_equal(before[1], np.asarray(eng.cache["conv"])[:, :, 1])
    _release(eng)


@pytest.mark.parametrize("kernels", ["xla", "pallas"])
def test_a_decoding_row_in_a_mixed_step_updates_as_the_decode_step_does(
        tiny, served, kernels):
    """One real position and fifteen padded ones in the C=16 step leave
    what the C=1 step leaves, to float32 rounding (matmuls of another
    extent). Under ``pallas`` that is the mixed step's XLA recurrence
    against the decode step's kernel."""
    cfg, _ = tiny
    eng = served(kernels).engine
    rng = np.random.default_rng(8)
    prompt = rng.integers(0, cfg.vocab_size, CHUNK + 3).tolist()
    token = [int(rng.integers(0, cfg.vocab_size))]
    states, logits = [], []
    for slot, chunk in ((0, CHUNK), (2, 1)):
        _feed(eng, {slot: (prompt[:CHUNK], 0)}, CHUNK)
        _feed(eng, {slot: (prompt[CHUNK:], CHUNK)}, CHUNK)
        logits.append(_feed(eng, {slot: (token, len(prompt))}, chunk)[slot])
        states.append(np.asarray(eng.cache["state"])[:, slot])
    _release(eng)
    for a, b in (states, logits):
        np.testing.assert_allclose(a, b, rtol=0, atol=2e-5 * np.abs(a).max())


def _step_jaxpr(family, cfg, chunk, kernels):
    params = jax.eval_shape(lambda: family.init_params(jax.random.PRNGKey(0), cfg))
    cache = jax.eval_shape(lambda: family.init_paged_kv_cache(
        cfg, SLOTS * 4, PAGE, jnp.float32, num_slots=SLOTS, cache_len=MAX_SEQ))
    i32 = lambda *dims: jax.ShapeDtypeStruct(dims, jnp.int32)

    def step(params, cache, tokens, positions, logits_idx, page_table):
        return family.serve_step_paged(
            params, cache, tokens, positions, logits_idx, None, None, page_table,
            cfg=cfg, cache_len=MAX_SEQ, kernels=kernels)

    return str(jax.make_jaxpr(step)(
        params, cache, i32(SLOTS, chunk), i32(SLOTS, chunk), i32(SLOTS),
        i32(SLOTS, MAX_SEQ // PAGE)))


def test_only_the_pallas_decode_program_holds_the_recurrence_kernel(tiny):
    """The kernel is chosen by the program's chunk of 1 and
    ``kernels="pallas"``, and is this family's: a call site a run of
    mamba layers in that program (a run is one loop: two, one, one
    layers here), none in the mixed step's (whose rows of one token
    keep XLA's recurrence), the XLA path's or Olmo's."""
    from flexflow_tpu.models import olmo_hybrid

    cfg, _ = tiny
    calls = lambda *a: _step_jaxpr(*a).count("name=ff_ssm_recur_c1")
    assert cfg.layer_types == (fam.MAMBA, fam.MAMBA, fam.ATTENTION,
                               fam.MAMBA, fam.ATTENTION)
    assert calls(fam, cfg, 1, "pallas") == 2
    assert calls(fam, cfg, CHUNK, "pallas") == 0
    assert calls(fam, cfg, 1, "xla") == calls(fam, cfg, CHUNK, "xla") == 0
    olmo = olmo_hybrid.tiny(dtype=jnp.float32)
    text = _step_jaxpr(olmo_hybrid, olmo, 1, "pallas")
    assert "pallas_call" in text and "ff_ssm_recur" not in text


# --- (e) what is refused, by name -------------------------------------------


@pytest.mark.parametrize("serving, model, specinfer, names", [
    (dict(prefix_caching=True), 1, False, "prefix_caching"),
    ({}, 1, True, "SpecInfer or beam search"),
    (dict(kv_quant="int8"), 1, False, "kv_quant"),
    (dict(fused_decode=("rope_kv_write",)), 1, False, "fused_decode"),
    (dict(kv_shard="context", context_shards=2), 1, False, "kv_shard"),
    (dict(kv_layout="dense"), 1, False, "kv_layout"),
    ({}, 2, False, "model > 1"),
], ids=["prefix_caching", "specinfer", "kv_quant", "fused_decode", "kv_shard",
        "dense", "model"])
def test_the_seven_refusals_name_their_reason(tiny, tiny_servers, serving, model, specinfer, names):
    """``validate_serving``, as the engine calls it at construction."""
    from flexflow_tpu.core.mesh import MachineSpec

    cfg, params = tiny
    mesh = MachineSpec(model=model).make_mesh(jax.devices()[:model])
    with pytest.raises(NotImplementedError, match=f"granite_hybrid does not serve.*{names}"):
        fam.validate_serving(cfg, tiny_servers.serving(**serving), mesh, specinfer=specinfer)
    if not specinfer:  # and the engine does call it
        # (a fused prologue the family does not advertise is refused
        # before the family is asked)
        with pytest.raises((NotImplementedError, ValueError),
                           match="granite_hybrid does not|does not advertise"):
            InferenceEngine(fam, cfg, params, tiny_servers.serving(**serving), mesh)


def test_beam_search_is_refused(shared):
    from flexflow_tpu.serve import GenerationConfig

    with pytest.raises(NotImplementedError, match="recurrent state"):
        shared.generate([[1, 2, 3]], GenerationConfig(num_beams=2, max_new_tokens=2))


# --- (f) the configuration file ----------------------------------------------


def _benchmark_file():
    with open(os.path.join(ROOT, "benchmarks", "configs", "granite-4.0-h-micro.json")) as f:
        return json.load(f)


def test_from_hf_reads_the_benchmark_configuration():
    hf = _benchmark_file()
    cfg = fam.from_hf(hf, dtype=jnp.bfloat16)
    assert cfg.num_hidden_layers == 40 and cfg.head_dim == 64
    assert [cfg.count(g) for g in fam.GROUPS] == [36, 4, 40]
    assert (cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state, cfg.mamba_d_conv,
            cfg.inner_size, cfg.conv_dim) == (64, 64, 128, 4, 4096, 4352)
    assert cfg.inner_size == hf["mamba_expand"] * hf["hidden_size"]
    assert (cfg.embedding_multiplier, cfg.residual_multiplier,
            cfg.attention_multiplier, cfg.logits_scaling) == (12.0, 0.22, 0.015625, 8.0)
    assert cfg.tie_word_embeddings and cfg.positions == "none"
    assert cfg.state_slots == 64 and cfg.norm_eps == 1e-5
    assert cfg.intermediate_size == 8192
    # 36 x 76.2 M + 4 x 60.8 M + 205.5 M: 3.19 G
    assert abs(fam.num_params(cfg) / 1e9 - 3.19) < 0.01
    # nine runs: 5, 1, 9, 1, 9, 1, 9, 1, 4 layers
    runs = transformer.layer_runs(cfg.kinds)
    assert [n for _, _, n in runs] == [5, 1, 9, 1, 9, 1, 9, 1, 4]
    assert [kind[0] for kind, _, _ in runs] == ["ssm", "attn"] * 4 + ["ssm"]
    assert runs[4][1] == {"ssm": 14, "ffn": 16} and runs[7][1] == {"attn": 3, "ffn": 35}
    # every published key (the catalog's row of the file), unchanged
    published = dict(
        attention_bias=False, attention_multiplier=0.015625, embedding_multiplier=12,
        hidden_act="silu", hidden_size=2048, intermediate_size=8192,
        layer_types=[fam.ATTENTION if i % 10 == 5 else fam.MAMBA for i in range(40)],
        logits_scaling=8, mamba_chunk_size=256, mamba_conv_bias=True, mamba_d_conv=4,
        mamba_d_head=64, mamba_d_state=128, mamba_expand=2, mamba_n_groups=1,
        mamba_n_heads=64, mamba_proj_bias=False, max_position_embeddings=131072,
        model_type="granitemoehybrid", normalization_function="rmsnorm",
        num_attention_heads=32, num_experts_per_tok=0, num_hidden_layers=40,
        num_key_value_heads=8, num_local_experts=0, position_embedding_type="nope",
        residual_multiplier=0.22, rms_norm_eps=1e-5, rope_scaling=None,
        rope_theta=10000, shared_intermediate_size=8192, tie_word_embeddings=True,
        vocab_size=100352)
    assert hf["reduced"] == {}
    for key, value in published.items():
        assert hf[key] == value, key


@pytest.mark.parametrize("key, value", [
    ("num_local_experts", 64), ("position_embedding_type", "rope"),
    ("mamba_n_groups", 8)])
def test_from_hf_refuses_the_larger_siblings_by_name(key, value):
    with pytest.raises(NotImplementedError, match=key):
        fam.from_hf(dict(_benchmark_file(), **{key: value}))


# --- (g) the reference against the published modelling code ------------------


def _published(gate_after_norm=False):
    """A tiny ``GraniteMoeHybridForCausalLM`` with seeded weights (its
    vectors too: ``A_log``, ``dt_bias``, ``D``, the norms' scales and
    the convolution's bias are drawn, not left at their constants), its
    state dict mapped into the family's tree, and the file keys the
    reference reads. -> (model, params, config keys)."""
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    from transformers.models.granitemoehybrid import modeling_granitemoehybrid as published

    kinds = [fam.MAMBA, fam.MAMBA, fam.ATTENTION, fam.MAMBA, fam.ATTENTION]
    hf = transformers.GraniteMoeHybridConfig(
        vocab_size=128, hidden_size=48, intermediate_size=96,
        shared_intermediate_size=96, num_hidden_layers=5, num_attention_heads=4,
        num_key_value_heads=2, layer_types=kinds, num_local_experts=0,
        num_experts_per_tok=0, position_embedding_type="nope",
        mamba_n_heads=6, mamba_d_head=16, mamba_d_state=8, mamba_expand=2,
        mamba_n_groups=1, mamba_d_conv=4, mamba_conv_bias=True,
        mamba_proj_bias=False, mamba_chunk_size=8, attention_bias=False,
        embedding_multiplier=12.0, residual_multiplier=0.22,
        attention_multiplier=0.0625, logits_scaling=8.0, rms_norm_eps=1e-5,
        tie_word_embeddings=True, attn_implementation="eager")
    torch.manual_seed(0)
    model = published.GraniteMoeHybridForCausalLM(hf).float().eval()
    with torch.no_grad():
        for name, w in model.named_parameters():
            if w.ndim == 1 and name.endswith(("A_log", "dt_bias", "D", "weight", "bias")):
                w.copy_(torch.randn_like(w) * 0.3 + (1.0 if name.endswith("weight") else 0.0))
            elif "conv1d.weight" in name:
                w.copy_(torch.randn_like(w) * 0.5)
            else:
                w.copy_(torch.randn_like(w) * 0.1)
    if gate_after_norm:
        def forward(self, hidden_states, gate=None):
            x = hidden_states.float()
            x = x * torch.rsqrt(x.pow(2).mean(-1, keepdim=True) + self.variance_epsilon)
            return self.weight * x * torch.nn.functional.silu(gate.float())

        for layer in model.model.layers:
            if layer.mamba is not None:
                layer.mamba.norm.forward = forward.__get__(layer.mamba.norm)
    sd = {k: np.asarray(v.detach().numpy(), np.float32)
          for k, v in model.state_dict().items()}

    def stack(kind, key, how=lambda w: w):
        return jnp.asarray(np.stack([
            how(sd[f"model.layers.{i}.{key}"]) for i, t in enumerate(kinds)
            if kind in (None, t)]))

    T = np.transpose
    F = hf.shared_intermediate_size
    params = {
        "embed": jnp.asarray(sd["model.embed_tokens.weight"]),
        "final_norm_scale": jnp.asarray(sd["model.norm.weight"]),
        "ssm": {
            "mixer_norm_scale": stack(fam.MAMBA, "input_layernorm.weight"),
            "w_in": stack(fam.MAMBA, "mamba.in_proj.weight", T),
            "conv_w": stack(fam.MAMBA, "mamba.conv1d.weight", lambda w: w[:, 0].T),
            "conv_bias": stack(fam.MAMBA, "mamba.conv1d.bias"),
            "dt_bias": stack(fam.MAMBA, "mamba.dt_bias"),
            "A_log": stack(fam.MAMBA, "mamba.A_log"),
            "D": stack(fam.MAMBA, "mamba.D"),
            "ssm_norm_scale": stack(fam.MAMBA, "mamba.norm.weight"),
            "w_out": stack(fam.MAMBA, "mamba.out_proj.weight", T)},
        "attn": {
            "mixer_norm_scale": stack(fam.ATTENTION, "input_layernorm.weight"),
            "wq": stack(fam.ATTENTION, "self_attn.q_proj.weight", T),
            "wk": stack(fam.ATTENTION, "self_attn.k_proj.weight", T),
            "wv": stack(fam.ATTENTION, "self_attn.v_proj.weight", T),
            "w_out": stack(fam.ATTENTION, "self_attn.o_proj.weight", T)},
        "ffn": {
            "mlp_norm_scale": stack(None, "post_attention_layernorm.weight"),
            "w_gate": stack(None, "shared_mlp.input_linear.weight", lambda w: w[:F].T),
            "w_up": stack(None, "shared_mlp.input_linear.weight", lambda w: w[F:].T),
            "w_out": stack(None, "shared_mlp.output_linear.weight", T)},
    }
    keys = {k: getattr(hf, k) for k in (
        "num_hidden_layers", "rms_norm_eps", "num_attention_heads",
        "num_key_value_heads", "mamba_n_heads", "mamba_d_head", "mamba_d_state",
        "mamba_n_groups", "embedding_multiplier", "residual_multiplier",
        "attention_multiplier", "logits_scaling")}
    keys["layer_types"] = kinds
    return model, params, keys


def _published_logits(model, tokens):
    import torch

    with torch.no_grad():
        return model(torch.as_tensor(tokens)).logits.numpy()


def test_the_reference_is_the_published_code(tiny_servers):
    """The reference's token-by-token forward pass against the
    installed ``GraniteMoeHybridForCausalLM`` (its naive chunked scan at
    a chunk of 8, a ragged 21 tokens): logits, float32."""
    model, params, keys = _published()
    tokens = np.random.default_rng(0).integers(0, 128, (2, 21))
    want = _published_logits(model, tokens)
    got = reference.forward(params, keys, tokens)
    assert _rms_share(got, want) < LOGITS_LIMIT[jnp.float32]
    # and the family's tree serves those weights to the same logits
    cfg = fam.from_hf(dict(
        keys, vocab_size=128, hidden_size=48, shared_intermediate_size=96,
        max_position_embeddings=512, mamba_d_conv=4), dtype=jnp.float32)
    eng = tiny_servers(fam, cfg=cfg, params=params).engine
    served = _feed(eng, {0: (tokens[0, :CHUNK].tolist(), 0)}, CHUNK)
    served = _feed(eng, {0: (tokens[0, CHUNK:].tolist(), CHUNK),
                         1: (tokens[1, :CHUNK].tolist(), 0)}, CHUNK)[0]
    assert _rms_share(served, want[0, -1]) < LOGITS_LIMIT[jnp.float32]


def test_the_gate_behind_the_norm_is_not_the_published_code():
    """The limit's other side: the published class with its gate moved
    behind the norm no longer agrees with the reference."""
    model, params, keys = _published(gate_after_norm=True)
    tokens = np.random.default_rng(0).integers(0, 128, (2, 21))
    got = reference.forward(params, keys, tokens)
    assert _rms_share(got, _published_logits(model, tokens)) > 0.1
