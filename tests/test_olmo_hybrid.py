"""Olmo-Hybrid on the paged serving path (models/olmo_hybrid.py) against
its plain reference (benchmarks/references/olmo_hybrid.py, the one
copy; imported by path), at a tiny size on the CPU with the family's own
seeded weights (decays from A in (0, 16) and dt in (0.001, 0.1), taps of
order 1/sqrt(4)): three heads (no power of two), dk = 8, dv = 16.

Tolerances, each with its reason.

DELTA RULE: the chunk form against the recurrence token by token in
float64, max|d| / max|want| of the outputs and of the final state under
1e-5. Sound float32 reads 2e-6 at worst (the triangular solve and the
sums in another order; decay 0.5 a token at C = 128, where exp(-G)
would overflow); the state rounded to bfloat16 between two chunks reads
2e-3, and is held to fail below.

LOGITS: rms(served - reference) / rms(reference) a judged row. Float32
model, pool and state: under 2e-5; sound reads 4e-6 at worst. Every
chunk's delta rule started from a zero state reads 1.0 to 1.4, the
convolution states alone left out 0.9 to 1.3, ``b`` without its factor
2 1.0 to 1.5 (my CPU readings, PR 44): each fails by orders, as would
a state or a solve in bfloat16 (2e-3 on the delta rule's own output,
above). bfloat16 model, pool and convolution states (the recurrent
state stays float32): under 0.15; sound reads 0.02 to 0.10 a row over
two seeds, bfloat16's own rounding of every activation, which a norm
after each sublayer brings back to full size ten times over at a hidden
size of 48; the reference computed in 9 bits reads 0.05 to 0.19, in int8
0.05 to 0.42 with a median of 0.2. The bfloat16 case holds the served
dtypes to the structure, the float32 cases hold the arithmetic.

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

from flexflow_tpu.models import olmo_hybrid as fam
from flexflow_tpu.models import transformer
from flexflow_tpu.serve.engine import InferenceEngine

from family_cases import *  # noqa: F401,F403 (the cases every family answers)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DELTA_LIMIT = 1e-5
LOGITS_LIMIT = {jnp.float32: 2e-5, jnp.bfloat16: 0.15}
PAGE, CHUNK, SLOTS, MAX_SEQ = 16, 16, 4, 128   # the tiny serving configuration's (conftest.py)
FAMILIES = {"olmo_hybrid": Family(fam, ALWAYS | {"ff.mixer"})}


def _reference():
    path = os.path.join(ROOT, "benchmarks", "references", "olmo_hybrid.py")
    spec = importlib.util.spec_from_file_location("reference_olmo_hybrid", path)
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
        linear_num_value_heads=cfg.linear_num_heads,
        linear_key_head_dim=cfg.linear_key_head_dim,
        linear_value_head_dim=cfg.linear_value_head_dim,
        linear_allow_neg_eigval=cfg.linear_allow_neg_eigval)


@pytest.fixture(scope="module")
def tiny(tiny_servers):
    return tiny_servers.params(fam)


@pytest.fixture
def shared(tiny_servers):
    """The file's kept XLA-path server, for the tests that need no option
    of their own (a server is a set of compiled step programs)."""
    return tiny_servers(fam).llm


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


# --- (a) the chunk form against the recurrence -------------------------------


def _delta_inputs(rng, R, T, decay, H=3, dk=8, dv=16):
    def unit(x):
        return x / np.sqrt((x * x).sum(-1, keepdims=True) + 1e-6)

    f32 = np.float32
    q = (unit(rng.standard_normal((R, T, H, dk))) * dk ** -0.5).astype(f32)
    k = unit(rng.standard_normal((R, T, H, dk))).astype(f32)
    v = rng.standard_normal((R, T, H, dv)).astype(f32)
    g = (np.log(decay) * rng.uniform(0.5, 1.5, (R, T, H))).astype(f32)
    b = rng.uniform(0.0, 2.0, (R, T, H)).astype(f32)
    return q, k, v, g, b


def _token_by_token(q, k, v, g, b, S, n):
    """The recurrence of one row's first ``n`` tokens in float64, in
    the reference's order. -> (o (n, H, dv), the state after them)."""
    S = S.astype(np.float64).copy()
    o = np.zeros((n,) + v.shape[1:])
    for t in range(n):
        S *= np.exp(g[t].astype(np.float64))[:, None, None]
        u = b[t][:, None] * (v[t] - np.einsum("hde,hd->he", S, k[t]))
        S += k[t][:, :, None] * u[:, None, :]
        o[t] = np.einsum("hde,hd->he", S, q[t])
    return o, S


@pytest.mark.parametrize("decay", [0.98, 0.5], ids=["near-1", "near-half"])
@pytest.mark.parametrize("C", [16, 128])
def test_the_chunk_form_is_the_recurrence(C, decay):
    """Ragged real lengths: a full row that carries its state, a fresh
    row (its stale state is not read), a row with no real token (its
    state bitwise unchanged), a row of one token. At C = 128 and a decay
    of 0.5 a token ``exp(-G)`` passes float32's range inside the chunk:
    every exponent has to be a difference on the triangle."""
    rng = np.random.default_rng(C)
    q, k, v, g, b = _delta_inputs(rng, 4, C, decay)
    state = rng.standard_normal((4, 3, 8, 16)).astype(np.float32)
    count = np.asarray([C, C * 5 // 8 + 1, 0, 1], np.int32)
    fresh = np.asarray([False, True, False, False])
    o, s = map(np.asarray, fam.gated_delta(
        *map(jnp.asarray, (q, k, v, g, b, state, count, fresh))))
    assert np.isfinite(o).all() and np.isfinite(s).all()
    np.testing.assert_array_equal(s[2], state[2])
    for r in (0, 1, 3):
        n = count[r]
        s0 = np.zeros_like(state[r]) if fresh[r] else state[r]
        want_o, want_s = _token_by_token(q[r], k[r], v[r], g[r], b[r], s0, n)
        assert np.abs(o[r, :n] - want_o).max() / np.abs(want_o).max() < DELTA_LIMIT
        assert np.abs(s[r] - want_s).max() / np.abs(want_s).max() < DELTA_LIMIT


def test_one_token_a_row_is_the_recurrence_and_bfloat16_state_is_not():
    """C = 1 (the decode step's form, the state read once) a token at a
    time against float64, and the limit's other side: the same chunks
    with the state rounded to bfloat16 between them fail it."""
    rng = np.random.default_rng(3)
    T = 48
    q, k, v, g, b = _delta_inputs(rng, 2, T, 0.9)
    zero = jnp.zeros((2, 3, 8, 16), jnp.float32)
    ones, fresh = jnp.ones((2,), jnp.int32), jnp.zeros((2,), bool)
    s, outs = zero, []
    for t in range(T):
        o, s = fam.gated_delta(*(jnp.asarray(x[:, t:t + 1]) for x in (q, k, v, g, b)),
                               s, ones, fresh)
        outs.append(np.asarray(o)[:, 0])
    got = np.stack(outs, axis=1)
    worst = {}
    for rounded in (False, True):
        s, chunks = zero, []
        for lo in range(0, T, 16):
            o, s = fam.gated_delta(*(jnp.asarray(x[:, lo:lo + 16]) for x in (q, k, v, g, b)),
                                   s, 16 * ones, fresh)
            if rounded:
                s = s.astype(jnp.bfloat16).astype(jnp.float32)
            chunks.append(np.asarray(o))
        chunked = np.concatenate(chunks, axis=1)
        errs = []
        for r in range(2):
            want, _ = _token_by_token(q[r], k[r], v[r], g[r], b[r],
                                      np.zeros((3, 8, 16)), T)
            errs.append(np.abs(chunked[r] - want).max() / np.abs(want).max())
            if not rounded:
                assert np.abs(got[r] - want).max() / np.abs(want).max() < DELTA_LIMIT
        worst[rounded] = max(errs)
    assert worst[False] < DELTA_LIMIT < 1e-3 < worst[True], worst


# --- (b) the served path against the reference ------------------------------


@pytest.mark.parametrize("kernels, dtype", [
    ("xla", jnp.float32), ("pallas", jnp.float32), ("pallas", jnp.bfloat16)],
    ids=["xla-f32", "pallas-f32", "pallas-bf16"])
def test_served_logits_match_the_reference(tiny, kernels, dtype, tiny_servers):
    """Chunked prefill of one row (a ragged last chunk), mixed steps in
    which it decodes while another prefills (packed rungs of the
    ladder: the recurrence for the row of one token, the chunk form for
    the other), then pure decode steps: every row the server would
    sample from, against the reference's full forward pass."""
    cfg, params = tiny
    if dtype == jnp.bfloat16:
        cfg = dataclasses.replace(cfg, dtype=dtype)
        params = fam.init_params(jax.random.PRNGKey(0), cfg)
        eng = tiny_servers(fam, cfg=cfg, params=params, kernels=kernels,
                           cache_dtype=dtype).engine
    else:
        eng = tiny_servers(fam, kernels=kernels).engine
    assert eng.pack_ladder(CHUNK) == (16, 32)
    assert eng.cache["state"].dtype == jnp.float32 and eng.cache["conv"].dtype == dtype
    rng = np.random.default_rng(1)
    seqs = {r: rng.integers(0, cfg.vocab_size, 70).tolist() for r in (0, 2)}
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
    want = reference.forward(
        params, _file_config(cfg), np.asarray([seqs[0], seqs[2]]))
    _release(eng)
    worst = max(_rms_share(got, want[r // 2, t]) for (r, t), got in judged.items())
    assert len(judged) == 3 + 2 * 3 + 2 * 4 and worst < LOGITS_LIMIT[dtype], worst


def test_a_packed_rung_is_the_padded_step(tiny, monkeypatch, tiny_servers):
    """The same mixed steps with and without the packed token axis: the
    logits and both states agree to float32 rounding (matmuls of another
    extent sum in another order)."""
    cfg, _ = tiny
    rng = np.random.default_rng(2)
    seq = {r: rng.integers(0, cfg.vocab_size, 30).tolist() for r in (1, 3)}
    out = []
    for packed in (True, False):
        monkeypatch.setattr(fam, "PACKED_STEP", packed)
        eng = tiny_servers(fam, fresh=True).engine   # both states are compared whole
        assert bool(eng.pack_ladder(CHUNK)) == packed
        _feed(eng, {1: (seq[1][:CHUNK], 0)}, CHUNK)
        logits = _feed(eng, {1: (seq[1][CHUNK:CHUNK + 1], CHUNK), 3: (seq[3][:11], 0)}, CHUNK)
        out.append((logits[[1, 3]], np.asarray(eng.cache["state"]),
                    np.asarray(eng.cache["conv"])))
    for a, b in zip(*out):
        np.testing.assert_allclose(a, b, rtol=0, atol=2e-6)


def _is_the_references_greedy(tiny, prompt, output):
    cfg, params = tiny
    want = reference.forward(params, _file_config(cfg), np.asarray([prompt + output]))[0]
    return output == want[len(prompt) - 1:-1].argmax(-1).tolist()


def test_greedy_tokens_through_generate_are_the_references(tiny, shared):
    """And the counters beside them: a reset a request, the recurrent
    updates of every real token the pipelined steps held (a request's
    prompt and all its answer's tokens but the last, which is sampled
    and never fed), times the four recurrent layers."""
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
    fed = sum(map(len, prompts)) + 3 * 5
    assert cfg.count("gdn") == 4
    assert stats.recurrent_updates - before.recurrent_updates == 4 * fed


# --- (c) slot reuse and recompute preemption ---------------------------------


def test_a_reused_slot_starts_from_zero_state(tiny, tiny_servers):
    """One slot, two requests one after the other: the second's tokens
    are the reference's for it alone, whatever the first left behind."""
    cfg, _ = tiny
    rng = np.random.default_rng(5)
    first, second = (rng.integers(0, cfg.vocab_size, n).tolist() for n in (50, 37))
    used = tiny_servers(fam, fresh=True, max_requests_per_batch=1).llm
    used.generate([first], max_new_tokens=4)
    for name in ("state", "conv"):
        assert np.abs(np.asarray(used.engine.cache[name])).max() > 0
    again = used.generate([second], max_new_tokens=6)[0].output_tokens
    assert _is_the_references_greedy(tiny, second, again)
    assert used.rm.stats.state_resets == 2


def test_a_preempted_request_recomputes_to_the_same_tokens(tiny, shared, tiny_servers):
    """An oversubscribed pool preempts and re-admits (recompute from
    position 0, which resets the states): no output changes."""
    cfg, _ = tiny
    rng = np.random.default_rng(6)
    prompts = [rng.integers(0, cfg.vocab_size, 40 + 8 * i).tolist() for i in range(4)]
    want = [o.output_tokens for o in shared.generate(prompts, max_new_tokens=8)]
    tight = tiny_servers(fam, fresh=True, max_sequence_length=96, max_cached_tokens=128).llm
    outs = tight.generate(prompts, max_new_tokens=8)
    assert [o.output_tokens for o in outs] == want
    assert tight.rm.stats.preemptions > 0, "the pool was never oversubscribed"
    assert tight.rm.stats.state_resets > len(prompts)
    tight.engine.pager.check_no_leaks()


# --- (d) padding leaves the states alone -------------------------------------


@pytest.mark.parametrize("chunk", [CHUNK, 1])
def test_a_padded_row_keeps_its_states_bitwise(tiny, shared, chunk):
    cfg, _ = tiny
    eng = shared.engine
    rng = np.random.default_rng(7)
    _feed(eng, {1: (rng.integers(0, cfg.vocab_size, CHUNK).tolist(), 0)}, CHUNK)
    before = (np.asarray(eng.cache["state"])[:, 1], np.asarray(eng.cache["conv"])[:, :, 1])
    assert all(np.abs(a).max() > 0 for a in before)
    _feed(eng, {0: (rng.integers(0, cfg.vocab_size, chunk).tolist(), 0)}, chunk)
    np.testing.assert_array_equal(before[0], np.asarray(eng.cache["state"])[:, 1])
    np.testing.assert_array_equal(before[1], np.asarray(eng.cache["conv"])[:, :, 1])
    _release(eng)


def test_a_decoding_row_in_a_mixed_step_updates_as_the_decode_step_does(tiny, shared):
    """One real position and fifteen padded ones in the C=16 step leave
    what the C=1 step leaves, to float32 rounding (matmuls of another
    extent)."""
    cfg, _ = tiny
    eng = shared.engine
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
    np.testing.assert_allclose(states[0], states[1], rtol=0, atol=2e-6)
    np.testing.assert_allclose(logits[0], logits[1], rtol=0, atol=2e-6)


def _step_jaxpr(cfg, chunk, kernels, pack=None, family=fam):
    params = jax.eval_shape(lambda: family.init_params(jax.random.PRNGKey(0), cfg))
    cache = jax.eval_shape(lambda: family.init_paged_kv_cache(
        cfg, SLOTS * 4, PAGE, jnp.float32, num_slots=SLOTS, cache_len=MAX_SEQ))
    i32 = lambda *dims: jax.ShapeDtypeStruct(dims, jnp.int32)

    def step(params, cache, tokens, positions, logits_idx, page_table):
        return family.serve_step_paged(
            params, cache, tokens, positions, logits_idx, None, None, page_table,
            cfg=cfg, cache_len=MAX_SEQ, kernels=kernels, pack=pack)

    return str(jax.make_jaxpr(step)(
        params, cache, i32(SLOTS, chunk), i32(SLOTS, chunk), i32(SLOTS),
        i32(SLOTS, MAX_SEQ // PAGE)))


def test_only_the_pallas_decode_program_holds_the_recurrence_kernel(tiny):
    """The kernel is chosen by the program's chunk of 1 and
    ``kernels="pallas"``: a call site a run of recurrent layers in that
    program (a run is one loop: three layers and one here), none in the
    mixed programs (whose rows of one token keep XLA's rule on the same
    packed state), none on the XLA path; and it is this family's: the
    Mamba-2 kernel is in none of them."""
    cfg, _ = tiny
    calls = lambda *a, **k: _step_jaxpr(cfg, *a, **k).count("name=ff_gdn_recur_c1")
    assert cfg.layer_types == (fam.LINEAR,) * 3 + (fam.ATTENTION, fam.LINEAR)
    assert calls(1, "pallas") == 2
    assert calls(CHUNK, "pallas") == calls(CHUNK, "pallas", pack=32) == 0
    assert calls(1, "xla") == calls(CHUNK, "xla") == 0
    text = _step_jaxpr(cfg, CHUNK, "pallas")
    assert "pallas_call" in text and "ff_ssm_recur" not in _step_jaxpr(cfg, 1, "pallas")


@pytest.mark.parametrize("kernels, chunk, pack, digest", [
    ("xla", 1, None, "1a3a0c54ccc87a58"), ("xla", CHUNK, None, "6d931809afad25a2"),
    ("xla", CHUNK, 32, "3959f50f4f5213cd"), ("pallas", 1, None, "cbb048f87a1de0e9"),
    ("pallas", CHUNK, None, "90961cc0184c9813"), ("pallas", CHUNK, 32, "f161ccec95c81c55")])
def test_the_shared_seam_leaves_granites_programs_alone(kernels, chunk, pack, digest):
    """``step_rows`` is shared with ``granite_hybrid``, whose state is
    lane-dense as it is (64 x 128) and whose kernel is its own: its six
    step jaxprs at the tiny preset are PR 47's, by the first 16 hex
    digits of their SHA-256 (taken on the parent commit, PR 48; the
    three Pallas programs' anew by PR 55 and PR 63, whose ragged paged
    kernel body and work list they print; the XLA programs' stand). A PR that means to
    change Granite's programs takes the digests anew; one that means to
    change Olmo's alone does not get here."""
    import hashlib

    from flexflow_tpu.models import granite_hybrid

    text = _step_jaxpr(granite_hybrid.tiny(dtype=jnp.float32), chunk, kernels,
                       pack, family=granite_hybrid)
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == digest


@pytest.mark.parametrize("H, dv, p", [(3, 16, 1), (30, 192, 2), (64, 128, 1),
                                      (4, 64, 2), (3, 64, 1), (6, 32, 1), (8, 32, 4)])
def test_lane_pack_is_the_least_divisor_that_fills_the_lanes(H, dv, p):
    """Read off the geometry: the tests' tiny widths and a width that
    is whole lane tiles already keep the heads apart, the published
    pair of 192 shares 384 lanes, an odd count of heads of 64 has no
    divisor that would."""
    assert fam.lane_pack(H, dv) == p
    s = jnp.arange(2 * H * 5 * dv, dtype=jnp.float32).reshape(2, H, 5, dv)
    packed = fam.pack_heads(s, p)
    assert packed.shape == (2, H // p, 5, p * dv)
    # head h sits on lanes (h % p) dv .. of row h // p
    h = H - 1
    np.testing.assert_array_equal(
        packed[:, h // p, :, (h % p) * dv:(h % p + 1) * dv], s[:, h])
    np.testing.assert_array_equal(fam.unpack_heads(packed, p), s)


@pytest.mark.parametrize("kernels", ["xla", "pallas"])
def test_a_lane_packed_state_serves_what_the_heads_apart_serve(kernels, monkeypatch, tiny_servers):
    """Four recurrent heads of 8 x 64 pack in pairs (``lane_pack``: 128
    lanes a row). The same prefill chunk, mixed step (a decoding row
    beside a prefilling one) and four decode steps with the state
    packed and with the heads kept apart: the logits of every step and
    the state agree to float32 rounding (the C = 1 rule sums over dk on
    the packed form, the chunk form unpacks one row around itself)."""
    cfg = fam.tiny(dtype=jnp.float32, linear_num_heads=4, linear_value_head_dim=64)
    params = fam.init_params(jax.random.PRNGKey(3), cfg)
    rng = np.random.default_rng(5)
    seq = {r: rng.integers(0, cfg.vocab_size, 40).tolist() for r in (1, 3)}
    out = []
    for packed in (True, False):
        if not packed:
            monkeypatch.setattr(fam, "lane_pack", lambda H, dv: 1)
        eng = tiny_servers(fam, cfg=cfg, params=params, kernels=kernels).engine
        p = 2 if packed else 1
        assert eng.cache["state"].shape == (4, SLOTS, 4 // p, 8, p * 64)
        logits = [_feed(eng, {1: (seq[1][:CHUNK], 0)}, CHUNK)[[1]],
                  _feed(eng, {1: (seq[1][CHUNK:CHUNK + 1], CHUNK),
                              3: (seq[3][:11], 0)}, CHUNK)[[1, 3]]]
        for t in range(4):
            logits.append(_feed(eng, {1: (seq[1][CHUNK + 1 + t:][:1], CHUNK + 1 + t),
                                      3: (seq[3][11 + t:][:1], 11 + t)}, 1)[[1, 3]])
        state = fam.unpack_heads(eng.cache["state"], p)
        assert float(jnp.abs(state[:, 1]).max()) > 0 == float(jnp.abs(state[:, 0]).max())
        out.append((np.concatenate(logits), np.asarray(state)))
    for a, b in zip(*out):
        np.testing.assert_allclose(a, b, rtol=0, atol=2e-6 * np.abs(b).max())


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
def test_the_seven_refusals_name_their_reason(
        tiny, serving, model, specinfer, names, tiny_servers):
    """``validate_serving``, as the engine calls it at construction."""
    from flexflow_tpu.core.mesh import MachineSpec

    cfg, params = tiny
    mesh = MachineSpec(model=model).make_mesh(jax.devices()[:model])
    with pytest.raises(NotImplementedError, match=f"olmo_hybrid does not serve.*{names}"):
        fam.validate_serving(cfg, tiny_servers.serving(**serving), mesh, specinfer=specinfer)
    if not specinfer:  # and the engine does call it
        # (a fused prologue the family does not advertise is refused
        # before the family is asked)
        with pytest.raises((NotImplementedError, ValueError),
                           match="olmo_hybrid does not|does not advertise"):
            InferenceEngine(fam, cfg, params, tiny_servers.serving(**serving), mesh)


def test_beam_search_is_refused(shared):
    from flexflow_tpu.serve import GenerationConfig

    with pytest.raises(NotImplementedError, match="recurrent state"):
        shared.generate([[1, 2, 3]], GenerationConfig(num_beams=2, max_new_tokens=2))


# --- (f) the configuration file ----------------------------------------------


def test_from_hf_reads_the_benchmark_configuration():
    with open(os.path.join(ROOT, "benchmarks", "configs", "olmo-hybrid-7b.json")) as f:
        hf = json.load(f)
    cfg = fam.from_hf(hf, dtype=jnp.bfloat16)
    assert cfg.num_hidden_layers == 12 and cfg.head_dim == 128
    assert [cfg.count(g) for g in fam.GROUPS] == [9, 3, 12]
    assert (cfg.linear_num_heads, cfg.linear_key_head_dim, cfg.linear_value_head_dim,
            cfg.linear_conv_kernel_dim, cfg.conv_dim) == (30, 96, 192, 4, 11520)
    assert cfg.linear_allow_neg_eigval and not cfg.tie_word_embeddings
    assert cfg.state_slots == 64 and cfg.norm_eps == 1e-6
    # 9 x 215.3 M + 3 x 185.8 M + 2 x 385.4 M: 3.27 G
    assert abs(fam.num_params(cfg) / 1e9 - 3.27) < 0.01
    assert transformer.layer_runs(cfg.kinds) == [
        (("gdn", "ffn"), {"gdn": 3 * i, "ffn": 4 * i}, 3) if kind == "gdn" else
        (("attn", "ffn"), {"attn": i, "ffn": 4 * i + 3}, 1)
        for i in range(3) for kind in ("gdn", "attn")]
    # every published key (the catalog's row of the file), unchanged but the depth
    published = dict(
        model_type="olmo_hybrid", vocab_size=100352, hidden_size=3840,
        intermediate_size=11008, num_hidden_layers=32, num_attention_heads=30,
        num_key_value_heads=30, hidden_act="silu", max_position_embeddings=65536,
        attention_bias=False, rms_norm_eps=1e-6, tie_word_embeddings=False,
        layer_types=[fam.LINEAR, fam.LINEAR, fam.LINEAR, fam.ATTENTION] * 8,
        linear_num_key_heads=30, linear_num_value_heads=30,
        linear_key_head_dim=96, linear_value_head_dim=192,
        linear_conv_kernel_dim=4, linear_allow_neg_eigval=True,
        rope_parameters={"rope_theta": None})
    assert set(hf["reduced"]) == {"num_hidden_layers", "layer_types"}
    for key, value in published.items():
        if key not in hf["reduced"]:
            assert hf[key] == value, key
    assert hf["layer_types"] == published["layer_types"][:12]
    with pytest.raises(NotImplementedError, match="rope_theta"):
        fam.from_hf(dict(hf, rope_parameters={"rope_theta": 1e4}))


# --- (g) the attention call in blocks of heads --------------------------------


def test_the_ragged_kernel_in_head_blocks_is_the_whole_call(monkeypatch):
    """Thirty K/V heads of one query each pass the fast memory at C=128
    (105 MiB of blocks); the call then takes a merged pool's heads in
    blocks under a leading grid axis (over the call's one axis, its
    work list). Here six heads, the ceiling lowered until two blocks of
    three are taken: the same result to the
    bit, with and without ``q_len`` and a row offset, and the XLA
    attention's to rounding."""
    from flexflow_tpu.serve import kernels

    R, C, H, d, ps, NP, P = 3, 16, 6, 8, 16, 2, 7
    rng = np.random.default_rng(11)
    q = jnp.asarray(rng.standard_normal((R, C, H, d)), jnp.float32)
    k, v = (jnp.asarray(rng.standard_normal((2 * (P + 1), ps, H * d)), jnp.float32)
            for _ in range(2))
    table = jnp.asarray(rng.permutation(P)[:R * NP].reshape(R, NP), jnp.int32)
    count = np.asarray([C, 5, 0])
    first = np.asarray([9, 0, 0])
    pos = first[:, None] + np.arange(C)[None, :]
    mask = jnp.asarray((np.arange(NP * ps)[None, None, :] <= pos[:, :, None])
                       & (np.arange(C)[None, :, None] < count[:, None, None]))
    kw = dict(row_offset=jnp.int32(P + 1), q_len=jnp.asarray(count, jnp.int32))

    def call(**kw):
        return kernels._ragged_paged_attention(q, k, v, table, mask, **kw)

    def grid_rank(**kw):
        text = str(jax.make_jaxpr(lambda: call(**kw))())
        dims = text.split("grid=(")[1].split(")")[0].split(",")
        return len([d for d in dims if d.strip()])  # "(n,)": one axis

    whole = {name: np.asarray(call(**a)) for name, a in (("plain", {}), ("offset", kw))}
    assert grid_rank(**kw) == 1
    # lower the ceiling to just under what six heads a step need (the
    # call's own sum: blocks, buffers, scratch and intermediates)
    full, seen = kernels._ragged_vmem_need, []
    monkeypatch.setattr(kernels, "_ragged_vmem_need",
                        lambda *a: seen.append(full(*a)) or seen[-1])
    call()
    monkeypatch.setattr(kernels, "_VMEM_SCOPE_CEILING", seen[0] - 1)
    assert grid_rank(**kw) == 2
    for name, a in (("plain", {}), ("offset", kw)):
        np.testing.assert_array_equal(np.asarray(call(**a)), whole[name])
    split = (P + 1, ps, H, d)
    want = kernels.ragged_paged_attention_xla(
        q, k[P + 1:].reshape(split), v[P + 1:].reshape(split), table, mask)
    live = np.arange(C)[None, :] < count[:, None]
    np.testing.assert_allclose(whole["offset"][live], np.asarray(want)[live],
                               rtol=0, atol=2e-5)
    assert not whole["offset"][~live].any()
