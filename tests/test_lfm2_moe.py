"""LFM2-MoE on the paged serving path (models/lfm2_moe.py) against its
plain reference (benchmarks/references/lfm2_moe.py, the one copy;
imported by path), at a tiny size on the CPU in float32 with the
family's own seeded weights (conv taps of order 1/sqrt(3), a non-zero
selection offset), a float32 pool and state.

Tolerances, each with its reason. LOGITS: rms(served - reference) /
rms(reference) under 2e-5 a judged row. Sound float32 reads 4e-7 at
worst (another order of the same sums); with the conv state left out
(every chunk starting from zeros) the same rows read 0.2 to 0.5, with
the selection offset weighing as well as choosing 0.02, with the q/k
norm after rope and not before it 1e-2 (my CPU readings, PR 34), so
each fails by orders. TOKENS: greedy tokens through ``RequestManager``
are the reference's argmax at every position (teacher-forced), and a
fresh server's exactly.
"""
import contextlib
import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.models import lfm2_moe as fam
from flexflow_tpu.models import transformer
from flexflow_tpu.obs import sublayers
from flexflow_tpu.serve.engine import InferenceEngine
from flexflow_tpu.serve.llm import LLM

from family_cases import *  # noqa: F401,F403 (the cases every family answers)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LOGITS_LIMIT = 2e-5
PAGE, CHUNK, SLOTS, MAX_SEQ = 16, 16, 4, 128   # the tiny serving configuration's (conftest.py)
FAMILIES = {"lfm2_moe": Family(fam, ALWAYS | {"ff.mixer", "ff.moe.route"})}


def _reference():
    path = os.path.join(ROOT, "benchmarks", "references", "lfm2_moe.py")
    spec = importlib.util.spec_from_file_location("reference_lfm2_moe", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


reference = _reference()


def _file_config(cfg):
    """The tiny preset as a configuration FILE's keys: what the
    reference reads."""
    return dict(
        num_hidden_layers=cfg.num_hidden_layers, layer_types=list(cfg.layer_types),
        num_dense_layers=cfg.num_dense_layers, norm_eps=cfg.norm_eps,
        num_attention_heads=cfg.num_attention_heads,
        num_key_value_heads=cfg.num_key_value_heads,
        num_experts=cfg.num_experts, num_experts_per_tok=cfg.num_experts_per_tok,
        norm_topk_prob=cfg.moe_norm_topk,
        routed_scaling_factor=cfg.routed_scaling_factor,
        rope_parameters={"rope_theta": cfg.rope_theta},
        tolerance={"routing_margin": 0.05})


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


# --- (a) the served path against the reference ------------------------------


@pytest.mark.parametrize("kernels", ["xla", "pallas"])
def test_served_logits_match_the_reference(tiny, kernels, tiny_servers):
    """Chunked prefill of one row (a ragged last chunk), mixed steps in
    which it decodes while another prefills (packed rungs of the
    ladder), then pure decode steps: every row the server would sample
    from, against the reference's full forward pass; the step's expert
    counts are the routed pairs of its real tokens."""
    cfg, params = tiny
    eng = tiny_servers(fam, kernels=kernels).engine
    assert eng.pack_ladder(CHUNK) == (16, 32)
    rng = np.random.default_rng(1)
    seqs = {r: rng.integers(0, cfg.vocab_size, 70).tolist() for r in (0, 2)}
    judged, done = {}, {0: 0, 2: 0}

    def step(chunk, feed):
        rows = {r: (seqs[r][done[r]:done[r] + n], done[r]) for r, n in feed.items()}
        logits = _feed(eng, rows, chunk)
        counts = eng.split_fetch(np.asarray(eng.step_fetch))[1]["moe_counts"]
        assert eng.step_tile == 16             # a few rows an expert at every width here
        assert counts.shape == (cfg.count("sparse"), cfg.num_experts)
        assert (counts.sum(-1) == sum(feed.values()) * cfg.num_experts_per_tok).all()
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
    assert len(judged) == 3 + 2 * 3 + 2 * 4 and worst < LOGITS_LIMIT, worst


def test_a_packed_rung_is_the_padded_step(tiny, monkeypatch, tiny_servers):
    """The same mixed steps with and without the packed token axis: the
    logits and the conv states agree to float32 rounding (matmuls of
    another extent sum in another order)."""
    cfg, _ = tiny
    rng = np.random.default_rng(2)
    seq = {r: rng.integers(0, cfg.vocab_size, 30).tolist() for r in (1, 3)}
    out = []
    for packed in (True, False):
        monkeypatch.setattr(fam, "PACKED_STEP", packed)
        eng = tiny_servers(fam, fresh=True).engine   # the states are compared whole
        assert bool(eng.pack_ladder(CHUNK)) == packed
        _feed(eng, {1: (seq[1][:CHUNK], 0)}, CHUNK)
        logits = _feed(eng, {1: (seq[1][CHUNK:CHUNK + 1], CHUNK), 3: (seq[3][:11], 0)}, CHUNK)
        out.append((logits[[1, 3]], np.asarray(eng.cache["conv"])))
    np.testing.assert_allclose(out[0][0], out[1][0], rtol=0, atol=2e-6)
    np.testing.assert_allclose(out[0][1], out[1][1], rtol=0, atol=2e-6)


def _is_the_references_greedy(tiny, prompt, output):
    cfg, params = tiny
    want = reference.forward(params, _file_config(cfg), np.asarray([prompt + output]))[0]
    return output == want[len(prompt) - 1:-1].argmax(-1).tolist()


def test_greedy_tokens_through_generate_are_the_references(tiny, shared):
    cfg, _ = tiny
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab_size, n).tolist() for n in (21, 40, 9)]
    before = dataclasses.replace(shared.rm.stats)
    outs = shared.generate(prompts, max_new_tokens=6)
    for prompt, out in zip(prompts, outs):
        assert _is_the_references_greedy(tiny, prompt, out.output_tokens)
    stats = shared.rm.stats
    grew = {f: getattr(stats, f) - getattr(before, f) for f in (
        "state_resets", "moe_pairs", "moe_experts_hit", "moe_experts_held", "moe_load_max",
        "moe_tiles")}
    assert grew["state_resets"] == 3
    assert stats.slot_state_bytes == shared.engine.slot_state_bytes() > 0
    # every flushed step's sparse layers: pairs of real tokens only
    assert grew["moe_pairs"] == (sum(map(len, prompts)) + 3 * 5) * cfg.num_experts_per_tok * cfg.count("sparse")
    assert 0 < grew["moe_experts_hit"] <= grew["moe_experts_held"]
    assert grew["moe_experts_held"] % (cfg.count("sparse") * cfg.num_experts) == 0
    assert grew["moe_load_max"] >= grew["moe_pairs"] / cfg.num_experts
    # each flushed step's tiles under its own width's row tile: an expert
    # that was given a token fills at least one, and no more than its tokens
    assert grew["moe_experts_hit"] <= grew["moe_tiles"] <= grew["moe_pairs"]


# --- (b) the conv state across chunk boundaries ------------------------------


@pytest.mark.parametrize("chunk", [1, 7, 16])
def test_chunked_conv_is_the_whole_sequence_convolution(chunk):
    """37 positions in chunks of 1, 7 and 16 (ragged last chunks,
    padded), two rows of which the second is three positions behind and
    a third that is padding throughout: outputs against the explicit sum
    over the taps of the whole sequence, bitwise; the padded row's
    state untouched."""
    D, L, T = 8, 3, 37
    rng = np.random.default_rng(4)
    u = rng.standard_normal((2, T, D)).astype(np.float32)
    taps = rng.standard_normal((L, D)).astype(np.float32)
    past = np.pad(u, ((0, 0), (L - 1, 0), (0, 0)))
    want = sum(taps[j] * past[:, j:j + T] for j in range(L))
    state = jnp.full((L - 1, 3, D), 7.0, jnp.float32)   # stale: position 0 resets it
    got = np.zeros_like(want)
    lens = [T, T - 3]
    row = jnp.repeat(jnp.arange(3), chunk)
    col = jnp.tile(jnp.arange(chunk), 3)
    place = jnp.arange(3 * chunk).reshape(3, chunk)
    for lo in range(0, T, chunk):
        count = np.array([min(chunk, max(0, n - lo)) for n in lens] + [0])
        block = np.zeros((3, chunk, D), np.float32)
        for r in range(2):
            block[r, :count[r]] = u[r, lo:lo + count[r]]
        c, state = fam.short_conv(
            jnp.asarray(block.reshape(3 * chunk, D)), jnp.asarray(taps), state,
            row, col, jnp.asarray(count), jnp.asarray([lo == 0, lo == 0, False]), place)
        c = np.asarray(c).reshape(3, chunk, D)
        for r in range(2):
            got[r, lo:lo + count[r]] = c[r, :count[r]]
    for r in range(2):
        np.testing.assert_allclose(got[r, :lens[r]], want[r, :lens[r]], rtol=0, atol=1e-6)
        np.testing.assert_array_equal(np.asarray(state)[:, r], u[r, lens[r] - 2:lens[r]])
    np.testing.assert_array_equal(np.asarray(state)[:, 2], 7.0)


# --- (c) slot reuse and recompute preemption ---------------------------------


def test_a_reused_slot_starts_from_zero_state(tiny, tiny_servers):
    """One slot, two requests one after the other: the second's tokens
    are the reference's for it alone, whatever the first left behind."""
    cfg, _ = tiny
    rng = np.random.default_rng(5)
    first, second = (rng.integers(0, cfg.vocab_size, n).tolist() for n in (50, 37))
    used = tiny_servers(fam, fresh=True, max_requests_per_batch=1).llm
    used.generate([first], max_new_tokens=4)
    assert np.abs(np.asarray(used.engine.cache["conv"])).max() > 0
    again = used.generate([second], max_new_tokens=6)[0].output_tokens
    assert _is_the_references_greedy(tiny, second, again)
    assert used.rm.stats.state_resets == 2


def test_a_preempted_request_recomputes_to_the_same_tokens(tiny, shared, tiny_servers):
    """An oversubscribed pool preempts and re-admits (recompute from
    position 0, which resets the state): no output changes."""
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


# --- (d) padding leaves the state alone --------------------------------------


@pytest.mark.parametrize("chunk", [CHUNK, 1])
def test_a_padded_row_keeps_its_state_bitwise(tiny, shared, chunk):
    cfg, _ = tiny
    eng = shared.engine
    rng = np.random.default_rng(7)
    _feed(eng, {1: (rng.integers(0, cfg.vocab_size, CHUNK).tolist(), 0)}, CHUNK)
    before = np.asarray(eng.cache["conv"])[:, :, 1]
    assert np.abs(before).max() > 0
    _feed(eng, {0: (rng.integers(0, cfg.vocab_size, chunk).tolist(), 0)}, chunk)
    np.testing.assert_array_equal(before, np.asarray(eng.cache["conv"])[:, :, 1])
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
        states.append(np.asarray(eng.cache["conv"])[:, :, slot])
    _release(eng)
    np.testing.assert_allclose(states[0], states[1], rtol=0, atol=2e-6)
    np.testing.assert_allclose(logits[0], logits[1], rtol=0, atol=2e-6)


# --- (e) the router and the routed layer -------------------------------------


def test_the_offset_chooses_and_does_not_weigh():
    """Three experts of four scores: the offset lifts expert 3 over
    expert 1 into the chosen two; its weight is its own score's share,
    with the 1e-6; without the norm the scores themselves."""
    h = jnp.eye(4, dtype=jnp.float32)[:1]
    logits = jnp.asarray([[2.0, 1.0, -1.0, 0.5]])
    w = jnp.zeros((4, 4)).at[0].set(logits[0])
    s = np.asarray(jax.nn.sigmoid(logits))[0]
    idx, g = transformer.route_sigmoid_topk(h, w, None, 2)
    assert idx.tolist() == [[0, 1]]
    idx, g = transformer.route_sigmoid_topk(h, w, jnp.asarray([0.0, 0.0, 0.0, 0.3]), 2)
    assert idx.tolist() == [[3, 0]]       # 0.62 + 0.3 over 0.88 over 0.73
    np.testing.assert_allclose(np.asarray(g)[0], s[[3, 0]] / (s[0] + s[3] + 1e-6), rtol=1e-6)
    assert abs(float(g.sum()) - 1.0) > 1e-7 and abs(float(g.sum()) - 1.0) < 2e-6
    _, g = transformer.route_sigmoid_topk(h, w, jnp.asarray([0.0, 0.0, 0.0, 0.3]), 2,
                                          norm_topk=False, scaling=2.0)
    np.testing.assert_allclose(np.asarray(g)[0], 2.0 * s[[3, 0]], rtol=1e-6)


def test_a_tie_goes_to_the_lower_index_in_program_and_reference():
    h = jnp.ones((1, 2), jnp.float32)
    w = jnp.asarray([[0.3, 0.1, 0.3, 0.1, 0.3], [0.0] * 5], jnp.float32)
    idx, _ = transformer.route_sigmoid_topk(h, w, jnp.zeros((5,)), 2)
    assert idx.tolist() == [[0, 2]]
    gate, margin = reference._route(h, w, jnp.zeros((5,)), False, k=2, norm=True, scaling=1.0)
    assert (np.asarray(gate)[0] > 0).tolist() == [True, False, True, False, False]
    assert float(margin[0]) == 0.0   # the 2nd and 3rd are level


def _sparse_layer(E=64, D=32, F=16, T=40, seed=9):
    cfg = fam.tiny(dtype=jnp.float32, num_experts=E, num_experts_per_tok=4,
                   hidden_size=D, moe_intermediate_size=F)
    key = iter(jax.random.split(jax.random.PRNGKey(seed), 8))
    p = {"w_router": jax.random.normal(next(key), (D, E)) * 0.5,
         "router_offset": jax.random.normal(next(key), (E,)) * 0.1,
         "w_gate": jax.random.normal(next(key), (E, D, F)) * 0.2,
         "w_up": jax.random.normal(next(key), (E, D, F)) * 0.2,
         "w_down": jax.random.normal(next(key), (E, F, D)) * 0.2}
    h = jax.random.normal(next(key), (T, D))
    real = jnp.arange(T) % 5 != 4                      # every fifth place is padding
    return cfg, p, h, real


def _all_experts(cfg, p, h, real):
    """The same routing, every expert evaluated for every token."""
    idx, g = transformer.route_sigmoid_topk(
        h, p["w_router"], p["router_offset"], cfg.num_experts_per_tok)
    gate = jnp.sum(jax.nn.one_hot(idx, cfg.num_experts) * g[..., None], axis=1)
    act = jax.nn.silu(jnp.einsum("td,edf->tef", h, p["w_gate"])) * jnp.einsum(
        "td,edf->tef", h, p["w_up"])
    out = jnp.einsum("tef,efd,te->td", act, p["w_down"], gate)
    return np.asarray(jnp.where(real[:, None], out, 0.0)), np.asarray(idx)


@pytest.mark.parametrize("kernels", ["xla", "pallas"])
def test_the_routed_layer_is_the_all_expert_evaluation_of_its_routing(kernels):
    cfg, p, h, real = _sparse_layer()
    want, idx = _all_experts(cfg, p, h, real)
    got, counts = fam.sparse_ffn(cfg, p, h, real, kernels=kernels)
    np.testing.assert_allclose(np.asarray(got), want, rtol=0, atol=1e-5 * np.abs(want).max())
    assert np.asarray(got)[~np.asarray(real)].any() == False   # padding routes nowhere
    np.testing.assert_array_equal(
        np.asarray(counts), np.bincount(idx[np.asarray(real)].reshape(-1), minlength=64))
    # the stacked form: the layer addresses its experts inside every layer's
    stack = {k: jnp.stack([jnp.zeros_like(v), v, jnp.ones_like(v)])
             for k, v in p.items() if k in ("w_gate", "w_up", "w_down")}
    stacked, _ = fam.sparse_ffn(cfg, dict(p, **stack), h, real, layer=1,
                                kernels=kernels)
    np.testing.assert_allclose(np.asarray(stacked), np.asarray(got), rtol=0, atol=2e-6)


def test_eight_ranges_of_eight_add_up_to_the_whole_layer():
    """The guide's usual cut: a chip holds some experts of each layer,
    routes over all of them and computes its own part; the parts of the
    ranges that cover the router add up to the uncut layer, which is
    the reference's."""
    cfg, p, h, real = _sparse_layer()
    whole, counts = fam.sparse_ffn(cfg, p, h, real)
    parts, held = [], []
    for lo in range(0, 64, 8):
        part_cfg = dataclasses.replace(cfg, experts_held=(lo, lo + 8))
        share = dict(p, **{k: p[k][lo:lo + 8] for k in ("w_gate", "w_up", "w_down")})
        out, n = fam.sparse_ffn(part_cfg, share, h, real)
        parts.append(np.asarray(out))
        held.append(np.asarray(n))
    np.testing.assert_allclose(sum(parts), np.asarray(whole), rtol=0,
                               atol=1e-5 * np.abs(np.asarray(whole)).max())
    np.testing.assert_array_equal(np.concatenate(held), np.asarray(counts))
    # and the reference's sparse layer, told one range, gives that part
    file_cfg = dict(_file_config(cfg), num_experts=64, num_experts_per_tok=4,
                    experts_held=[8, 16])
    w = {k: v[None] for k, v in dict(
        p, mlp_norm_scale=jnp.ones((h.shape[1],)),
        **{k: p[k][8:16] for k in ("w_gate", "w_up", "w_down")}).items()}
    # the reference norms its input: hand it the layer's input and compare
    # with the program's part on the same normed input
    normed = reference._rmsnorm(h, w["mlp_norm_scale"][0], cfg.norm_eps)
    (ref_out,), _ = reference._sparse_ffn(file_cfg, w, 0, [h], [False], 0)
    part_cfg = dataclasses.replace(cfg, experts_held=(8, 16))
    share = dict(p, **{k: p[k][8:16] for k in ("w_gate", "w_up", "w_down")})
    got, _ = fam.sparse_ffn(part_cfg, share, normed, jnp.ones_like(real))
    np.testing.assert_allclose(np.asarray(ref_out - h), np.asarray(got), rtol=0, atol=1e-5)


def test_the_reference_bounds_its_routings():
    """At most 2^4 routings a judged token: routing 0 is float32's own;
    a routing flips only layers under the file's routing_margin, the
    tightest first; one that names a layer the token lacks is never
    taken (its flip_margin is inf)."""
    margins = np.asarray([[[0.30, 0.01, 0.04, 0.02, 0.5, 0.03, 0.011]],
                          [[0.30, 0.20, 0.04, 0.40, 0.5, 0.60, 0.700]]], np.float32)
    flips, valid = reference.flipped_layers(margins, 0.05)
    assert flips.shape == (2, 1, 16, 7) and not flips[:, :, 0].any()
    assert flips[0, 0, 15].tolist() == [False, True, False, True, False, True, True]
    assert flips[0, 0, 1].tolist() == [False, True] + [False] * 5 and valid[0].all()
    assert valid[1, 0].tolist() == [True, True] + [False] * 14
    assert flips[1, 0, 1].tolist() == [False, False, True] + [False] * 4
    cfg = fam.tiny(dtype=jnp.float32)
    params = fam.init_params(jax.random.PRNGKey(1), cfg)
    tokens = np.random.default_rng(10).integers(0, cfg.vocab_size, (2, 24))
    judge = np.asarray([[23, 11], [5, 17]])
    logits, flip_margin, margin = reference.judged_logits(
        params, _file_config(cfg), tokens, judge)
    assert logits.shape == (2, 2, 16, cfg.vocab_size) and flip_margin.shape == (2, 2, 16)
    full = reference.forward(params, _file_config(cfg), tokens)
    for b in range(2):
        for j in range(2):
            np.testing.assert_allclose(logits[b, j, 0], full[b, judge[b, j]], atol=1e-5)
    assert (flip_margin[:, :, 0] == 0).all() and (margin > 0).all()
    taken = np.isfinite(flip_margin)
    assert (flip_margin[taken] <= 0.05 * 1.5).all()   # a flipped token's own margin moves a little
    one, _, _ = reference.judged_logits(params, _file_config(cfg), tokens, judge,
                                        control_bits=8)
    assert one.shape == (2, 2, 1, cfg.vocab_size)
    assert _rms_share(one[0, 0, 0], full[0, 23]) > 1e-3


# --- (f) what is refused, by name -------------------------------------------


@pytest.mark.parametrize("serving, names", [
    (dict(prefix_caching=True), "prefix_caching"),
    (dict(kv_quant="int8"), "kv_quant"),
    (dict(fused_decode=("rope_kv_write",)), "rope_kv_write"),
    (dict(fused_decode=("sampling",)), "unknown fused_decode entry 'sampling'"),
    (dict(kv_shard="context", context_shards=2), "kv_shard"),
    (dict(kv_layout="dense"), "kv_layout"),
], ids=lambda v: v if isinstance(v, str) else "")
def test_refused_combinations_name_their_reason(tiny, serving, names, tiny_servers):
    cfg, params = tiny
    with pytest.raises((NotImplementedError, ValueError), match=names):
        InferenceEngine(fam, cfg, params, tiny_servers.serving(**serving))


def test_a_model_parallel_mesh_is_refused(tiny, tiny_servers):
    from flexflow_tpu.core.mesh import MachineSpec

    cfg, params = tiny
    mesh = MachineSpec(model=2).make_mesh(jax.devices()[:2])
    with pytest.raises(NotImplementedError, match="model > 1"):
        InferenceEngine(fam, cfg, params, tiny_servers.serving(), mesh)


@pytest.mark.parametrize("draft", ["ssm", "early_exit"])
def test_speculation_is_refused(tiny, draft, tiny_servers):
    from flexflow_tpu.serve import SpecConfig
    from flexflow_tpu.serve.llm import SSM

    cfg, params = tiny
    llm = LLM(fam, cfg, params=params)
    ssms = [SSM(fam, cfg, params=params)] if draft == "ssm" else []
    spec = SpecConfig(draft=draft, draft_layers=1) if draft == "early_exit" else None
    with pytest.raises(NotImplementedError, match="SpecInfer"):
        llm.compile(tiny_servers.serving(), ssms=ssms, spec=spec)


def test_beam_search_is_refused(shared):
    from flexflow_tpu.serve import GenerationConfig

    llm = shared
    with pytest.raises(NotImplementedError, match="conv state"):
        llm.generate([[1, 2, 3]], GenerationConfig(num_beams=2, max_new_tokens=2))


def test_from_hf_reads_the_benchmark_configuration():
    import json

    with open(os.path.join(ROOT, "benchmarks", "configs", "lfm2-24b-a2b.json")) as f:
        hf = json.load(f)
    cfg = fam.from_hf(hf, dtype=jnp.bfloat16)
    assert cfg.num_hidden_layers == 9 and cfg.head_dim == 64
    assert [cfg.count(g) for g in fam.GROUPS] == [7, 2, 1, 8]
    assert (cfg.num_experts, cfg.num_experts_per_tok, cfg.held) == (64, 4, (0, 64))
    assert cfg.tie_word_embeddings and cfg.state_slots == 64 and cfg.rope_theta == 1e6
    # 89.1 M + 2 x 614.6 M + 6 x 620.9 M + 134.2 M (a tied head): 5.18 G
    assert abs(fam.num_params(cfg) / 1e9 - 5.18) < 0.01
    assert transformer.layer_runs(cfg.kinds) == [
        (("conv", "dense"), {"conv": 0, "dense": 0}, 1),
        (("attn", "sparse"), {"attn": 0, "sparse": 0}, 1),
        (("conv", "sparse"), {"conv": 1, "sparse": 1}, 3),
        (("attn", "sparse"), {"attn": 1, "sparse": 4}, 1),
        (("conv", "sparse"), {"conv": 4, "sparse": 5}, 3)]
    # a smaller depth takes the first entries: both mixers, both FFN kinds
    two = fam.from_hf(hf, num_hidden_layers=2)
    assert two.kinds == (("conv", "dense"), ("attn", "sparse"))


# --- the ``ff.*`` scopes are metadata: the same equations in the same order ---


def test_the_scopes_change_no_equation(monkeypatch):
    mod = fam  # conv, attention, dense and routed layers in one step
    cfg = mod.tiny(dtype=jnp.float32)
    params = jax.eval_shape(lambda: mod.init_params(jax.random.PRNGKey(0), cfg))
    cache = jax.eval_shape(lambda: mod.init_paged_kv_cache(
        cfg, 2 * SLOTS, PAGE, jnp.float32, num_slots=SLOTS))

    def jaxpr(pack):
        def step(params, cache, tokens, positions, idx, table):
            return mod.serve_step_paged(
                params, cache, tokens, positions, idx, None, None, table,
                cfg=cfg, cache_len=2 * PAGE, kernels="pallas", pack=pack)

        i32 = jnp.int32
        return jax.make_jaxpr(step)(
            params, cache, jax.ShapeDtypeStruct((SLOTS, CHUNK), i32),
            jax.ShapeDtypeStruct((SLOTS, CHUNK), i32),
            jax.ShapeDtypeStruct((SLOTS,), i32),
            jax.ShapeDtypeStruct((SLOTS, 2), i32))

    def scopes(j):
        return {str(e.source_info.name_stack) for e in j.jaxpr.eqns}

    for pack in (None, 2 * CHUNK):
        with_scopes = jaxpr(pack)
        assert any("ff.glue" in s for s in scopes(with_scopes))
        with monkeypatch.context() as m:
            m.setattr(sublayers, "_named_scope",
                      lambda name: contextlib.nullcontext())
            without = jaxpr(pack)
        assert not any("ff." in s for s in scopes(without))
        assert str(with_scopes) == str(without)
        assert len(with_scopes.jaxpr.eqns) == len(without.jaxpr.eqns)
