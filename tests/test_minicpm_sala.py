"""MiniCPM-SALA on the paged serving path (models/minicpm_sala.py)
against its plain reference (benchmarks/references/minicpm_sala.py, the
one copy; imported by path), at a tiny size on the CPU in float32 with
seeded weights. The tiny preset cuts the sparse sizes so that 200
tokens cross ``dense_len`` (96): block 16, kernel 8, stride 4, six
blocks chosen, a window of 32.

Tolerances, each with its reason. LOGITS: rms(served - reference) /
rms(reference) under 2e-4 a judged row. Sound float32 reads 2.4e-5 at
worst (another order of the same sums); with the block choice left out
(every block attended above ``dense_len``) the worst of the same rows
reads 3.4e-3, with the decay left out 0.12, with the state rounded to
bfloat16 after every step 1.1e-3 (my CPU readings, PR 29), so each of
the three fails by a factor of five or more. TOKENS: greedy tokens through ``RequestManager`` equal a fresh
server's exactly (same program, same arithmetic).
"""
import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.models import minicpm_sala as fam
from flexflow_tpu.serve.engine import InferenceEngine
from flexflow_tpu.serve.llm import LLM

# the cases every family answers, less the trim: no packed step here
from family_cases import (  # noqa: F401
    ALWAYS, Family, family_server, pytest_generate_tests, step_texts,
    test_every_working_operation_has_a_sublayer)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LOGITS_LIMIT = 2e-4
CHUNK = 16                       # the tiny serving configuration's (conftest.py)
FAMILIES = {"minicpm_sala": Family(
    fam, ALWAYS | {"ff.mixer", "ff.attn.select"},
    serving=dict(max_sequence_length=256))}


def _reference():
    path = os.path.join(ROOT, "benchmarks", "references", "minicpm_sala.py")
    spec = importlib.util.spec_from_file_location("reference_minicpm_sala", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


reference = _reference()


def _file_config(cfg):
    """The tiny preset as a configuration FILE's keys: what the
    reference reads."""
    return dict(
        num_hidden_layers=cfg.num_hidden_layers, mixer_types=list(cfg.mixer_types),
        rms_norm_eps=cfg.norm_eps, scale_depth=cfg.scale_depth,
        scale_depth_layers=cfg.scale_depth_layers, scale_emb=cfg.scale_emb,
        hidden_size=cfg.hidden_size, dim_model_base=cfg.dim_model_base,
        lightning_nh=cfg.lightning_heads, rope_theta=cfg.rope_theta,
        num_attention_heads=cfg.num_attention_heads,
        num_key_value_heads=cfg.num_key_value_heads,
        sparse_config=dict(
            kernel_size=cfg.sparse_kernel, kernel_stride=cfg.sparse_stride,
            block_size=cfg.sparse_block, topk=cfg.sparse_topk,
            window_size=cfg.sparse_window, init_blocks=cfg.sparse_init_blocks,
            dense_len=cfg.dense_len))


@pytest.fixture(scope="module")
def tiny(tiny_servers):
    return tiny_servers.params(fam)


@pytest.fixture
def served(tiny_servers):
    """kernels -> the file's kept server, 256 positions (two rows past
    ``dense_len``); ``fresh=True`` for a test that reads whole states."""
    return lambda kernels="xla", **kw: tiny_servers(
        fam, **{**FAMILIES["minicpm_sala"].serving, "kernels": kernels, **kw})


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
def test_served_logits_match_the_reference(tiny, kernels, served):
    """Chunked prefill of two rows below and above ``dense_len``, mixed
    steps in which row 0 decodes while row 2 prefills, then pure decode
    steps: every row the server would sample from, against the
    reference's full forward pass; and the block choice of each row's
    last position is the reference's."""
    cfg, params = tiny
    eng = served(kernels).engine
    rng = np.random.default_rng(1)
    seqs = {r: rng.integers(0, cfg.vocab_size, 200).tolist() for r in (0, 2)}
    judged = {}
    done = {0: 0, 2: 0}

    def step(chunk, feed):
        rows = {r: (seqs[r][done[r]:done[r] + n], done[r]) for r, n in feed.items()}
        logits = _feed(eng, rows, chunk)
        for r, n in feed.items():
            done[r] += n
            judged[(r, done[r] - 1)] = logits[r]

    while done[0] < 150:                       # row 0 prefills alone
        step(CHUNK, {0: min(CHUNK, 150 - done[0])})
    while done[2] < 170:                       # row 0 decodes, row 2 prefills
        step(CHUNK, {0: 1, 2: min(CHUNK, 170 - done[2])})
    for _ in range(4):                         # both decode
        step(1, {0: 1, 2: 1})
    assert max(done.values()) > cfg.dense_len + 64

    tokens = np.zeros((2, 200), np.int64)
    tokens[0], tokens[1] = seqs[0], seqs[2]
    hidden, chosen = reference.forward(params, _file_config(cfg), tokens)
    want = np.asarray(hidden @ params["lm_head"].astype(jnp.float32))
    worst = max(_rms_share(got, want[r // 2, t]) for (r, t), got in judged.items())
    assert len(judged) > 25 and worst < LOGITS_LIMIT, worst
    choice = np.asarray(eng.cache["chosen"])
    _release(eng)
    for layer in range(cfg.count(fam.SPARSE)):
        for r in (0, 2):
            ref = np.asarray(chosen[layer])[r // 2, done[r] - 1]
            assert ref.sum(-1).tolist() == [cfg.sparse_topk] * cfg.num_key_value_heads
            assert (choice[layer, r][:, :ref.shape[-1]] == ref).all()


def _greedy_reference(cfg, params, prompt, n):
    toks = list(prompt)
    for _ in range(n):
        hidden, _ = reference.forward(params, _file_config(cfg), np.array([toks]))
        logits = hidden[0, -1] @ params["lm_head"].astype(jnp.float32)
        toks.append(int(jnp.argmax(logits)))
    return toks[len(prompt):]


def test_request_manager_serves_the_reference_greedy_tokens(tiny, served):
    """Through ``LLM.generate`` (submit/step, chunked prefill, the mixed
    and the decode step programs): the tokens are the reference's own
    greedy continuation of a prompt that crosses ``dense_len``."""
    cfg, params = tiny
    llm = served().llm
    before = dataclasses.replace(llm.rm.stats)
    prompt = np.random.default_rng(2).integers(0, cfg.vocab_size, 120).tolist()
    out = llm.generate([prompt], max_new_tokens=5)[0]
    assert out.output_tokens == _greedy_reference(cfg, params, prompt, 5)
    stats = llm.rm.stats
    assert stats.state_resets == before.state_resets + 1
    assert stats.sparse_rows > before.sparse_rows
    assert stats.real_rows >= stats.sparse_rows
    assert stats.slot_state_bytes == llm.engine.slot_state_bytes() > 0
    assert llm.engine.kv_cache_bytes() > llm.engine.slot_state_bytes()
    assert llm.engine.kv_allocated_bytes() >= llm.engine.slot_state_bytes()


# --- (b) the chunked lightning form against the plain recurrence ------------


@pytest.mark.parametrize("chunk", [1, 16, 7])
def test_chunked_lightning_is_the_recurrence(chunk):
    """37 positions in chunks of 1 (the recurrence branch), 16 and 7 (a
    ragged last chunk, padded), two rows of which the second starts 5
    positions later: outputs and final state against float64."""
    H, d, T = 4, 8, 37
    rng = np.random.default_rng(3)
    q, k, v = (rng.standard_normal((2, T, H, d)).astype(np.float32) for _ in range(3))
    lam = np.exp(-np.asarray(fam.lightning_slopes(H), np.float64))
    want_o = np.zeros((2, T, H, d))
    S = np.zeros((2, H, d, d))
    for t in range(T):
        S = lam[None, :, None, None] * S + np.einsum("rhd,rhe->rhde", k[:, t], v[:, t])
        want_o[:, t] = np.einsum("rhd,rhde->rhe", q[:, t], S)
    state = jnp.full((2, H, d, d), 7.0, jnp.float32)   # stale: position 0 resets it
    got_o = np.zeros_like(want_o)
    for lo in range(0, T, chunk):
        n = min(chunk, T - lo)
        pad = ((0, 0), (0, chunk - n), (0, 0), (0, 0))
        real = jnp.arange(chunk)[None, :] < n
        o, state = fam.lightning_attend(
            *(jnp.pad(a[:, lo:lo + n], pad) for a in (q, k, v)), state,
            jnp.broadcast_to(real, (2, chunk)), jnp.full((2,), lo == 0))
        got_o[:, lo:lo + n] = np.asarray(o)[:, :n]
    # float32 sums in another order: 1e-5 of the largest value
    assert np.abs(got_o - want_o).max() < 1e-5 * np.abs(want_o).max()
    assert np.abs(np.asarray(state) - S).max() < 1e-5 * np.abs(S).max()


# --- (c) slot reuse and recompute preemption ---------------------------------


def test_a_reused_slot_starts_from_zero_state(tiny, served):
    """One slot, two requests one after the other: the second's tokens
    and final state are those of a fresh server that saw only it."""
    cfg, _ = tiny
    rng = np.random.default_rng(4)
    first, second = (rng.integers(0, cfg.vocab_size, n).tolist() for n in (130, 110))
    used = served(fresh=True, max_requests_per_batch=1).llm
    used.generate([first], max_new_tokens=4)
    again = used.generate([second], max_new_tokens=6)[0].output_tokens
    fresh = served(fresh=True, max_requests_per_batch=1).llm
    assert again == fresh.generate([second], max_new_tokens=6)[0].output_tokens
    assert used.rm.stats.state_resets == 2
    np.testing.assert_array_equal(np.asarray(used.engine.cache["state"]),
                                  np.asarray(fresh.engine.cache["state"]))


def test_a_preempted_request_recomputes_to_the_same_tokens(tiny, served):
    """An oversubscribed pool preempts and re-admits (recompute from
    position 0, which resets the state): no output changes."""
    cfg, _ = tiny
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab_size, 40 + 8 * i).tolist() for i in range(4)]
    want = [served().llm.generate([p], max_new_tokens=8)[0].output_tokens
            for p in prompts]
    tight = served(fresh=True, max_sequence_length=96, max_cached_tokens=128).llm
    outs = tight.generate(prompts, max_new_tokens=8)
    assert [o.output_tokens for o in outs] == want
    assert tight.rm.stats.preemptions > 0, "the pool was never oversubscribed"
    assert tight.rm.stats.state_resets > len(prompts)
    tight.engine.pager.check_no_leaks()


# --- (d) padding leaves the state alone --------------------------------------


def _slot_state(eng, row):
    return {name: np.asarray(eng.cache[name])[:, row] for name in ("state", "kbar")}


@pytest.mark.parametrize("chunk", [CHUNK, 1])
def test_a_padded_row_keeps_its_state_bitwise(tiny, chunk, served):
    cfg, _ = tiny
    eng = served().engine
    rng = np.random.default_rng(6)
    _feed(eng, {1: (rng.integers(0, cfg.vocab_size, CHUNK).tolist(), 0)}, CHUNK)
    before = _slot_state(eng, 1)
    assert np.abs(before["state"]).max() > 0 and np.abs(before["kbar"]).max() > 0
    _feed(eng, {0: (rng.integers(0, cfg.vocab_size, chunk).tolist(), 0)}, chunk)
    after = _slot_state(eng, 1)
    _release(eng)
    for name in before:
        np.testing.assert_array_equal(before[name], after[name])


def test_a_decoding_row_in_a_mixed_step_updates_as_the_decode_step_does(tiny, served):
    """One real position and fifteen padded ones in the C=16 step leave
    what the C=1 step leaves: the compressed keys bitwise, the state to
    one float32 rounding (the chunked form's one product and the
    recurrence's are fused differently by the compiler)."""
    cfg, _ = tiny
    rng = np.random.default_rng(7)
    prompt = rng.integers(0, cfg.vocab_size, 2 * CHUNK - 1).tolist()
    token = [int(rng.integers(0, cfg.vocab_size))]
    states = []
    for chunk in (CHUNK, 1):
        # the slot's arrays are compared whole, and in the pool's default
        # bfloat16: a float32 pool keeps the one rounding the two forms of
        # the compressed keys' sum differ by
        eng = served(fresh=True, cache_dtype=jnp.bfloat16).engine
        _feed(eng, {0: (prompt[:CHUNK], 0)}, CHUNK)
        _feed(eng, {0: (prompt[CHUNK:], CHUNK)}, CHUNK)
        _feed(eng, {0: (token, len(prompt))}, chunk)   # completes a compressed key
        states.append(_slot_state(eng, 0))
    np.testing.assert_array_equal(states[0]["kbar"], states[1]["kbar"])
    np.testing.assert_allclose(states[0]["state"], states[1]["state"], rtol=0,
                               atol=1e-6 * np.abs(states[1]["state"]).max())


# --- (e) what is refused, by name -------------------------------------------


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


def test_beam_search_is_refused(tiny, served):
    from flexflow_tpu.serve import GenerationConfig

    llm = served().llm
    with pytest.raises(NotImplementedError, match="recurrent state"):
        llm.generate([[1, 2, 3]], GenerationConfig(num_beams=2, max_new_tokens=2))


def test_from_hf_reads_the_benchmark_configuration():
    import json

    with open(os.path.join(ROOT, "benchmarks", "configs", "minicpm-sala.json")) as f:
        hf = json.load(f)
    cfg = fam.from_hf(hf, dtype=jnp.bfloat16)
    assert cfg.num_hidden_layers == 12 and cfg.count(fam.SPARSE) == 3
    assert (cfg.dense_len, cfg.sparse_topk, cfg.sparse_block) == (8192, 64, 64)
    assert cfg.scale_depth_layers == 32 and cfg.state_slots == 4
    # 9 x 285.2 M + 3 x 253.7 M + 601.7 M: the issue's 3.93 G
    assert abs(fam.num_params(cfg) / 1e9 - 3.93) < 0.01
    # a smaller depth takes the first entries: one of each kind
    two = fam.from_hf(hf, num_hidden_layers=2)
    assert two.mixer_types == (fam.SPARSE, fam.LIGHTNING)
    assert fam._runs(cfg.mixer_types) == [
        (fam.SPARSE, 0, 1), (fam.LIGHTNING, 0, 6), (fam.SPARSE, 1, 2),
        (fam.LIGHTNING, 6, 3)]
