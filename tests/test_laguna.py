"""Laguna on the paged serving path (models/laguna.py: window layers
beside full layers with a head count a KIND, a gate a head, two rope
tables, a leading dense layer, sigmoid-routed experts beside a shared
one, two CLASSES of page through models/smallthinker.py's helpers)
against its plain reference (benchmarks/references/laguna.py, the one
copy; imported by path), at a tiny size on the CPU in float32 with the
family's own seeded weights and float32 pools: layers [F, S, S, S, F,
S], the first dense; 6 query heads on a full layer and 8 on a window
layer over 2 K/V heads of 16; a window of 8 lines on pages of 4;
contexts of 47 to 70 lines: six to nine windows long, with the window
class's pages freed on the way and its table rolled.

Tolerance: rms(served - reference) / rms(reference) under 2e-5 a judged
row, what tests/test_smallthinker.py holds (sound float32 reads 5e-7 at
worst here; each changed layer below reads over 2e-3).
"""
import dataclasses
import functools
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.models import laguna as fam
from flexflow_tpu.models import smallthinker, transformer

from family_cases import *  # noqa: F401,F403 (the cases every family answers)
from flexflow_tpu.serve.paging import window_table_pages

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LOGITS_LIMIT = 2e-5
PAGE, CHUNK, SLOTS, MAX_SEQ = 4, 8, 2, 96
# the geometry is the test: a window of 8 lines is two pages of 4, its
# rolling table five, and a context of 45 lines five and a half windows
GEOMETRY = dict(page_size=PAGE, prefill_chunk=CHUNK, max_requests_per_batch=SLOTS,
                max_sequence_length=MAX_SEQ)
# heads by kind, a gate a head, a leading dense layer, a shared expert
FAMILIES = {"laguna": Family(fam, ALWAYS | {"ff.moe.route"})}
ROPES = {
    "full_attention": {"rope_theta": 500000, "rope_type": "yarn", "factor": 4,
                       "original_max_position_embeddings": 16, "beta_slow": 1,
                       "beta_fast": 64, "partial_rotary_factor": 0.5,
                       "attention_factor": 0.1 * np.log(4.0) + 1.0},
    "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                          "partial_rotary_factor": 1}}


def _reference():
    path = os.path.join(ROOT, "benchmarks", "references", "laguna.py")
    spec = importlib.util.spec_from_file_location("reference_laguna", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


reference = _reference()


def _file_config(cfg):
    """The tiny preset as a configuration FILE's keys: what the
    reference reads."""
    return dict(
        num_hidden_layers=cfg.num_hidden_layers, head_dim=cfg.head_dim,
        layer_types=["sliding_attention" if k == fam.WINDOW else "full_attention"
                     for k in cfg.layer_kinds],
        mlp_layer_types=list(cfg.ffn_kinds),
        num_attention_heads_per_layer=[cfg.heads(k) for k in cfg.layer_kinds],
        num_attention_heads=cfg.num_attention_heads,
        num_key_value_heads=cfg.num_key_value_heads,
        sliding_window=cfg.sliding_window, rms_norm_eps=cfg.norm_eps,
        max_position_embeddings=cfg.max_position_embeddings,
        rope_parameters=ROPES, num_experts=cfg.num_experts,
        num_experts_per_tok=cfg.num_experts_per_tok,
        moe_routed_scaling_factor=cfg.routed_scaling_factor,
        tolerance={"routing_margin": 0.05})


@pytest.fixture(scope="module")
def tiny(tiny_servers):
    return tiny_servers.params(fam)


@pytest.fixture
def servers(tiny_servers):
    """kernels -> the file's kept server on ``GEOMETRY``; ``fresh=True``
    or a ``cfg`` of the caller's own for one nobody else sees."""
    return lambda kernels="xla", **kw: tiny_servers(
        fam, **{**GEOMETRY, "kernels": kernels, **kw}).llm


@pytest.fixture(scope="module")
def sequence(tiny):
    """70 tokens and the reference's logits at every position."""
    cfg, params = tiny
    seq = np.random.default_rng(1).integers(0, cfg.vocab_size, 70).tolist()
    return seq, reference.forward(params, _file_config(cfg), np.asarray([seq]))[0]


def _feed(eng, rows, chunk):
    """One ``run_mixed`` step: ``rows`` maps slot -> (tokens, first
    position), pages reserved as the benchmark's probe reserves them.
    Returns the logits (slots, vocab) at each row's last token."""
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


def _walk(eng, seq, slot=1, prefill=45, decode=2, beside=None):
    """Chunked prefill of ``seq`` in ``slot`` (a ragged last chunk),
    then decode steps; ``beside``: (slot, tokens) of a second row that
    prefills from its start from the third step on. Returns {position:
    the logits sampled from there}, the first row's."""
    out, done, other = {}, 0, 0
    while done < prefill + decode:
        n = min(CHUNK, prefill - done) if done < prefill else 1
        rows = {slot: (seq[done:done + n], done)}
        chunk = CHUNK if n > 1 else 1
        if beside is not None and other < len(beside[1]) and done >= 16:
            m = min(CHUNK, len(beside[1]) - other)
            rows[beside[0]] = (beside[1][other:other + m], other)
            other, chunk = other + m, CHUNK
        out[done + n - 1] = _feed(eng, rows, chunk)[slot]
        done += n
    for r in range(eng.num_slots):
        eng.pager.release(r)
    return out


# --- 1. the served path against the reference --------------------------------


@pytest.mark.parametrize("kernels", ["xla", "pallas"])
def test_served_logits_match_the_reference_several_windows_on(
        tiny, servers, sequence, kernels):
    """Chunked prefill to a context of 45 (five and a half windows of 8),
    a second row prefilling beside it, then decode steps: every row the
    server would sample from against the reference's full forward pass,
    with the window class's pages freed on the way and its table
    rolled; the Pallas path calls the kernel at a group of 3 query
    heads a K/V head padded to 8 (full layers) and of 4 padded (window
    layers)."""
    seq, want = sequence
    eng = servers(kernels).engine
    win = eng.pager.classes[fam.WINDOW]
    assert win.pages_per_slot == window_table_pages(8, CHUNK, PAGE) == 5
    before = win.trimmed
    got = _walk(eng, seq, beside=(0, seq[20:42]))
    worst = max(_rms_share(logits, want[pos]) for pos, logits in got.items())
    assert worst < LOGITS_LIMIT, worst
    assert len(got) == 6 + 2 and max(got) == 46
    assert win.trimmed - before >= 8
    eng.pager.check_no_leaks()
    assert eng.pager.used_pages == 0


def test_generate_through_the_request_manager_is_the_references_argmax(
        tiny, servers, sequence):
    """``LLM.generate`` (the scheduler's mixed and decode steps, pages
    freed behind the window, the new decode-context counter): greedy
    tokens are the reference's argmax at every position."""
    cfg, params = tiny
    seq, _ = sequence
    llm = servers("xla")
    prompts = [seq[:65], seq[5:70]]  # with 5 tokens each: the fixture's length
    outs = [o.output_tokens for o in llm.generate(prompts, max_new_tokens=5)]
    for prompt, out in zip(prompts, outs):
        logits = reference.forward(params, _file_config(cfg), np.asarray([prompt + out]))[0]
        assert out == logits[len(prompt) - 1:-1].argmax(-1).tolist()
    stats = llm.rm.stats
    assert stats.window_pages_freed > 0 and stats.moe_experts_held > 0
    # a decode row at position p attends p + 1 lines: the rows of each
    # request decode at positions len(prompt) .. len(prompt) + 3
    assert stats.decode_context_lines == sum(
        sum(range(len(p) + 1, len(p) + 5)) for p in prompts)
    assert stats.decode_tokens == 8


def _one_row_logits(cfg, params, seq):
    """The step program called directly: ``seq`` as ONE row's chunk
    over fresh pools, the logits at every position."""
    pages = -(-len(seq) // PAGE)
    cache = fam.init_paged_kv_cache(cfg, pages, PAGE, jnp.float32,
                                    class_pages={"full": pages, "window": pages})
    table = jnp.arange(pages, dtype=jnp.int32)[None]
    step = jax.jit(functools.partial(
        fam.serve_step_paged, cfg=cfg, cache_len=pages * PAGE, all_logits=True))
    logits, _ = step(params, cache, jnp.asarray([seq], jnp.int32),
                     jnp.arange(len(seq), dtype=jnp.int32)[None],
                     jnp.zeros((1,), jnp.int32), None, None,
                     {"full": table, "window": table})
    return np.asarray(logits[0], np.float32)


@pytest.mark.parametrize("change", [
    None, "window_a_page_longer", "plain_rope_on_the_full_layers",
    "relu_gate", "no_head_norm", "softmax_router", "no_shared_expert"])
def test_the_comparison_fails_on_a_changed_layer(tiny, sequence, monkeypatch, change):
    """What the equations fix, each changed in the program: the logits
    then leave the reference by orders of the limit (None: the program
    as it is, inside it at every position)."""
    cfg, params = tiny
    seq, want = sequence
    if change == "window_a_page_longer":
        cfg = dataclasses.replace(cfg, sliding_window=8 + PAGE)
    elif change == "plain_rope_on_the_full_layers":
        cfg = dataclasses.replace(cfg, full_rope_factor=1.0,
                                  full_rope_attention_factor=1.0)
    elif change == "relu_gate":
        monkeypatch.setattr(fam, "head_gate", jax.nn.relu)
    elif change == "no_head_norm":
        monkeypatch.setattr(fam, "normed_heads", lambda cfg, p, q, k: (q, k))
    elif change == "softmax_router":
        monkeypatch.setattr(fam, "route", lambda cfg, p, h: transformer.route_softmax_topk(
            h, p["w_router"], cfg.num_experts_per_tok))
    elif change == "no_shared_expert":
        monkeypatch.setattr(fam, "shared_expert", lambda cfg, p, h: 0.0 * h)
    got = _one_row_logits(cfg, params, seq)
    worst = max(_rms_share(got[p], want[p]) for p in range(24, len(seq)))
    if change is None:
        assert worst < LOGITS_LIMIT, worst
    else:
        assert worst > 100 * LOGITS_LIMIT, (change, worst)


# --- 2. heads by kind ---------------------------------------------------------


def test_weights_are_shaped_by_kind_and_the_published_count_is_the_cards():
    cfg = fam.config()
    shapes = jax.eval_shape(lambda: fam.init_params(jax.random.PRNGKey(0), cfg))
    for kind, n, H in ((fam.FULL, 10, 48), (fam.WINDOW, 30, 64)):
        assert shapes[kind]["wq"].shape == (n, 2048, H * 128)
        assert shapes[kind]["wo"].shape == (n, H * 128, 2048)
        assert shapes[kind]["wg"].shape == (n, 2048, H)
        assert shapes[kind]["wk"].shape == (n, 2048, 8 * 128)
    assert shapes["dense"]["w_gate"].shape == (1, 2048, 8192)
    assert shapes["sparse"]["w_gate"].shape == (39, 256, 2048, 512)
    assert shapes["sparse"]["shared"]["w_down"].shape == (39, 512, 2048)
    # the sum of ISSUE 58: 33.44 G in all, 3.02 G active ("33.4B-A3B")
    assert fam.num_params(cfg) == pytest.approx(33.44e9, rel=1e-3)
    assert fam.active_params(cfg) == pytest.approx(3.02e9, rel=1e-3)
    # a gate a CHANNEL would be 0.63 G more: not the card's count
    assert fam.num_params(cfg) + 0.63e9 > 1.001 * 33.44e9


@pytest.mark.parametrize("heads", [6, 8])
def test_the_kernel_path_equals_the_xla_path_at_both_groups(heads):
    """``smallthinker.attend_class`` at a group of 6 (padded to 8 and
    cut back) and of 8 query heads a K/V head: the Pallas kernel
    (interpret) against the XLA twin, lines written through the table."""
    rng = np.random.default_rng(heads)
    R, C, KV, d, ps, NP = 2, 4, 1, 16, 4, 4
    H = heads * KV
    q, k, v = (jnp.asarray(rng.standard_normal((R, C, n, d)), jnp.float32)
               for n in (H, KV, KV))
    table = jnp.asarray(rng.permutation(R * NP).reshape(R, NP), jnp.int32)
    first = np.asarray([3, 11])
    positions = jnp.asarray(first[:, None] + np.arange(C)[None], jnp.int32)
    cache = {name: jnp.asarray(rng.standard_normal((1, R * NP + 1, ps, KV * d)), jnp.float32)
             for name in ("k", "v", "k_win", "v_win")}
    tables = {"full": table, "window": table}
    out = {}
    for kernels in ("xla", "pallas"):
        _, _, ctx = smallthinker.step_context(
            cache, positions, positions, tables, window=8, cache_len=NP * ps,
            pack=None, rope=lambda pos: None, kernels=kernels)
        out[kernels] = {
            kind: np.asarray(smallthinker.attend_class(
                fam.tiny(), ctx, kind, cache, 0, q, k, v)[0])
            for kind in (fam.FULL, fam.WINDOW)}
    for kind in (fam.FULL, fam.WINDOW):
        assert out["xla"][kind].shape == (R, C, H * d)
        np.testing.assert_allclose(out["pallas"][kind], out["xla"][kind],
                                   rtol=0, atol=2e-6)
    assert np.abs(out["xla"][fam.FULL] - out["xla"][fam.WINDOW]).max() > 1e-3


# --- 3. the two rope tables ---------------------------------------------------


def test_rope_tables_at_the_published_parameters():
    cfg = fam.config()
    inv = fam.full_inv_freq(cfg)
    plain = 5e5 ** -(np.arange(32) / 32.0)
    assert inv.shape == (32,) and inv[0] == 1.0
    # low = 5, high = 16: plain up to channel 5, over 64 from 16 on
    np.testing.assert_allclose(inv[:6], plain[:6], rtol=1e-12)
    np.testing.assert_allclose(inv[16:], plain[16:] / 64, rtol=1e-12)
    assert inv[6] < plain[6] and inv[15] > plain[15] / 64
    assert inv[31] == pytest.approx(5e5 ** (-62 / 64) / 64, rel=1e-12)
    np.testing.assert_allclose(inv, reference.yarn_inv_freq(64, 5e5, 64, 4096, 64, 1),
                               rtol=1e-12)
    pos = jnp.arange(5000, 5003)
    tables = fam.rope_tables(cfg, pos)
    cos, sin = tables[fam.FULL]
    assert cos.shape == (3, 64)  # 64 of 128 channels rotate, the others pass
    factor = 1.4158883083359672
    assert factor == pytest.approx(0.1 * np.log(64) + 1)
    np.testing.assert_allclose(np.asarray(cos ** 2 + sin ** 2), factor ** 2, rtol=1e-5)
    x = jnp.ones((3, 2, 128))
    roped = transformer.apply_rope(x, cos, sin)
    np.testing.assert_array_equal(np.asarray(roped[..., 64:]), 1.0)
    wcos, wsin = tables[fam.WINDOW]
    assert wcos.shape == (3, 128)
    ang = np.asarray(pos, np.float64)[:, None] * 1e4 ** -(np.arange(64) / 64.0)
    np.testing.assert_allclose(np.asarray(wcos[:, :64]), np.cos(ang), atol=2e-3)
    np.testing.assert_allclose(np.asarray(wcos ** 2 + wsin ** 2), 1.0, rtol=1e-5)


# --- 4. the gate a head, and the window's lines --------------------------------


def test_the_gate_is_a_scalar_a_head(tiny, monkeypatch):
    """Zero ``wg`` (every gate a half) halves the ungated attention
    output; one head's column of ``wg`` moves that head's part alone."""
    cfg, params = tiny
    rng = np.random.default_rng(2)
    T = 8
    x = jnp.asarray(rng.standard_normal((1, T, cfg.hidden_size)), jnp.float32)
    positions = jnp.arange(T, dtype=jnp.int32)[None]
    table = jnp.arange(4, dtype=jnp.int32)[None]
    cache = fam.init_paged_kv_cache(cfg, 4, PAGE, jnp.float32,
                                    class_pages={"full": 4, "window": 4})

    def attended(stack):
        _, _, ctx = smallthinker.step_context(
            cache, positions, positions, {"full": table, "window": table},
            window=cfg.sliding_window, cache_len=16, pack=None, kernels="xla",
            rope=functools.partial(fam.rope_tables, cfg))
        return fam._attn_block(fam.FULL, cfg, ctx, stack, 0, x, cache)[0] - x

    stack = dict(params[fam.FULL])
    d, H = cfg.head_dim, cfg.full_heads
    zero = jnp.zeros_like(stack["wg"])
    gated = jax.jit(attended)
    half = np.asarray(gated(dict(stack, wg=zero)))
    one = np.asarray(gated(dict(stack, wg=zero.at[:, :, 2].set(stack["wg"][:, :, 2] * 50))))
    monkeypatch.setattr(fam, "head_gate", jnp.ones_like)
    whole = np.asarray(jax.jit(lambda s: attended(s))(stack))  # traced anew
    np.testing.assert_allclose(half, 0.5 * whole, rtol=0, atol=1e-6)
    # head 2's gate alone left a half: the difference lies in the span
    # of head 2's rows of wo
    moved = (one - half).reshape(T, -1)
    wo2 = np.asarray(stack["wo"][0]).reshape(H, d, -1)[2]
    coef, *_ = np.linalg.lstsq(wo2.T, moved.T, rcond=None)
    np.testing.assert_allclose(wo2.T @ coef, moved.T, atol=1e-6)
    assert np.abs(moved).max() > 1e-4


def test_the_window_mask_sees_its_lines_its_own_among_them():
    positions = jnp.asarray([[1000]], jnp.int32)
    start = jnp.asarray([384], jnp.int32)
    mask = np.asarray(smallthinker._window_mask(positions, start, 768, 512, 2000))[0, 0]
    seen = np.flatnonzero(mask) + 384
    assert len(seen) == 512 and seen[0] == 1000 - 511 and seen[-1] == 1000


# --- 5. the share ties to the model -------------------------------------------


@pytest.mark.parametrize("kernels", ["xla", "pallas"])
def test_four_quarters_of_the_experts_add_up_to_the_references_block(tiny, kernels):
    """The guide's test that ties a share to the model: the parts that
    ``experts_held`` ranges (0, 4) ... (12, 16) give, the shared expert
    counted once, add up to the uncut reference's whole sparse block,
    with the 2.5."""
    cfg, params = tiny
    rng = np.random.default_rng(5)
    T, D = 24, cfg.hidden_size
    # small beside the block's output: ``want - x`` rounds at x's size
    x = jnp.asarray(0.01 * rng.standard_normal((T, D)), jnp.float32)
    w = params["sparse"]
    with jax.default_matmul_precision("highest"):
        want, _ = reference._sparse_ffn(_file_config(cfg), w, 1, x, False, 0)
    want = np.asarray(want - x)
    h = transformer._norm(cfg, x, w["mlp_norm_scale"][1], None)
    real = jnp.ones((T,), bool)
    total, counts = 0.0, []
    for lo in range(0, 16, 4):
        part = dataclasses.replace(cfg, experts_held=(lo, lo + 4))
        p = {k: v[1] for k, v in w.items() if k != "shared"}
        p.update({k: p[k][lo:lo + 4] for k in ("w_gate", "w_up", "w_down")})
        out, n = fam.sparse_ffn(part, p, h, real, kernels=kernels)  # no shared
        total, counts = total + np.asarray(out), counts + [np.asarray(n)]
    shared = np.asarray(fam.shared_expert(
        cfg, {k: v[1] for k, v in w["shared"].items()}, h))
    np.testing.assert_allclose(total + shared, want, rtol=0,
                               atol=2e-5 * np.abs(want).max())
    assert int(np.concatenate(counts).sum()) == T * cfg.num_experts_per_tok
    assert cfg.routed_scaling_factor == 2.5 and np.abs(shared).max() > 1e-5


# --- 6. from_hf and what is refused -------------------------------------------


def _catalog(name):
    """The catalog row's ``config``, copied here (Laguna-XS.2 and
    Laguna-S-2.1, config.json as published)."""
    xs = name == "Laguna-XS.2"
    n = 40 if xs else 48
    full = {"rope_theta": 500000, "rope_type": "yarn", "factor": 64 if xs else 128,
            "original_max_position_embeddings": 4096 if xs else 8192,
            "beta_slow": 1, "beta_fast": 64 if xs else 32,
            "attention_factor": 1.4158883083359672 if xs else 1.4852030263919618,
            "partial_rotary_factor": 0.5}
    cfg = {
        "model_type": "laguna", "vocab_size": 100352,
        "hidden_size": 2048 if xs else 3072,
        "intermediate_size": 8192 if xs else 12288, "num_hidden_layers": n,
        "num_attention_heads": 48, "num_key_value_heads": 8, "head_dim": 128,
        "max_position_embeddings": 262144 if xs else 1048576,
        "attention_bias": False, "rms_norm_eps": 1e-06, "num_experts": 256,
        "num_experts_per_tok": 8 if xs else 10,
        "moe_intermediate_size": 512 if xs else 1024,
        "shared_expert_intermediate_size": 512 if xs else 1024,
        "tie_word_embeddings": False, "gating": True if xs else "per-head",
        "sliding_window": 512,
        "rope_parameters": {
            "full_attention": full,
            "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                                  "partial_rotary_factor": 1}},
        "layer_types": ["full_attention", "sliding_attention",
                        "sliding_attention", "sliding_attention"] * (n // 4),
        "moe_apply_router_weight_on_input": False,
        "mlp_layer_types": ["dense"] + ["sparse"] * (n - 1),
        "moe_routed_scaling_factor": 2.5,
        "num_attention_heads_per_layer": [48, 64 if xs else 72, 64 if xs else 72,
                                          64 if xs else 72] * (n // 4)}
    if xs:
        cfg["partial_rotary_factor"] = 0.5
        cfg["rope_parameters"]["original_max_position_embeddings"] = 4096
    else:
        cfg.update(norm_topk_prob=True, decoder_sparse_step=1, mlp_only_layers=[0],
                   gating_types=["per_head"] * n, moe_router_logit_softcapping=0)
    return cfg


def test_from_hf_reads_the_catalogs_rows_verbatim():
    cfg = fam.from_hf(_catalog("Laguna-XS.2"))
    assert cfg == fam.config()
    assert (cfg.full_heads, cfg.window_heads, cfg.sliding_window) == (48, 64, 512)
    assert cfg.kinds[:5] == (("full", "dense"), ("window", "sparse"),
                             ("window", "sparse"), ("window", "sparse"),
                             ("full", "sparse"))
    assert len(transformer.layer_runs(cfg.kinds)) == 20
    big = fam.from_hf(_catalog("Laguna-S-2.1"))
    assert (big.full_heads, big.window_heads, big.num_experts_per_tok,
            big.moe_intermediate_size, big.hidden_size) == (48, 72, 10, 1024, 3072)
    assert (big.full_rope_factor, big.full_rope_original_max,
            big.full_rope_beta_fast) == (128.0, 8192, 32.0)
    assert big.full_rope_attention_factor == pytest.approx(0.1 * np.log(128) + 1)
    with open(os.path.join(ROOT, "benchmarks", "configs", "laguna-xs.2.json")) as f:
        file = json.load(f)
    cut = fam.from_hf(file, dtype=jnp.bfloat16)
    assert cut == fam.config(num_hidden_layers=5, dtype=jnp.bfloat16)
    assert cut.layer_kinds == ("full", "window", "window", "window", "full")
    assert fam.num_params(cut) == 3_869_835_264 + 11 * 2048 + 10 * 128 + 4 * 256


@pytest.mark.parametrize("change, names", [
    (dict(gating=False), "gating"), (dict(gating="per-channel"), "gating"),
    (dict(moe_apply_router_weight_on_input=True), "moe_apply_router_weight_on_input"),
    (dict(moe_router_logit_softcapping=30.0), "moe_router_logit_softcapping"),
    (dict(attention_bias=True), "attention_bias"),
    (dict(mlp_layer_types=["sparse", "dense"] + ["sparse"] * 38), "mlp_layer_types"),
    (dict(num_attention_heads_per_layer=[48, 64, 56, 64] * 10), "query heads"),
    ("llama3", "rope_type"),
])
def test_from_hf_refuses_what_is_not_built(change, names):
    hf = _catalog("Laguna-XS.2")
    if change == "llama3":
        hf["rope_parameters"]["full_attention"]["rope_type"] = "llama3"
    else:
        hf.update(change)
    with pytest.raises(NotImplementedError, match=names):
        fam.from_hf(hf)


@pytest.mark.parametrize("serving, names", [
    (dict(kv_layout="dense"), "kv_layout"),
    (dict(prefix_caching=True), "prefix_caching"),
    (dict(kv_quant="int8"), "kv_quant"),
    (dict(kv_shard="context", context_shards=2), "kv_shard"),
])
def test_refused_combinations_name_the_family_and_their_reason(tiny, serving, names, servers):
    with pytest.raises(NotImplementedError, match=f"laguna does not serve {names}"):
        servers(fresh=True, **serving)


def test_the_fused_prologue_is_refused(tiny, servers):
    with pytest.raises(ValueError, match="FUSED_DECODE"):
        servers("pallas", fresh=True, fused_decode=("rope_kv_write",))


# --- the reference's own arms --------------------------------------------------


def test_the_references_controls_are_other_models(tiny, sequence):
    """The benchmark's controls: ``window=False`` (window layers attend
    the whole context) and ``yarn=False`` (plain rope on the full
    layers) leave the reference by orders of the limit past the first
    window, and the int8 control too."""
    cfg, params = tiny
    seq, want = sequence
    file = _file_config(cfg)
    tokens = np.asarray([seq])
    whole = reference.forward(params, file, tokens, window=False)[0]
    assert _rms_share(whole[6], want[6]) < LOGITS_LIMIT
    assert min(_rms_share(whole[p], want[p]) for p in range(24, 70)) > 100 * LOGITS_LIMIT
    plain = reference.forward(params, file, tokens, yarn=False)[0]
    assert min(_rms_share(plain[p], want[p]) for p in range(24, 70)) > 100 * LOGITS_LIMIT


def test_the_reference_judges_rows_with_bounded_routings(tiny, sequence):
    """``judged_logits`` in the probe's shapes: routing 0 is the full
    forward pass's row; every routing's flip_margin is 0, a margin it
    overruled, or inf (never taken)."""
    cfg, params = tiny
    seq, want = sequence
    tokens = np.asarray([seq])
    judge = np.asarray([[40, 55, 69]])
    logits, flip_margin, margin = reference.judged_logits(
        params, _file_config(cfg), tokens, judge)
    assert logits.shape == (1, 3, 16, cfg.vocab_size) and margin.shape == (1, 3)
    np.testing.assert_allclose(logits[0, :, 0], want[[40, 55, 69]], rtol=0, atol=1e-5)
    assert (flip_margin[:, :, 0] == 0).all()
    taken = np.isfinite(flip_margin)
    assert (flip_margin[taken] < 0.05).all()
    flipped = taken & (flip_margin > 0)
    assert flipped.any()
    j, r = np.argwhere(flipped[0])[0]
    assert _rms_share(logits[0, j, r], logits[0, j, 0]) > LOGITS_LIMIT
    control = reference.judged_logits(params, _file_config(cfg), tokens, judge,
                                      control_bits=8)[0]
    assert control.shape == (1, 3, 1, cfg.vocab_size)
    assert _rms_share(control[0, 0, 0], want[40]) > 100 * LOGITS_LIMIT
