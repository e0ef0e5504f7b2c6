"""SmallThinker on the paged serving path (models/smallthinker.py: full
layers without positions beside window layers with rope, two CLASSES of
page, a router that reads the layer's input, ReGLU experts) against its
plain reference (benchmarks/references/smallthinker.py, the one copy;
imported by path), at a tiny size on the CPU in float32 with the
family's own seeded weights and float32 pools: two periods of one full
and three window layers, a window of 24 lines (three pages of 8),
contexts of 150 lines: six windows long, with the window class's pages
freed on the way.

Tolerances, each with its reason. LOGITS: rms(served - reference) /
rms(reference) under 2e-5 a judged row. Sound float32 reads 4e-7 at
worst (another order of the same sums); with the window a page longer
the same rows read 2e-2, with rope on the full layers 0.3, with the
router behind a norm 0.5, with silu for relu 0.2 (my CPU readings,
PR 50), so each fails by orders. BITWISE where the arithmetic is the
same and only the pages differ: freeing nothing, a fresh server.
"""
import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.models import smallthinker as fam
from flexflow_tpu.models import transformer

from family_cases import *  # noqa: F401,F403 (the cases every family answers)
from flexflow_tpu.serve.paging import window_table_pages

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LOGITS_LIMIT = 2e-5
PAGE, CHUNK, SLOTS, MAX_SEQ = 8, 8, 4, 160
# the geometry is the test: a window of 24 lines is three pages of 8, its
# rolling table five, and a context of 140 lines five and a half windows
GEOMETRY = dict(page_size=PAGE, prefill_chunk=CHUNK, max_sequence_length=MAX_SEQ)
# full and window layers alike, the router at the top of the block
FAMILIES = {"smallthinker": Family(fam, ALWAYS | {"ff.moe.route"})}


def _reference():
    path = os.path.join(ROOT, "benchmarks", "references", "smallthinker.py")
    spec = importlib.util.spec_from_file_location("reference_smallthinker", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


reference = _reference()


def _file_config(cfg):
    """The tiny preset as a configuration FILE's keys: what the
    reference reads."""
    layout = [int(kind == fam.WINDOW) for kind in cfg.layer_kinds]
    return dict(
        num_hidden_layers=cfg.num_hidden_layers, sliding_window_layout=layout,
        rope_layout=layout, sliding_window_size=cfg.sliding_window,
        rms_norm_eps=cfg.norm_eps, rope_theta=cfg.rope_theta,
        num_attention_heads=cfg.num_attention_heads,
        num_key_value_heads=cfg.num_key_value_heads,
        moe_num_primary_experts=cfg.num_experts,
        moe_num_active_primary_experts=cfg.num_experts_per_tok,
        tolerance={"routing_margin": 0.05})


@pytest.fixture(scope="module")
def tiny(tiny_servers):
    return tiny_servers.params(fam)


@pytest.fixture
def served(tiny_servers):
    """kernels -> the file's kept freeing server on ``GEOMETRY`` (the
    Pallas one in interpret mode); ``fresh=True`` or a ``cfg`` of the
    caller's own for one nobody else sees."""
    return lambda kernels="xla", **kw: tiny_servers(
        fam, **{**GEOMETRY, "kernels": kernels, **kw})


@pytest.fixture
def shared(served):
    return served().llm


@pytest.fixture(scope="module")
def sequence(tiny):
    """150 tokens and the reference's logits at every position."""
    cfg, params = tiny
    seq = np.random.default_rng(1).integers(0, cfg.vocab_size, 150).tolist()
    return seq, reference.forward(params, _file_config(cfg), np.asarray([seq]))[0]


def _keep_every_page(monkeypatch):
    """The control: the family declares its window class with no
    window, so the allocator keeps every page and a whole context's
    table, ``tables()`` hands no ``window_start``, and the mask (from
    ``cfg.sliding_window``) alone hides the lines behind the window.
    For engines built while the patch holds."""
    declared = fam.page_classes
    monkeypatch.setattr(fam, "page_classes", lambda cfg: {
        name: (pools, None) for name, (pools, _) in declared(cfg).items()})


def _release(eng):
    for r in range(eng.num_slots):
        eng.pager.release(r)


def _feed(eng, rows, chunk):
    """One ``run_mixed`` step: ``rows`` maps slot -> (tokens, first
    position). Pages are reserved as the benchmark's probe reserves
    them: ``pager.ensure(slot, lines)`` and nothing else. Returns the
    logits (slots, vocab) at each row's last token."""
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


def _walk(eng, seq, slot=1, prefill=134, decode=6, beside=None):
    """Chunked prefill of ``seq`` in ``slot`` (a ragged last chunk),
    then decode steps; ``beside``: (slot, tokens) of a second row that
    prefills from its start while the first is on its way (mixed steps
    in which the rows are at different places of their windows).
    Returns {position: the logits sampled from there}, the first row's."""
    out, done, other = {}, 0, 0
    while done < prefill + decode:
        n = min(CHUNK, prefill - done) if done < prefill else 1
        rows = {slot: (seq[done:done + n], done)}
        chunk = CHUNK if n > 1 else 1
        if beside is not None and other < len(beside[1]) and done >= 40:
            m = min(CHUNK, len(beside[1]) - other)
            rows[beside[0]] = (beside[1][other:other + m], other)
            other, chunk = other + m, CHUNK
        logits = _feed(eng, rows, chunk)
        done += n
        out[done - 1] = logits[slot]
    return out


# --- (a) the served path against the reference ------------------------------


@pytest.mark.parametrize("kernels", ["xla", "pallas"])
def test_served_logits_match_the_reference_several_windows_on(
        tiny, served, sequence, kernels):
    """Chunked prefill to a context of 134 (five and a half windows of
    24), a second row prefilling beside it from step 5 on, then decode
    steps: every row the server would sample from against the
    reference's full forward pass, with the window class's pages freed
    on the way and its table rolled: the slot never holds more than its
    table's 5 pages there, and holds ceil(L / 8) in the full class."""
    cfg, _ = tiny
    seq, want = sequence
    eng = served(kernels).engine
    win, full = eng.pager.classes[fam.WINDOW], eng.pager.classes[fam.FULL]
    assert win.pages_per_slot == window_table_pages(24, CHUNK, PAGE) == 5
    before = win.trimmed
    got = _walk(eng, seq, beside=(3, seq[70:131]))
    worst = max(_rms_share(logits, want[pos]) for pos, logits in got.items())
    assert worst < LOGITS_LIMIT, worst
    assert len(got) == 17 + 6 and max(got) == 139
    assert win.trimmed - before >= 13 and win.first_page[1] == 13
    assert win.slot_pages(1) <= 5 and full.slot_pages(1) == -(-140 // PAGE)
    eng.pager.check_no_leaks()
    _release(eng)
    assert eng.pager.used_pages == 0


@pytest.mark.parametrize("kernels", ["pallas", "xla"])
def test_freeing_nothing_gives_the_same_logits(
        tiny, served, sequence, kernels, monkeypatch):
    """The window class told to free nothing (``_keep_every_page``):
    it keeps every page and a whole context's table, and the mask alone
    hides the lines behind the window. The kernel walks a row's pages in order and skips those
    no query sees, so a query meets the same keys in the same order
    and the logits are the freeing server's TO THE BIT. The XLA twin
    sums a softmax over 168 gathered lines where the other sums over
    40: another order of the same sum, under the limit."""
    seq, _ = sequence
    freeing = served(kernels).engine  # built (or kept) with its window declared
    _keep_every_page(monkeypatch)
    kept = served(kernels, fresh=True).engine
    assert kept.pager.classes[fam.WINDOW].window is None
    assert "window_start" not in kept.pager.tables()
    a = _walk(freeing, seq, beside=(3, seq[70:131]))
    b = _walk(kept, seq, beside=(3, seq[70:131]))
    for pos in a:
        if kernels == "pallas":
            np.testing.assert_array_equal(a[pos], b[pos])
        else:
            assert _rms_share(a[pos], b[pos]) < LOGITS_LIMIT
    assert kept.pager.classes[fam.WINDOW].slot_pages(1) == -(-140 // PAGE)
    _release(freeing)


def test_a_slot_given_back_and_prefilled_again_agrees(tiny, shared, sequence):
    """What a preempted request does: its pages of BOTH classes go back
    (the window table's start with them), it prefills again from its
    start in another order of chunks, and a row judged after that reads
    as the first time, against the reference too."""
    seq, want = sequence
    eng = shared.engine
    first = _walk(eng, seq, prefill=100, decode=2)
    assert eng.pager.classes[fam.WINDOW].first_page[1] > 0
    eng.pager.release(1)
    assert eng.pager.used_pages == 0
    assert eng.pager.classes[fam.WINDOW].first_page[1] == 0
    again = _walk(eng, seq, prefill=101, decode=1)
    for pos in (100, 101):
        assert _rms_share(again[pos], want[pos]) < LOGITS_LIMIT
        assert _rms_share(again[pos], first[pos]) < LOGITS_LIMIT
    _release(eng)


def _greedy(tiny, llm, prompts, new):
    return [o.output_tokens for o in llm.generate(prompts, max_new_tokens=new)]


def test_preempted_requests_recompute_to_the_same_tokens(tiny, sequence, served):
    """Through ``RequestManager``: a pool too small for three long
    requests at once (the full class runs out: the window class never
    does) preempts the newest, which prefills again from its start; the
    greedy tokens are those of a server with room for all, and the
    reference's argmax at every position. The counters say what
    happened: pages were freed behind the window, the window class
    never held more than its tables, both classes are empty at the end."""
    cfg, params = tiny
    seq, _ = sequence
    prompts = [seq[:90], seq[20:120], seq[40:125]]
    roomy = served().llm
    want = _greedy(tiny, roomy, prompts, 6)
    assert roomy.rm.stats.preemptions == 0
    tight = served(fresh=True, max_cached_tokens=(4 * 5 + 30) * PAGE).llm
    assert tight.engine.pager.classes[fam.FULL].num_pages == 30
    got = _greedy(tiny, tight, prompts, 6)
    assert got == want
    stats = tight.rm.stats
    assert stats.preemptions > 0 and stats.window_pages_freed > 0
    assert stats.pages_live_peak[fam.WINDOW] <= 3 * 5
    assert stats.window_pages_unfreed_peak > stats.pages_live_peak[fam.WINDOW]
    assert tight.engine.pager.used_pages == 0
    tight.engine.pager.check_no_leaks()
    for prompt, out in zip(prompts, want):
        tokens = np.asarray([prompt + out])
        logits = reference.forward(params, _file_config(cfg), tokens)[0]
        assert out == logits[len(prompt) - 1:-1].argmax(-1).tolist()


# --- (b) what the comparison holds the family to ----------------------------


def _normed_router(h, w, k, **kw):
    h = h.astype(jnp.float32)
    h = h * jax.lax.rsqrt(jnp.mean(h * h, axis=-1, keepdims=True) + 1e-6)
    return transformer.route_softmax_topk(h, w, k, **kw)


@pytest.mark.parametrize("change", [
    "window_a_page_longer", "rope_on_the_full_layers", "router_behind_a_norm",
    "router_behind_attention", "silu_for_relu"])
def test_the_comparison_fails_on_a_changed_layer(tiny, sequence, monkeypatch, change, served):
    """The four things the equations fix, each changed in the program:
    the served logits then leave the reference by orders of the limit."""
    cfg, _ = tiny
    seq, want = sequence
    if change == "window_a_page_longer":
        cfg = dataclasses.replace(cfg, sliding_window=24 + PAGE)
    elif change == "rope_on_the_full_layers":
        monkeypatch.setattr(fam, "_roped", lambda kind: True)
    elif change == "router_behind_a_norm":
        monkeypatch.setattr(fam, "route_softmax_topk", _normed_router)
    elif change == "router_behind_attention":
        kinds = tuple((kind[1], "route", "sparse") for kind in cfg.kinds)
        monkeypatch.setattr(fam.SmallThinkerConfig, "kinds", property(lambda self: kinds))
    else:
        cfg = dataclasses.replace(cfg, activation="silu")
    eng = served(cfg=cfg).engine   # a changed program: nobody else's
    got = _walk(eng, seq, prefill=70, decode=2)
    worst = max(_rms_share(logits, want[pos]) for pos, logits in got.items())
    assert worst > 100 * LOGITS_LIMIT, (change, worst)


def test_the_router_reads_the_layers_input():
    """The block's choice is the top-k of x W_r on the residual stream
    as it enters the layer: not of the normed stream, whose order of
    router outputs differs for this x (a scale a feature)."""
    cfg = fam.tiny(dtype=jnp.float32)
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((1, 16, cfg.hidden_size)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((1, cfg.hidden_size, cfg.num_experts)),
                    jnp.float32)
    _, carried = fam._route_block(cfg, {}, {"w_router": w}, 0, x, {})
    r = np.asarray(x[0] @ w[0])
    want = np.argsort(-r, axis=-1, kind="stable")[:, :cfg.num_experts_per_tok]
    np.testing.assert_array_equal(np.asarray(carried["route_experts"]), want)
    top = np.take_along_axis(r, want, -1)
    gate = np.exp(top - top.max(-1, keepdims=True))
    np.testing.assert_allclose(np.asarray(carried["route_weights"]),
                               gate / gate.sum(-1, keepdims=True), rtol=1e-5)
    scaled = x * jnp.asarray(rng.uniform(0.2, 5.0, cfg.hidden_size), jnp.float32)
    other = np.argsort(-np.asarray(scaled[0] @ w[0]), axis=-1)[:, :cfg.num_experts_per_tok]
    assert (other != want).any()


@pytest.mark.parametrize("kernels", ["xla", "pallas"])
def test_four_ranges_of_sixteen_add_up_to_the_references_layer(kernels):
    """The guide's test that ties a share to the model, at the published
    64 experts top-6 (small widths): the parts that ``experts_held``
    ranges (0, 16) ... (48, 64) give add up to the whole layer's result,
    their counts to the whole layer's, and the whole is the reference's
    ReGLU layer under the same routing."""
    D, F, E, K, T = 32, 16, 64, 6, 40
    rng = np.random.default_rng(5)
    h = jnp.asarray(rng.standard_normal((T, D)), jnp.float32)
    x_in = jnp.asarray(rng.standard_normal((T, D)), jnp.float32)
    w = {name: jnp.asarray(rng.standard_normal(shape) * 0.2, jnp.float32)
         for name, shape in (("w_gate", (E, D, F)), ("w_up", (E, D, F)),
                             ("w_down", (E, F, D)))}
    w_router = jnp.asarray(rng.standard_normal((D, E)), jnp.float32)
    experts, weights = transformer.route_softmax_topk(x_in, w_router, K)
    real = jnp.ones((T,), bool)

    def part(lo, hi):
        return transformer.routed_experts_ffn(
            h, real, experts, weights, *(w[n][lo:hi] for n in ("w_gate", "w_up", "w_down")),
            experts_held=(lo, hi), kernels=kernels, activation="relu")

    whole, counts = part(0, E)
    parts = [part(lo, lo + 16) for lo in range(0, E, 16)]
    np.testing.assert_allclose(sum(np.asarray(p[0]) for p in parts), np.asarray(whole),
                               rtol=0, atol=1e-5 * np.abs(np.asarray(whole)).max())
    np.testing.assert_array_equal(np.concatenate([np.asarray(p[1]) for p in parts]),
                                  np.asarray(counts))
    assert int(counts.sum()) == T * K
    # the reference's layer on the same inputs: its router reads x_in,
    # its experts the normed stream (a scale of ones: h normed is h's norm)
    config = dict(moe_num_primary_experts=E, rms_norm_eps=1e-6)
    params = {"sparse": dict({n: a[None] for n, a in w.items()},
                             mlp_norm_scale=jnp.ones((1, D), jnp.float32))}
    gate, _ = reference._route(x_in, w_router, False, k=K)
    normed = reference._rmsnorm(h, jnp.ones((D,)), 1e-6)
    ref_out = reference._experts(config, params, 0, h, gate, 0) - h
    got, _ = transformer.routed_experts_ffn(
        normed, real, experts, weights, w["w_gate"], w["w_up"], w["w_down"],
        experts_held=(0, E), kernels=kernels, activation="relu")
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref_out), rtol=0,
                               atol=1e-5 * np.abs(np.asarray(ref_out)).max())


def test_silu_stays_the_default_activation_of_the_grouped_experts():
    """The three sparse cells' programs do not change: where nothing is
    said the grouped matmuls gate with silu, and relu is another
    result."""
    rng = np.random.default_rng(6)
    h = jnp.asarray(rng.standard_normal((8, 16)), jnp.float32)
    w = [jnp.asarray(rng.standard_normal(s) * 0.3, jnp.float32)
         for s in ((2, 16, 8), (2, 16, 8), (2, 8, 16))]
    experts = jnp.asarray(rng.integers(0, 2, (8, 1)), jnp.int32)
    kw = dict(experts_held=(0, 2))
    args = (h, jnp.ones((8,), bool), experts, jnp.ones((8, 1), jnp.float32), *w)
    said, _ = transformer.routed_experts_ffn(*args, activation="silu", **kw)
    default, _ = transformer.routed_experts_ffn(*args, **kw)
    relu, _ = transformer.routed_experts_ffn(*args, activation="relu", **kw)
    np.testing.assert_array_equal(np.asarray(said), np.asarray(default))
    assert np.abs(np.asarray(relu) - np.asarray(default)).max() > 1e-3


def test_the_reference_without_its_window_is_another_model(tiny, sequence):
    """The benchmark's second control (``window=False``: the window
    layers attend the whole context) leaves the reference itself by
    orders of the limit past the first window, and agrees inside it."""
    cfg, params = tiny
    seq, want = sequence
    other = reference.forward(params, _file_config(cfg), np.asarray([seq]), window=False)[0]
    assert _rms_share(other[20], want[20]) < LOGITS_LIMIT
    assert min(_rms_share(other[p], want[p]) for p in range(60, 150)) > 100 * LOGITS_LIMIT


def test_the_reference_judges_rows_with_bounded_routings(tiny, sequence):
    """``judged_logits`` in the probe's shapes: routing 0 is the full
    forward pass's row; every routing's flip_margin is 0, a margin it
    overruled, or inf (never taken)."""
    cfg, params = tiny
    seq, want = sequence
    tokens = np.asarray([seq, seq[30:] + [0] * 30])
    judge = np.asarray([[100, 149], [60, 119]])
    logits, flip_margin, margin = reference.judged_logits(
        params, _file_config(cfg), tokens, judge)
    assert logits.shape == (2, 2, 16, cfg.vocab_size) and margin.shape == (2, 2)
    np.testing.assert_allclose(logits[0, :, 0], want[[100, 149]], rtol=0, atol=1e-5)
    assert (flip_margin[:, :, 0] == 0).all()
    taken = np.isfinite(flip_margin)
    assert (flip_margin[taken] < 0.05).all()
    control = reference.judged_logits(params, _file_config(cfg), tokens, judge,
                                      control_bits=8)[0]
    assert control.shape == (2, 2, 1, cfg.vocab_size)
    assert _rms_share(control[0, 0, 0], want[100]) > 100 * LOGITS_LIMIT


# --- (c) what is refused -----------------------------------------------------


@pytest.mark.parametrize("serving, names", [
    (dict(kv_layout="dense"), "kv_layout"),
    (dict(prefix_caching=True), "prefix_caching"),
    (dict(kv_quant="int8"), "kv_quant"),
    (dict(kv_shard="context", context_shards=2), "kv_shard"),
])
def test_refused_combinations_name_their_reason(tiny, serving, names, served):
    with pytest.raises(NotImplementedError, match=names):
        served(fresh=True, **serving)


def test_the_fused_prologue_is_refused(tiny, served):
    """The engine refuses it first: the family advertises no fusion."""
    with pytest.raises(ValueError, match="FUSED_DECODE"):
        served("pallas", fresh=True, fused_decode=("rope_kv_write",))


def test_from_hf_reads_the_benchmark_configuration():
    """The benchmark's file: the published widths, twelve layers in
    three periods, and what ``from_hf`` refuses."""
    import json

    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "smallthinker-21b-a3b.json")) as f:
        file = json.load(f)
    cfg = fam.from_hf(file, dtype=jnp.bfloat16)
    assert cfg.layer_kinds == (fam.FULL, fam.WINDOW, fam.WINDOW, fam.WINDOW) * 3
    assert (cfg.hidden_size, cfg.num_attention_heads, cfg.num_key_value_heads,
            cfg.head_dim) == (2560, 28, 4, 128)
    assert (cfg.num_experts, cfg.num_experts_per_tok, cfg.moe_intermediate_size,
            cfg.sliding_window, cfg.activation) == (64, 6, 768, 4096, "relu")
    assert not cfg.tie_word_embeddings and cfg.vocab_size == 151936
    assert fam.num_params(cfg) == 12 * (20_971_520 + 163_840 + 377_487_360 + 5_120) + 2 * 151936 * 2560 + 2560
    assert len(transformer.layer_runs(cfg.kinds)) == 6
    with pytest.raises(NotImplementedError, match="rope_layout"):
        fam.from_hf(dict(file, rope_layout=[1] * 12))
    with pytest.raises(NotImplementedError, match="softmax"):
        fam.from_hf(dict(file, moe_primary_router_apply_softmax=False))
