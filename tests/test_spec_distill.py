"""Distilled drafts + verify-skip (PR 20, ROADMAP item 4).

Two claims under test. (1) Verify-skip: a request whose controller
sits at the (1,1) rung with a cold acceptance EMA rides the incremental
decode path — bitwise the non-speculative scheduler, with the SSM
mirrors' cache debt repaid before anything reads them. (2) Distillation
(`serve/spec_distill.py`): harvest → KL-train → checkpoint is
deterministic on the pinned-threefry CPU backend, and the emitted
student loads as an SSM spec whose utility the eval harness prices by
accept-rate-per-draft-GFLOP.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.models import llama
from flexflow_tpu.serve import (
    GenerationConfig,
    InferenceEngine,
    RequestManager,
    ServingConfig,
    SpecConfig,
    SpecInferManager,
)
from flexflow_tpu.serve import spec_distill as sd
from flexflow_tpu.serve.specinfer import TreeController


@pytest.fixture(scope="module")
def tiny():
    cfg = llama.LLaMAConfig.tiny(dtype=jnp.float32)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


@pytest.fixture(scope="module")
def cold_draft(tiny):
    # the adversarial draft: an UNRELATED 1-layer random init — nothing
    # it drafts agrees with the target, so acceptance sits at chance
    cfg, _ = tiny
    dcfg = dataclasses.replace(cfg, num_hidden_layers=1)
    dparams = llama.init_params(jax.random.PRNGKey(7), dcfg)
    return dcfg, dparams


def make_sc(**kw):
    d = dict(
        max_requests_per_batch=4,
        max_sequence_length=96,
        prefill_chunk=8,
        max_spec_tree_tokens=16,
        cache_dtype=jnp.float32,
        kv_layout="paged",
        page_size=16,
    )
    d.update(kw)
    return ServingConfig(**d)


def make_engine(model_params, **kw):
    cfg, params = model_params
    return InferenceEngine(llama, cfg, params, make_sc(**kw))


PROMPTS = [[3, 17, 91, 42, 7], [9, 8, 7], [42] * 9, [5, 9, 2, 11]]


_INCR = {}


def incr_ref(tiny, prompts=PROMPTS, n_new=16, **sc_kw):
    """Incremental greedy decoding's outputs, computed once a model and
    configuration (deterministic; every engine built compiles its step
    programs again, ROADMAP A13)."""
    key = (id(tiny[1]), tuple(map(tuple, prompts)), n_new) + tuple(
        sorted((k, v) for k, v in sc_kw.items() if v is not None))
    if key not in _INCR:
        rm = RequestManager(make_engine(tiny, **sc_kw))
        _INCR[key] = [o.output_tokens
                      for o in rm.generate(prompts, max_new_tokens=n_new)]
    return _INCR[key]


# ---------------------------------------------------------------------------
# verify-skip state machine (pure controller units)


class TestVerifySkipController:
    def spec(self, **kw):
        d = dict(beam_width=2, beam_depth=3, adaptive=True,
                 verify_skip=True, skip_threshold=0.1, reprobe_every=4)
        d.update(kw)
        return SpecConfig(**d)

    def cold(self, spec):
        """A controller driven down to rung (1,1) with a dead EMA."""
        ctrl = TreeController(spec)
        while ctrl.idx > 0 or ctrl.ema > spec.skip_threshold:
            ctrl.observe(0)
        return ctrl

    def test_requires_adaptive(self):
        with pytest.raises(ValueError, match="adaptive"):
            SpecConfig(2, 3, verify_skip=True)

    def test_threshold_validation(self):
        with pytest.raises(ValueError, match="skip_threshold"):
            self.spec(skip_threshold=1.5)
        with pytest.raises(ValueError, match="shrink_threshold"):
            self.spec(skip_threshold=0.9)
        with pytest.raises(ValueError, match="reprobe_every"):
            self.spec(reprobe_every=0)

    def test_off_means_always_spec(self):
        ctrl = TreeController(SpecConfig(2, 3, adaptive=True))
        for _ in range(20):
            assert ctrl.next_action() == "spec"
            ctrl.observe(0)

    def test_skip_engages_only_at_cold_bottom_rung(self):
        spec = self.spec()
        ctrl = TreeController(spec)
        # fresh controller: full tree, mid-band prior — no skipping
        assert ctrl.idx == len(spec.bucket_ladder) - 1
        assert ctrl.next_action() == "spec"
        assert self.cold(spec).next_action() == "skip"

    def test_reprobe_cadence(self):
        spec = self.spec(reprobe_every=4)
        ctrl = self.cold(spec)
        trace = [ctrl.next_action() for _ in range(10)]
        assert trace == ["skip"] * 4 + ["reprobe"] + ["skip"] * 4 + [
            "reprobe"
        ]
        assert ctrl.skipped_rounds == 8 and ctrl.reprobes == 2

    def test_warm_reprobe_exits_skip_regime(self):
        spec = self.spec(reprobe_every=2)
        ctrl = self.cold(spec)
        assert ctrl.next_action() == "skip"
        # a draft that warmed back up: perfect acceptance at re-probes
        # walks the EMA over the threshold and back up the ladder
        for _ in range(64):
            if ctrl.next_action() in ("reprobe", "spec"):
                ctrl.observe(ctrl.bucket[1], used_width=True)
        assert ctrl.next_action() == "spec"
        assert ctrl.idx > 0

    def test_streak_resets_on_spec_state(self):
        spec = self.spec(reprobe_every=4)
        ctrl = self.cold(spec)
        ctrl.next_action(), ctrl.next_action()  # streak 2
        ctrl.ema = spec.skip_threshold * 2 + 0.5  # warmed externally
        assert ctrl.next_action() == "spec"
        ctrl.ema = 0.0  # cold again: the cadence starts over
        assert [ctrl.next_action() for _ in range(5)] == (
            ["skip"] * 4 + ["reprobe"]
        )


# ---------------------------------------------------------------------------
# verify-skip end to end


def test_verify_skip_bitwise_and_ssm_debt_repaid(tiny, cold_draft):
    """The skip arm == plain incremental greedy, skips actually taken,
    re-probes on cadence, and no SSM cache debt left behind."""
    ref = incr_ref(tiny, n_new=16)
    mgr = SpecInferManager(
        make_engine(tiny),
        make_engine(cold_draft),
        SpecConfig(2, 3, adaptive=True, verify_skip=True,
                   skip_threshold=0.1, reprobe_every=4),
    )
    outs = [o.output_tokens for o in mgr.generate(PROMPTS, max_new_tokens=16)]
    assert outs == ref
    assert mgr.stats.verify_skipped_rounds > 0
    assert mgr.stats.spec_reprobes > 0
    # the skipped rounds advanced the LLM only; every lag entry must
    # have been repaid (re-probe) or voided (completion)
    assert mgr._ssm_lag == {}


def test_verify_skip_warm_draft_never_skips(tiny):
    """A perfect draft (the target itself) never trips the skip: the
    controller stays on the ladder and every round speculates."""
    ref = incr_ref(tiny, n_new=12)
    mgr = SpecInferManager(
        make_engine(tiny),
        make_engine(tiny),
        SpecConfig(2, 3, adaptive=True, verify_skip=True,
                   skip_threshold=0.1, reprobe_every=4),
    )
    outs = [o.output_tokens for o in mgr.generate(PROMPTS, max_new_tokens=12)]
    assert outs == ref
    assert mgr.stats.verify_skipped_rounds == 0
    assert mgr.stats.spec_accept_rate > 0.3


def test_verify_skip_early_exit_self_draft(tiny):
    """Early-exit self-draft (no SSM mirrors): the skip arm is the
    literal decode step — still bitwise, with nothing to repay."""
    ref = incr_ref(tiny, n_new=16)
    mgr = SpecInferManager(
        make_engine(tiny),
        None,
        SpecConfig(2, 3, adaptive=True, verify_skip=True,
                   skip_threshold=0.45, reprobe_every=4,
                   shrink_threshold=0.45,
                   draft="early_exit", draft_layers=1),
    )
    outs = [o.output_tokens for o in mgr.generate(PROMPTS, max_new_tokens=16)]
    assert outs == ref
    assert mgr._ssm_lag == {}


# ---------------------------------------------------------------------------
# harvest buffer


def test_buffer_add_and_batches():
    buf = sd.HarvestBuffer(max_examples=64)
    V = 32
    # default start: rows line up against the END of the token list
    buf.add([1, 2, 3, 4, 5], np.zeros((2, V), np.float32))
    assert len(buf) == 2
    toks0, _ = buf.examples[0]
    assert toks0 == [1, 2, 3, 4]  # context of row 0: tokens[:start+1]
    for toks, row in buf.examples:
        assert row.shape == (V,)
    # batches: fixed shapes, right-aligned, ragged tail dropped
    for i in range(7):
        buf.add([i] * 6, np.ones((3, V), np.float32))
    batches = buf.batches(seq_len=4, batch_size=8)
    assert len(batches) == (len(buf) // 8)
    toks, idx, tgt = batches[0]
    assert toks.shape == (8, 4) and toks.dtype == np.int32
    assert idx.shape == (8,) and tgt.shape == (8, V)
    assert np.all(idx < 4)

    # more rows than tokens: the empty-context rows are dropped, not kept
    n = len(buf)
    buf.add([1, 2], np.zeros((5, V), np.float32))
    assert len(buf) == n


def test_harvest_offline_rows_match_teacher_greedy(tiny):
    """Offline replay harvests every position's next-token logits; on
    the teacher's OWN greedy trace the argmax of a harvested row must
    overwhelmingly agree with the token that actually followed."""
    cfg, params = tiny
    rm = RequestManager(make_engine(tiny))
    traces = rm.generate(PROMPTS, max_new_tokens=12)
    buf = sd.harvest_offline(llama, cfg, params, traces, max_len=20)
    assert len(buf) > 0
    # recompute agreement over the generated region of the first trace
    hits = total = 0
    t0 = list(traces[0].input_tokens) + list(traces[0].output_tokens)
    fwd = jax.jit(lambda p, t: llama.forward(p, t, cfg))
    lg = np.asarray(
        fwd(params, jnp.asarray(np.asarray(t0, np.int32)[None, :],
                                dtype=jnp.int32))
    )[0]
    for k in range(len(traces[0].input_tokens) - 1, len(t0) - 1):
        total += 1
        hits += int(np.argmax(lg[k]) == t0[k + 1])
    assert total > 0 and hits / total > 0.8, (hits, total)


def test_harvest_online_sink_attach_detach(tiny):
    cfg, params = tiny
    mgr = SpecInferManager(
        make_engine(tiny),
        make_engine(tiny),
        SpecConfig(2, 3, adaptive=True),
    )
    assert mgr.logit_sink is None
    buf = sd.harvest_online(mgr, PROMPTS, max_new_tokens=8)
    assert mgr.logit_sink is None  # detached on exit
    assert len(buf) > 0
    for toks, row in buf.examples:
        assert row.shape == (cfg.vocab_size,)
        assert len(toks) >= 1


# ---------------------------------------------------------------------------
# distillation training


def _small_buffer(tiny, n_new=12):
    cfg, params = tiny
    rm = RequestManager(make_engine(tiny))
    traces = rm.generate(PROMPTS, max_new_tokens=n_new)
    return sd.harvest_offline(llama, cfg, params, traces, max_len=20)


def test_distill_deterministic_and_loss_improves(tiny):
    """Two identical runs on the pinned-threefry CPU backend: bitwise
    identical loss histories AND parameter trees; sharp-target training
    moves the loss."""
    cfg, _ = tiny
    buf = _small_buffer(tiny)
    dcfg = sd.DistillConfig(
        hidden_size=32, num_layers=1, num_heads=2, seq_len=16,
        batch_size=4, steps=40, lr=3e-3, temperature=0.05, seed=0,
    )
    scfg1, p1, h1 = sd.train_distilled_draft(buf, cfg, dcfg, family=llama)
    scfg2, p2, h2 = sd.train_distilled_draft(buf, cfg, dcfg, family=llama)
    assert h1 == h2
    flat1 = jax.tree_util.tree_leaves(p1)
    flat2 = jax.tree_util.tree_leaves(p2)
    assert all(np.array_equal(a, b) for a, b in zip(flat1, flat2))
    assert h1[-1] < h1[0], h1
    # the student inherits non-geometry fields from the teacher
    assert scfg1.vocab_size == cfg.vocab_size
    assert scfg1.hidden_size == 32 and scfg1.num_hidden_layers == 1


def test_distill_config_validation():
    with pytest.raises(ValueError, match="divisible"):
        sd.DistillConfig(hidden_size=30, num_heads=4)
    with pytest.raises(ValueError, match="temperature"):
        sd.DistillConfig(temperature=0.0)
    with pytest.raises(ValueError, match="fewer than one"):
        sd.train_distilled_draft(
            sd.HarvestBuffer(),
            llama.LLaMAConfig.tiny(dtype=jnp.float32),
            sd.DistillConfig(hidden_size=32, num_layers=1, num_heads=2),
            family=llama,
        )


def test_save_load_roundtrip(tiny, tmp_path):
    cfg, _ = tiny
    buf = _small_buffer(tiny)
    dcfg = sd.DistillConfig(
        hidden_size=32, num_layers=1, num_heads=2, seq_len=16,
        batch_size=4, steps=4, lr=1e-3, seed=0,
    )
    scfg, sparams, _ = sd.train_distilled_draft(buf, cfg, dcfg, family=llama)
    sd.save_distilled_draft(str(tmp_path / "draft"), scfg, sparams)
    lcfg, lparams = sd.load_distilled_draft(
        str(tmp_path / "draft"), cfg, family=llama
    )
    assert lcfg == scfg
    a = jax.tree_util.tree_leaves(sparams)
    b = jax.tree_util.tree_leaves(lparams)
    assert all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(a, b))


# ---------------------------------------------------------------------------
# the eval harness + cost-model feed


def test_measure_draft_utility_and_rank(tiny):
    cfg, _ = tiny
    buf = _small_buffer(tiny)
    dcfg = sd.DistillConfig(
        hidden_size=32, num_layers=1, num_heads=2, seq_len=16,
        batch_size=4, steps=20, lr=3e-3, temperature=0.05, seed=0,
    )
    scfg, sparams, _ = sd.train_distilled_draft(buf, cfg, dcfg, family=llama)
    mgr = SpecInferManager(
        make_engine(tiny),
        InferenceEngine(llama, scfg, sparams, make_sc()),
        SpecConfig(2, 3, adaptive=True),
    )
    ev = sd.measure_draft_utility(mgr, PROMPTS, max_new_tokens=8,
                                  name="distilled")
    assert 0.0 <= ev.accept_rate <= 1.0
    assert ev.draft_gflops_per_token > 0
    assert ev.output_tokens > 0
    assert ev.accept_rate_per_gflop == pytest.approx(
        ev.accept_rate / ev.draft_gflops_per_token
    )
    other = sd.DraftEval("b", 0.5, 1.0, 0.5)
    best = sd.rank_drafts([ev, other])[0]
    assert best.accept_rate_per_gflop == max(
        ev.accept_rate_per_gflop, 0.5
    )
    # the pricing matches the cost model's 2·params convention
    assert ev.draft_gflops_per_token == pytest.approx(
        sd.draft_gflops_per_token(scfg)
    )


def test_cost_model_prefers_measured_accept_rate():
    from flexflow_tpu.serve.autotune import (
        ModelGeometry,
        ServingCandidate,
        ServingCostModel,
        TrafficProfile,
    )

    geom = ModelGeometry(
        hidden_size=512, num_layers=8, num_heads=8, num_kv_heads=8,
        intermediate_size=2048, vocab_size=32000,
    )
    cm = ServingCostModel(geom)
    cand = ServingCandidate(speculation=True, spec_width=2, spec_depth=4)

    def traffic(**kw):
        return TrafficProfile(
            arrival_rate_rps=50.0, prompt_len_p50=128.0,
            prompt_len_p99=512.0, output_len_p50=128.0,
            output_len_p99=256.0, spec_accept_rate=0.7, **kw,
        )

    commit_prior, _ = cm._spec_commit(cand, traffic())
    commit_cold, _ = cm._spec_commit(
        cand, traffic(measured_accept_rate=0.0)
    )
    commit_hot, _ = cm._spec_commit(
        cand, traffic(measured_accept_rate=0.95)
    )
    assert commit_cold == 1.0          # measured-dead draft: bonus only
    assert commit_hot > commit_prior   # measured-hot beats the prior


