"""SpecInfer tests — the reference's key correctness property is that
speculative inference produces token-identical output to incremental
greedy decoding (reference tests/inference/python_inference_tests.sh:
111-123 diffs the two), while taking fewer LLM steps.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.models import llama
from flexflow_tpu.serve import (
    InferenceEngine,
    RequestManager,
    ServingConfig,
    SpecConfig,
    SpecInferManager,
    TokenTree,
)


@pytest.fixture(scope="module")
def tiny():
    cfg = llama.LLaMAConfig.tiny(dtype=jnp.float32)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


@pytest.fixture(scope="module")
def tiny_ssm():
    # A *different* tiny model as the draft: partial acceptance path.
    cfg = llama.LLaMAConfig.tiny(dtype=jnp.float32, num_hidden_layers=1)
    params = llama.init_params(jax.random.PRNGKey(7), cfg)
    return cfg, params


def make_engine(model_params):
    cfg, params = model_params
    sc = ServingConfig(
        max_requests_per_batch=4,
        max_sequence_length=96,
        prefill_chunk=8,
        max_spec_tree_tokens=16,
        cache_dtype=jnp.float32,
    )
    return InferenceEngine(llama, cfg, params, sc)


class TestTokenTree:
    def test_dedup_and_ancestors(self):
        t = TokenTree(5)
        a, _ = t.add(1, 0, -0.1)
        b, _ = t.add(2, 0, -0.5)
        dup, is_new = t.add(1, 0, -0.2)  # duplicate (parent, token)
        assert dup == a and not is_new
        c, _ = t.add(3, a, -0.3)
        anc = t.ancestor_matrix()
        assert anc[c, a] and anc[c, 0] and anc[c, c]
        assert not anc[c, b] and not anc[a, b]
        assert t.depths == [0, 1, 1, 2]

    def test_merge_trees_dedups_shared_branches(self):
        from flexflow_tpu.serve.specinfer import merge_trees

        t1 = TokenTree(5)
        a1, _ = t1.add(1, 0, -0.1)
        t1.add(3, a1, -0.3)
        t2 = TokenTree(5)
        a2, _ = t2.add(1, 0, -0.05)  # same branch, better logprob
        t2.add(4, a2, -0.4)          # new continuation
        m = merge_trees([t1, t2])
        # root + shared "1" + "3" + "4" = 4 nodes, not 5
        assert len(m) == 4
        assert sorted(m.tokens[1:]) == [1, 3, 4]
        shared = m.tokens.index(1)
        assert m.logprobs[shared] == -0.05  # max of duplicates

    def test_accept_walk(self):
        t = TokenTree(5)
        a, _ = t.add(1, 0, 0)
        t.add(2, 0, 0)
        c, _ = t.add(3, a, 0)
        # greedy_next per node: root->1 (match a), a->3 (match c), c->9 (bonus)
        greedy = np.zeros(len(t), np.int32)
        greedy[0], greedy[a], greedy[c] = 1, 3, 9
        path, bonus = t.accept_greedy(greedy)
        assert path == [0, a, c] and bonus == 9

    def test_accept_stops_on_mismatch(self):
        t = TokenTree(5)
        t.add(1, 0, 0)
        greedy = np.full(len(t), 42, np.int32)
        path, bonus = t.accept_greedy(greedy)
        assert path == [0] and bonus == 42


class TestSpecInfer:
    def test_self_speculation_matches_greedy(self, tiny, ref_greedy):
        """SSM == LLM: every speculated token is accepted; output must be
        identical to incremental greedy and use far fewer LLM steps."""
        cfg, params = tiny
        llm_eng = make_engine(tiny)
        ssm_eng = make_engine(tiny)
        mgr = SpecInferManager(
            llm_eng, ssm_eng, SpecConfig(beam_width=2, beam_depth=3)
        )
        prompt = [3, 17, 91, 42, 7]
        out = mgr.generate([prompt], max_new_tokens=12)[0]
        assert out.output_tokens == ref_greedy(cfg, params, prompt, 12)
        # Perfect draft => every round commits depth+1 tokens.
        assert out.profile.llm_decoding_steps < 12
        assert out.profile.accepted_tokens > 0

    def test_weak_draft_still_matches_greedy(self, tiny, tiny_ssm, ref_greedy):
        """A different draft model changes only the speed, never the
        output (the defining spec-decoding invariant)."""
        cfg, params = tiny
        for prompt in ([5, 9, 2], [77] * 11):
            mgr2 = SpecInferManager(
                make_engine(tiny), make_engine(tiny_ssm),
                SpecConfig(beam_width=2, beam_depth=4),
            )
            out = mgr2.generate([prompt], max_new_tokens=10)[0]
            assert out.output_tokens == ref_greedy(cfg, params, prompt, 10), prompt

    def test_batch_spec_infer(self, tiny, tiny_ssm, ref_greedy):
        cfg, params = tiny
        mgr = SpecInferManager(
            make_engine(tiny), make_engine(tiny_ssm),
            SpecConfig(beam_width=2, beam_depth=3),
        )
        prompts = [[1, 2, 3, 4], [9, 8, 7], [42] * 10]
        outs = mgr.generate(prompts, max_new_tokens=8)
        for p, o in zip(prompts, outs):
            assert o.output_tokens == ref_greedy(cfg, params, p, 8), p

    def test_spec_matches_incremental_manager(self, tiny, tiny_ssm):
        """End-to-end: SpecInferManager output == RequestManager output."""
        prompt = [11, 22, 33]
        rm = RequestManager(make_engine(tiny))
        incr = rm.generate([prompt], max_new_tokens=9)[0]
        mgr = SpecInferManager(
            make_engine(tiny), make_engine(tiny_ssm), SpecConfig(2, 3)
        )
        spec = mgr.generate([prompt], max_new_tokens=9)[0]
        assert spec.output_tokens == incr.output_tokens

    def test_two_ssm_tree_merge_matches_greedy(self, tiny, tiny_ssm, ref_greedy):
        """Two different drafts' trees merge (reference merge_dfs_trees)
        — output must still be exactly the greedy tokens."""
        cfg, params = tiny
        cfg2 = llama.LLaMAConfig.tiny(dtype=jnp.float32, num_hidden_layers=1)
        tiny_ssm2 = (cfg2, llama.init_params(jax.random.PRNGKey(31), cfg2))
        for prompt in ([5, 9, 2], [1, 2, 3, 4, 5, 6, 7]):
            mgr = SpecInferManager(
                make_engine(tiny),
                [make_engine(tiny_ssm), make_engine(tiny_ssm2)],
                SpecConfig(beam_width=2, beam_depth=3),
            )
            out = mgr.generate([prompt], max_new_tokens=10)[0]
            assert out.output_tokens == ref_greedy(cfg, params, prompt, 10), prompt

    def test_two_ssm_acceptance_not_degraded(self, tiny, ref_greedy):
        """Adding a second (identical) draft must not LOWER acceptance:
        if the multi-SSM commit corrupted the SSM caches, the drafts
        would attend garbage history from round 2 on and acceptance
        would collapse below the single-SSM baseline (output would stay
        greedy-correct, hiding the bug)."""
        cfg, params = tiny
        prompt = [3, 17, 91, 42, 7]
        single = SpecInferManager(
            make_engine(tiny), make_engine(tiny), SpecConfig(2, 3)
        ).generate([prompt], max_new_tokens=16)[0]
        dual = SpecInferManager(
            make_engine(tiny), [make_engine(tiny), make_engine(tiny)],
            SpecConfig(2, 3),
        ).generate([prompt], max_new_tokens=16)[0]
        assert dual.output_tokens == ref_greedy(cfg, params, prompt, 16)
        assert dual.profile.accepted_tokens >= single.profile.accepted_tokens
        assert dual.profile.llm_decoding_steps <= single.profile.llm_decoding_steps

    def test_two_ssm_through_llm_api(self, tiny, tiny_ssm, ref_greedy):
        """LLM.compile(ssms=[a, b]) no longer rejects multi-SSM."""
        from flexflow_tpu.core.mesh import MachineSpec
        from flexflow_tpu.serve.llm import LLM, SSM

        cfg, params = tiny
        mesh = MachineSpec().make_mesh(jax.devices()[:1])
        m = LLM(llama, cfg, params, mesh=mesh)
        ssm_a = SSM(llama, tiny_ssm[0], tiny_ssm[1], mesh=mesh)
        ssm_b = SSM(llama, cfg, params, mesh=mesh)  # self-draft
        sc = ServingConfig(
            max_requests_per_batch=4, max_sequence_length=96,
            prefill_chunk=8, max_spec_tree_tokens=16,
            cache_dtype=jnp.float32,
        )
        m.compile(sc, ssms=[ssm_a, ssm_b], spec=SpecConfig(2, 3))
        prompt = [3, 17, 91]
        out = m.generate([prompt], max_new_tokens=8)[0]
        assert out.output_tokens == ref_greedy(cfg, params, prompt, 8)


class TestSlidingWindowSpec:
    """Sliding-window models through the speculation loop: the window
    mask must use TRUE key positions (the pos cache) — tree-verify
    cache lines sit at prefix+node_index, not prefix+depth, so a
    line-index window under-masks and breaks spec==greedy exactly when
    the window is comparable to the tree depth."""

    def test_spec_equals_greedy_window_comparable_to_tree(self):
        import jax
        import jax.numpy as jnp

        from flexflow_tpu.models import mistral
        from flexflow_tpu.serve import (
            InferenceEngine,
            RequestManager,
            ServingConfig,
        )

        # window 4 ~ beam_depth+1: several verified keys per round fall
        # right at the window boundary
        cfg = mistral.tiny(dtype=jnp.float32, sliding_window=4)
        params = mistral.init_params(jax.random.PRNGKey(2), cfg)
        dcfg = mistral.tiny(dtype=jnp.float32, sliding_window=4,
                            num_hidden_layers=1)
        dparams = dict(params)
        dparams["layers"] = {k: v[:1] for k, v in params["layers"].items()}
        sc = ServingConfig(
            max_requests_per_batch=2, max_sequence_length=64,
            prefill_chunk=8, max_spec_tree_tokens=12,
            cache_dtype=jnp.float32,
        )
        prompts = [[3, 17, 91, 42, 5, 6, 7, 8, 9, 10, 11, 12], [9, 8, 7]]
        rm = RequestManager(InferenceEngine(mistral, cfg, params, sc))
        greedy = [
            o.output_tokens for o in rm.generate(prompts, max_new_tokens=12)
        ]
        mgr = SpecInferManager(
            InferenceEngine(mistral, cfg, params, sc),
            InferenceEngine(mistral, dcfg, dparams, sc),
            SpecConfig(beam_width=2, beam_depth=3),
        )
        spec = [
            o.output_tokens for o in mgr.generate(prompts, max_new_tokens=12)
        ]
        assert spec == greedy, (spec, greedy)
