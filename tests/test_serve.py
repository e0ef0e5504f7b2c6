"""Serving-stack tests — mirrors the reference's inference test strategy
(reference tests/inference/python_inference_tests.sh): incremental
decoding must match a naive full-forward greedy loop, chunked prefill
must match single-shot prefill, and continuous batching must not change
any request's output.
"""
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
)


@pytest.fixture(scope="module")
def tiny():
    cfg = llama.LLaMAConfig.tiny(dtype=jnp.float32)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def make_engine(tiny, **kw):
    cfg, params = tiny
    sc = ServingConfig(
        max_requests_per_batch=4,
        max_sequence_length=96,
        prefill_chunk=8,
        max_spec_tree_tokens=8,
        cache_dtype=jnp.float32,
        **kw,
    )
    return InferenceEngine(llama, cfg, params, sc)


class TestIncrementalDecoding:
    def test_matches_full_forward_greedy(self, tiny, ref_greedy):
        cfg, params = tiny
        eng = make_engine(tiny)
        rm = RequestManager(eng)
        prompt = [3, 17, 91, 42, 7]
        out = rm.generate([prompt], max_new_tokens=12)[0]
        expect = ref_greedy(cfg, params, prompt, 12)
        assert out.output_tokens == expect

    def test_chunked_prefill_matches(self, tiny, ref_greedy):
        """Prompt longer than prefill_chunk → multiple prefill steps, same
        output as the reference loop."""
        cfg, params = tiny
        eng = make_engine(tiny)
        rm = RequestManager(eng)
        prompt = [(i * 7 + 3) % cfg.vocab_size for i in range(21)]  # 3 chunks
        out = rm.generate([prompt], max_new_tokens=8)[0]
        assert out.output_tokens == ref_greedy(cfg, params, prompt, 8)

    def test_continuous_batching_isolation(self, tiny, ref_greedy):
        """Multiple concurrent requests produce exactly the single-request
        outputs (slot reuse + shared cache cannot leak across requests)."""
        cfg, params = tiny
        prompts = [
            [1, 2, 3, 4],
            [9, 8, 7, 6, 5, 4, 3, 2, 1, 11, 12, 13],
            [100, 200],
            [42] * 17,
            [5, 10, 15],  # 5 requests > 4 slots: exercises queueing
        ]
        eng = make_engine(tiny)
        rm = RequestManager(eng)
        outs = rm.generate(prompts, max_new_tokens=6)
        for p, o in zip(prompts, outs):
            assert o.output_tokens == ref_greedy(cfg, params, p, 6), p

    def test_slot_reuse_no_stale_cache(self, tiny, ref_greedy):
        """A request admitted into a previously-used slot must not read the
        old occupant's KV lines."""
        cfg, params = tiny
        eng = make_engine(tiny)
        rm = RequestManager(eng)
        first = rm.generate([[7, 7, 7, 7, 7, 7, 7, 7]], max_new_tokens=4)[0]
        second = rm.generate([[3, 1]], max_new_tokens=4)[0]
        assert second.output_tokens == ref_greedy(cfg, params, [3, 1], 4)
        assert first.output_tokens == ref_greedy(
            cfg, params, [7] * 8, 4
        )

    def test_dispatch_ahead_pipeline_used(self, tiny, ref_greedy):
        """Steady-state decode must go through the in-flight pipeline
        (no per-token blocking device_get — reference request_manager.cc
        :2310-2325) and still match the reference loop exactly."""
        cfg, params = tiny
        eng = make_engine(tiny)
        rm = RequestManager(eng)
        seen_depth = []
        orig = rm._dispatch_decode

        def spy(decoding):
            orig(decoding)
            seen_depth.append(len(rm._inflight))

        rm._dispatch_decode = spy
        prompt = [3, 17, 91]
        out = rm.generate([prompt], max_new_tokens=12)[0]
        assert out.output_tokens == ref_greedy(cfg, params, prompt, 12)
        assert seen_depth and max(seen_depth) >= 2, seen_depth

    def test_profiling_recorded(self, tiny):
        eng = make_engine(tiny)
        rm = RequestManager(eng)
        out = rm.generate([[1, 2, 3]], max_new_tokens=5)[0]
        assert out.profile.llm_decoding_steps == 5
        assert out.profile.latency_s > 0


class TestSampling:
    def test_greedy_flag_matches_argmax(self):
        from flexflow_tpu.serve.sampling import sample_tokens

        logits = jnp.asarray(np.random.default_rng(0).normal(size=(4, 50)))
        toks = sample_tokens(
            logits,
            jax.random.PRNGKey(0),
            greedy=jnp.ones((4,), bool),
            temperature=jnp.ones((4,)),
            topp=jnp.ones((4,)) * 2,
        )
        np.testing.assert_array_equal(
            np.asarray(toks), np.argmax(np.asarray(logits), -1)
        )

    def test_topp_restricts_support(self):
        from flexflow_tpu.serve.sampling import sample_tokens

        # One dominant token (prob ~1) → top-p 0.5 must always pick it.
        logits = np.full((2, 32), -10.0, np.float32)
        logits[:, 5] = 10.0
        for i in range(20):
            toks = sample_tokens(
                jnp.asarray(logits),
                jax.random.PRNGKey(i),
                greedy=jnp.zeros((2,), bool),
                temperature=jnp.ones((2,)),
                topp=jnp.full((2,), 0.5),
            )
            assert np.all(np.asarray(toks) == 5)

    def test_per_row_topk_restricts_support(self):
        """GenerationConfig.topk is honored per row in one program:
        k=1 forces the argmax even at high temperature; k<=0 leaves the
        row unrestricted."""
        from flexflow_tpu.serve.sampling import sample_tokens

        logits = np.tile(np.arange(32, dtype=np.float32), (2, 1))
        for i in range(20):
            toks = sample_tokens(
                jnp.asarray(logits * 0.01),  # nearly flat
                jax.random.PRNGKey(i),
                greedy=jnp.zeros((2,), bool),
                temperature=jnp.ones((2,)) * 5.0,
                topp=jnp.full((2,), 2.0),
                topk_arr=jnp.asarray([1, 0], np.int32),
            )
            assert int(toks[0]) == 31  # k=1 → always the max
        # the k=0 row must explore beyond the argmax at this temperature
        seen = {
            int(sample_tokens(
                jnp.asarray(logits * 0.01), jax.random.PRNGKey(i),
                greedy=jnp.zeros((2,), bool),
                temperature=jnp.ones((2,)) * 5.0,
                topp=jnp.full((2,), 2.0),
                topk_arr=jnp.asarray([1, 0], np.int32),
            )[1])
            for i in range(20)
        }
        assert len(seen) > 1

    def test_eos_stops_generation(self, tiny, ref_greedy):
        cfg, params = tiny
        eng = make_engine(tiny)
        # Find what greedy emits first, then declare it EOS.
        first = ref_greedy(cfg, params, [4, 9], 1)[0]
        rm = RequestManager(eng, eos_token_id=first)
        out = rm.generate([[4, 9]], max_new_tokens=10)[0]
        assert out.output_tokens == [first]


def test_output_file_telemetry(tiny, tmp_path):
    """-output-file sink: per finished request, latency + decoding steps
    + token ids are appended (reference request_manager.cc:417-440)."""
    path = str(tmp_path / "out.txt")
    rm = RequestManager(make_engine(tiny), output_file=path)
    prompts = [[1, 2, 3, 4], [5, 6, 7]]
    outs = rm.generate(prompts, max_new_tokens=5)
    text = open(path).read()
    lines = [l for l in text.splitlines() if l.startswith("[Profile]")]
    assert len(lines) == 2
    for o, line in zip(outs, lines):
        assert f"guid({o.request_id})" in line
        assert f"llm_decoding_steps({o.profile.llm_decoding_steps})" in line
        assert "latency(" in line
        # the token line carries prompt + output ids
        full = " ".join(str(t) for t in o.input_tokens + o.output_tokens)
        assert full in text
