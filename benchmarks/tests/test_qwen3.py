"""The Qwen3-Next configuration's part of the benchmark (PR 53): the
count modules against hand sums at one small mix and against the
issue's arithmetic at the published widths; the new readers read
nothing, and raise nothing, without a trace or on a program that lacks
what they name; the plain reference against the installed
``transformers``' ``Qwen3NextForCausalLM`` (torch on the CPU, a
checkpoint's interleaved ``W_qkvz`` / ``W_ba`` permuted into the plain
column blocks the program holds); and the host's counters this family
is the first to feed together, through ``LLM.generate`` at the tiny
preset."""
import dataclasses
import json
import os
import types

import numpy as np
import pytest

from benchmarks.harness import reduce, spec
from benchmarks.harness.loop import Window

SMALL = dict(
    hidden_size=8, vocab_size=100, num_attention_heads=4, num_key_value_heads=2,
    head_dim=6, num_hidden_layers=5, full_attention_interval=4,
    linear_num_key_heads=2, linear_num_value_heads=6, linear_key_head_dim=2,
    linear_value_head_dim=4, linear_conv_kernel_dim=4, num_experts=4,
    router_outputs=16, experts_held=[4, 8], num_experts_per_tok=3,
    moe_intermediate_size=5, shared_expert_intermediate_size=7)
# three decoding rows at 50 keys each, one prefilling row of a 20-token
# prompt, half-way, feeding 10 tokens
MIX = dict(decode_rows=3, decode_ctx=150, prefill_rows=1, prefill_tokens=10,
           prefill_row_ctx=10, prefill_tok_ctx=10 * 10.5)


def _count(name, cfg=SMALL, mix=MIX):
    return spec.load_module("counts", name).count(cfg, mix)


def test_counts_against_hand_sums():
    D, V, H, KV, d = 8, 100, 4, 2, 6
    Hk, Hv, dk, dv, taps = 2, 6, 2, 4, 4
    E, held, K, F, S = 16, 4, 3, 5, 7
    channels = 2 * Hk * dk + Hv * dv                        # 32
    gdn = D * (channels + Hv * dv) + D * 2 * Hv + Hv * dv * D
    attn = D * H * 2 * d + 2 * D * KV * d + H * d * D
    sparse = D * E + 3 * D * S + D                          # router, shared expert and gate
    expert = 3 * D * F
    tokens, rows = 13, 4
    # layers 0-4: [L, L, L, F, L]: 4 recurrent, 1 full, 5 sparse blocks
    per_token = 4 * gdn + 1 * attn + 5 * sparse
    hit = held * (1 - (1 - K / E) ** tokens)
    pairs = tokens * K * held / E
    rule = (3 * 7 * Hv * dk * dv
            + 10 * (Hv * (6 * dk * dv + 2 * 64 * dv) + Hk * 2 * 64 * dk)
            + tokens * 2 * taps * channels)
    state = 2 * rows * (4 * Hv * dk * dv + 2 * (taps - 1) * channels)
    flops = (2 * tokens * per_token + 2 * 5 * pairs * expert
             + 4 * H * d * (150 + 105) * 1 + 4 * rule + 2 * rows * D * V)
    nbytes = 2 * (per_token + 5 * hit * expert + D * V + tokens * D
                  + 1 * 2 * KV * d * (150 + 10 + tokens)) + 4 * state
    assert _count("qwen3_next_step") == (pytest.approx(flops), pytest.approx(nbytes))
    assert _count("gdn_grouped_mixer") == (
        pytest.approx(4 * (2 * tokens * gdn + rule)),
        pytest.approx(4 * (2 * (gdn + 2 * tokens * D) + state)))
    assert _count("held_moe_ffn") == (
        pytest.approx(2 * pairs * expert),
        pytest.approx(2 * (hit * expert + 2 * pairs * D)))
    # one call of the state kernel: 3 decoding rows; k and q a KEY head
    assert _count("gdn_grouped_recur_kernel") == (
        pytest.approx(7 * 3 * Hv * dk * dv),
        pytest.approx(4 * 3 * (2 * Hv * dk * dv + 2 * Hk * dk + 5 * Hv * dv)))


def test_counts_at_the_published_widths_are_the_issues():
    """64 rows at a mean context of 600: 13 ms of memory a step by the
    chip's peak (the issue: some 14): a layer's hit experts 0.71 ms,
    a recurrent layer's state each way 0.33 ms; 72% of the experts held
    are hit, 1.25 rows an expert held."""
    with open(os.path.join(spec.BENCH_DIR, "configs", "qwen3-next-80b-a3b.json")) as f:
        cfg = json.load(f)
    mix = dict(decode_rows=64, decode_ctx=64 * 600, prefill_rows=0,
               prefill_tokens=0, prefill_row_ctx=0, prefill_tok_ctx=0)
    sizes = spec.load_module("counts", "qwen3_next_sizes")
    s = sizes.sizes(cfg)
    assert (s["n_gdn"], s["n_attn"], s["held"], s["E"]) == (9, 3, 128, 512)
    assert s["gdn_mixer"] / 1e6 == pytest.approx(33.69, abs=0.01)
    assert s["attn_mixer"] / 1e6 == pytest.approx(27.26, abs=0.01)
    assert s["state"] * 4 == 2_097_152 and s["kv_line"] * 2 * s["n_attn"] == 6144
    assert sizes.experts_hit(s, 64) / 128 == pytest.approx(0.717, abs=0.001)
    assert sizes.pairs_held(s, 64) / 128 == 1.25
    ms = lambda name: _count(name, cfg, mix)[1] / 819e9 * 1e3
    assert ms("qwen3_next_step") == pytest.approx(13.0, abs=0.2)
    assert ms("held_moe_ffn") == pytest.approx(0.71, abs=0.01)
    assert ms("gdn_grouped_recur_kernel") == pytest.approx(0.335, abs=0.005)
    assert ms("gdn_grouped_mixer") / 9 == pytest.approx(0.42, abs=0.01)


def _ctx(trace=reduce.NoTrace()):
    return reduce.Context(
        window=Window(), setup_s=0.0, cfg=SMALL, peaks=None, trace=trace,
        engine_serving=types.SimpleNamespace(mixed_chunk=128))


@pytest.mark.parametrize("name", [
    "step.gdn_moe_decode_roofline", "moe.held_ffn_roofline.decode",
    "mixer.gdn_grouped_roofline.decode", "kernel.gdn_grouped_recur_roofline.decode"])
def test_nothing_to_read_is_nothing(name):
    """Without a trace, and on a traced program that has no such kernel
    or scope (the parent of PR 53 under these files)."""
    reader = spec.load_module("per_layer", name)
    assert reader.read(_ctx()) is None
    bare = types.SimpleNamespace(ops=(), programs={}, sublayers=None, lo=0, hi=0,
                                 program_ms=lambda chunk: None)
    assert reader.read(_ctx(trace=bare)) is None


# --- the reference against transformers' Qwen3NextForCausalLM -----------------

HF = dict(
    vocab_size=256, hidden_size=64, intermediate_size=128, num_hidden_layers=8,
    num_attention_heads=4, num_key_value_heads=2, head_dim=32,
    partial_rotary_factor=0.25, rope_theta=1e7, rms_norm_eps=1e-6,
    linear_conv_kernel_dim=4, linear_key_head_dim=16, linear_value_head_dim=16,
    linear_num_key_heads=2, linear_num_value_heads=4, decoder_sparse_step=1,
    moe_intermediate_size=32, shared_expert_intermediate_size=32,
    num_experts_per_tok=3, num_experts=16, norm_topk_prob=True, mlp_only_layers=[],
    full_attention_interval=4, tie_word_embeddings=False,
    max_position_embeddings=512, hidden_act="silu", attention_bias=False)


def converted(sd, hf):
    """A ``Qwen3NextForCausalLM`` state dict as the program's (and the
    reference's) parameter tree: matrices transposed to (in, out), the
    interleaved ``in_proj_qkvz`` ([q | k | v | z] a KEY head) and
    ``in_proj_ba`` ([b | a] a key head) permuted into plain column
    blocks, each group stacked over its layers."""
    import jax
    import jax.numpy as jnp

    Hk, H = hf["linear_num_key_heads"], hf["linear_num_value_heads"]
    dk, dv, G = hf["linear_key_head_dim"], hf["linear_value_head_dim"], H // Hk
    T = lambda w: np.ascontiguousarray(w.T)

    def qkvz_cols(w):
        w = w.reshape(Hk, 2 * dk + 2 * G * dv, -1)
        parts = (w[:, :dk], w[:, dk:2 * dk], w[:, 2 * dk:2 * dk + G * dv],
                 w[:, 2 * dk + G * dv:])
        return T(np.concatenate([x.reshape(-1, w.shape[-1]) for x in parts], 0))

    def ba_cols(w):
        w = w.reshape(Hk, 2 * G, -1)
        return T(np.concatenate([w[:, :G].reshape(H, -1), w[:, G:].reshape(H, -1)], 0))

    gdn, attn, sparse = [], [], []
    for i in range(hf["num_hidden_layers"]):
        pre = f"model.layers.{i}."
        norm = sd[pre + "input_layernorm.weight"]
        if (i + 1) % hf["full_attention_interval"]:
            la = pre + "linear_attn."
            gdn.append(dict(
                attn_norm_w=norm, w_qkvz=qkvz_cols(sd[la + "in_proj_qkvz.weight"]),
                conv_w=T(sd[la + "conv1d.weight"][:, 0]),
                w_gates=ba_cols(sd[la + "in_proj_ba.weight"]),
                dt_bias=sd[la + "dt_bias"], A_log=sd[la + "A_log"],
                o_norm_scale=sd[la + "norm.weight"], wo=T(sd[la + "out_proj.weight"])))
        else:
            sa = pre + "self_attn."
            attn.append(dict(
                attn_norm_w=norm, wq=T(sd[sa + "q_proj.weight"]),
                wk=T(sd[sa + "k_proj.weight"]), wv=T(sd[sa + "v_proj.weight"]),
                q_norm_w=sd[sa + "q_norm.weight"], k_norm_w=sd[sa + "k_norm.weight"],
                wo=T(sd[sa + "o_proj.weight"])))
        mlp = pre + "mlp."
        experts = lambda name: np.stack([
            T(sd[mlp + f"experts.{e}.{name}.weight"]) for e in range(hf["num_experts"])])
        sparse.append(dict(
            mlp_norm_w=sd[pre + "post_attention_layernorm.weight"],
            w_router=T(sd[mlp + "gate.weight"]), w_gate=experts("gate_proj"),
            w_up=experts("up_proj"), w_down=experts("down_proj"),
            shared={name: T(sd[mlp + f"shared_expert.{hf_name}.weight"])
                    for name, hf_name in (("w_gate", "gate_proj"), ("w_up", "up_proj"),
                                          ("w_down", "down_proj"))},
            w_shared_gate=T(sd[mlp + "shared_expert_gate.weight"])))
    stack = lambda layers: jax.tree.map(lambda *a: jnp.asarray(np.stack(a)), *layers)
    return dict(embed=jnp.asarray(sd["model.embed_tokens.weight"]),
                final_norm_w=jnp.asarray(sd["model.norm.weight"]),
                lm_head=jnp.asarray(T(sd["lm_head.weight"])),
                gdn=stack(gdn), attn=stack(attn), sparse=stack(sparse))


def test_the_reference_is_transformers_qwen3_next():
    """Two periods at a tiny size, every parameter moved off its
    initial value (the zero-centred norms' weights 0.1 normal, the
    output norm's about one): logits agree to float32 rounding (2.5e-6
    of a row's size, my CPU run, PR 53), so the equations the reference
    writes out are the published implementation's."""
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    if not hasattr(transformers, "Qwen3NextForCausalLM"):
        pytest.skip("this transformers has no Qwen3Next")
    torch.manual_seed(0)
    model = transformers.Qwen3NextForCausalLM(
        transformers.Qwen3NextConfig(**HF, layer_types=None)).float().eval()
    with torch.no_grad():
        for name, p in model.named_parameters():
            if "norm" in name:
                p.copy_(torch.randn_like(p) * 0.1
                        + (1.0 if name.endswith("linear_attn.norm.weight") else 0.0))
            elif "A_log" not in name and "dt_bias" not in name:
                p.copy_(torch.randn_like(p) * (0.3 if "conv1d" in name else 0.05))
    params = converted({k: v.detach().numpy() for k, v in model.state_dict().items()}, HF)
    tokens = np.random.default_rng(0).integers(0, 256, (2, 40))
    with torch.no_grad():
        want = model(torch.tensor(tokens)).logits.numpy()
    got = spec.load_module("references", "qwen3_next").forward(params, HF, tokens)
    assert np.sqrt(np.mean((got - want) ** 2) / np.mean(want ** 2)) < 2e-5


def test_routing_zero_of_the_judged_tokens_is_the_forward_pass():
    """``judged_logits`` walks each judged token again, alone, through
    single-token forms of both mixers (the row's own state, inputs and
    keys before it): under routing 0 (no flip) that is the forward
    pass's own row, and a flip that is allowed moves it."""
    import jax
    import jax.numpy as jnp

    from flexflow_tpu.models import qwen3_next as fam

    cfg = fam.tiny(dtype=jnp.float32, num_hidden_layers=8)
    params = fam.init_params(jax.random.PRNGKey(1), cfg)
    config = dict(HF, tolerance={"routing_margin": 0.5})
    reference = spec.load_module("references", "qwen3_next")
    tokens = np.random.default_rng(4).integers(0, 256, (2, 24))
    judge = np.asarray([[0, 5, 23], [11, 12, 3]])
    logits, flip_margin, margin = reference.judged_logits(params, config, tokens, judge)
    want = reference.forward(params, config, tokens)
    assert logits.shape == (2, 3, 2 ** reference.MAX_FLIPPED, 256)
    rows = np.arange(2)[:, None]
    np.testing.assert_allclose(logits[:, :, 0], want[rows, judge], atol=2e-5)
    assert (flip_margin[:, :, 0] == 0).all() and (margin > 0).all()
    moved = np.abs(logits[:, :, 1:] - logits[:, :, :1]).max(-1)
    assert (moved[np.isfinite(flip_margin[:, :, 1:])] > 1e-4).all()


# --- the host's counters, through the scheduler --------------------------------


def test_generate_feeds_the_hosts_counters():
    """Greedy tokens through ``RequestManager`` are the reference's
    argmax (teacher-forced), and beside them the counters this family
    is the first to feed together: the recurrent updates of every real
    token fed, times the three recurrent layers, and the routed pairs of
    the four sparse layers, of which the experts held get all (the tiny
    preset holds every expert)."""
    import jax
    import jax.numpy as jnp

    from flexflow_tpu.models import qwen3_next as fam
    from flexflow_tpu.serve import ServingConfig
    from flexflow_tpu.serve.llm import LLM

    cfg = fam.tiny(dtype=jnp.float32)
    params = fam.init_params(jax.random.PRNGKey(0), cfg)
    file_config = dict(
        HF, num_hidden_layers=cfg.num_hidden_layers,
        num_experts=cfg.num_experts, num_experts_per_tok=cfg.num_experts_per_tok)
    reference = spec.load_module("references", "qwen3_next")
    llm = LLM(fam, cfg, params=params)
    llm.compile(ServingConfig(
        kv_layout="paged", kernels="xla", page_size=16, max_requests_per_batch=4,
        max_sequence_length=96, prefill_chunk=16, cache_dtype=jnp.float32))
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab_size, n).tolist() for n in (21, 40, 9)]
    before = dataclasses.replace(llm.rm.stats)
    outs = llm.generate(prompts, max_new_tokens=6)
    for prompt, out in zip(prompts, outs):
        want = reference.forward(params, file_config,
                                 np.asarray([prompt + out.output_tokens]))[0]
        assert out.output_tokens == want[len(prompt) - 1:-1].argmax(-1).tolist()
    stats = llm.rm.stats
    fed = sum(map(len, prompts)) + 3 * 5
    grew = {n: getattr(stats, n) - getattr(before, n) for n in (
        "state_resets", "recurrent_updates", "moe_pairs", "moe_experts_hit",
        "moe_experts_held", "moe_load_max")}
    assert grew["state_resets"] == 3
    assert grew["recurrent_updates"] == cfg.count("gdn") * fed == 3 * fed
    assert grew["moe_pairs"] == fed * cfg.num_experts_per_tok * cfg.count("sparse")
    assert 0 < grew["moe_experts_hit"] <= grew["moe_experts_held"]
    assert grew["moe_experts_held"] % (4 * cfg.num_experts) == 0
    assert grew["moe_load_max"] >= grew["moe_pairs"] / cfg.num_experts
    assert stats.slot_state_bytes == sum(
        int(llm.engine.cache[n].nbytes) for n in fam.SLOT_STATE)
