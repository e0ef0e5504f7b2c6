"""Qwen2-MoE model family (HF ``Qwen2MoeForCausalLM``, e.g.
Qwen1.5-MoE-A2.7B) — beyond the reference zoo. Runs on the generic
decoder's MoE path plus its Qwen2-MoE extensions: routed experts with
their own FFN width, softmax-over-all top-k WITHOUT renormalization
(``norm_topk_prob=False`` default), and an always-on sigmoid-gated
shared expert. Attention is Qwen2-style (RoPE, GQA, RMSNorm, QKV
biases). As for ``models/mixtral.py``, a paged serving step on one
device whose (token, expert) pairs are 16 an expert routes its tokens
through the grouped expert matmuls (``transformer.routed_experts_ffn``;
the shared expert stays dense matmuls over every token) and every
other step takes the all-expert einsum (``transformer._moe_ffn``)."""
from __future__ import annotations

from typing import Any, Dict

import jax.numpy as jnp
import numpy as np

from . import transformer
from .transformer import (  # noqa: F401  (engine serving protocol)
    DecoderConfig,
    FUSED_DECODE,
    PACKED_STEP,
    commit_kv,
    commit_kv_paged,
    copy_page_kv,
    expert_routing,
    forward,
    gather_page_kv,
    init_kv_cache,
    init_paged_kv_cache,
    init_params,
    kv_cache_pspecs,
    num_params,
    paged_kv_cache_pspecs,
    param_pspecs,
    reorder_slots,
    reorder_slots_paged,
    scatter_page_kv,
    serve_debug_activations,
    serve_step,
    serve_step_paged,
    step_counts,
)
from .hf_utils import layer_stackers, linear_w, stack, to_np


def config(**kw) -> DecoderConfig:
    d: Dict[str, Any] = dict(
        vocab_size=151936,
        hidden_size=2048,
        intermediate_size=5632,
        num_hidden_layers=24,
        num_attention_heads=16,
        num_key_value_heads=16,
        max_position_embeddings=8192,
        norm_type="rmsnorm",
        norm_bias=False,
        norm_eps=1e-6,
        positions="rope",
        rope_theta=1e6,
        activation="silu",
        glu=True,
        qkv_bias=True,
        out_bias=False,
        mlp_bias=False,
        tie_word_embeddings=False,
        num_local_experts=60,
        num_experts_per_tok=4,
        moe_intermediate_size=1408,
        moe_shared_expert_intermediate_size=5632,
        moe_norm_topk=False,
    )
    d.update(kw)
    return DecoderConfig(**d)


def tiny(**kw) -> DecoderConfig:
    d = dict(
        vocab_size=256,
        hidden_size=64,
        intermediate_size=128,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=2,
        max_position_embeddings=128,
        num_local_experts=4,
        num_experts_per_tok=2,
        moe_intermediate_size=96,
        moe_shared_expert_intermediate_size=112,
    )
    d.update(kw)
    return config(**d)


def from_hf(hf: Dict[str, Any], **kw) -> DecoderConfig:
    if hf.get("decoder_sparse_step", 1) != 1 or hf.get("mlp_only_layers"):
        # non-uniform layer mixtures (every-Nth-layer MoE / forced-dense
        # layers) would need per-layer FFN shapes in the scan
        raise NotImplementedError(
            "Qwen2-MoE with decoder_sparse_step != 1 or mlp_only_layers "
            "is not supported (non-uniform layer stacks)"
        )
    if hf.get("use_sliding_window"):
        raise NotImplementedError(
            "Qwen2-MoE sliding-window attention is not supported"
        )
    d = dict(
        vocab_size=hf["vocab_size"],
        hidden_size=hf["hidden_size"],
        intermediate_size=hf["intermediate_size"],
        num_hidden_layers=hf["num_hidden_layers"],
        num_attention_heads=hf["num_attention_heads"],
        num_key_value_heads=hf.get(
            "num_key_value_heads", hf["num_attention_heads"]
        ),
        max_position_embeddings=hf["max_position_embeddings"],
        norm_eps=hf.get("rms_norm_eps", 1e-6),
        rope_theta=hf.get("rope_theta", 1e6),
        num_local_experts=hf.get("num_experts", 60),
        num_experts_per_tok=hf.get("num_experts_per_tok", 4),
        moe_intermediate_size=hf.get("moe_intermediate_size", 1408),
        moe_shared_expert_intermediate_size=hf.get(
            "shared_expert_intermediate_size", 5632
        ),
        moe_norm_topk=hf.get("norm_topk_prob", False),
        tie_word_embeddings=hf.get("tie_word_embeddings", False),
    )
    d.update(kw)
    return config(**d)


def convert_hf_state_dict(
    sd: Dict[str, Any], cfg: DecoderConfig
) -> Dict[str, Any]:
    """HF ``Qwen2MoeForCausalLM`` state dict → framework pytree."""
    dt = cfg.dtype
    L, E = cfg.num_hidden_layers, cfg.num_local_experts
    pre = "model."
    mats, vecs = layer_stackers(sd, pre, L, dt)

    def experts(which):
        return stack(
            [
                np.stack(
                    [
                        linear_w(
                            sd,
                            pre + f"layers.{i}.mlp.experts.{e}."
                                  f"{which}.weight",
                        )
                        for e in range(E)
                    ],
                    axis=0,
                )
                for i in range(L)
            ],
            dt,
        )

    layers = {
        "attn_norm_scale": vecs("layers.{}.input_layernorm.weight"),
        "mlp_norm_scale": vecs("layers.{}.post_attention_layernorm.weight"),
        "wq": mats("layers.{}.self_attn.q_proj.weight"),
        "wk": mats("layers.{}.self_attn.k_proj.weight"),
        "wv": mats("layers.{}.self_attn.v_proj.weight"),
        "wo": mats("layers.{}.self_attn.o_proj.weight"),
        "bq": vecs("layers.{}.self_attn.q_proj.bias"),
        "bk": vecs("layers.{}.self_attn.k_proj.bias"),
        "bv": vecs("layers.{}.self_attn.v_proj.bias"),
        "w_router": mats("layers.{}.mlp.gate.weight"),
        "w_gate": experts("gate_proj"),
        "w_up": experts("up_proj"),
        "w_down": experts("down_proj"),
        "w_shared_up": mats("layers.{}.mlp.shared_expert.up_proj.weight"),
        "w_shared_gate": mats("layers.{}.mlp.shared_expert.gate_proj.weight"),
        "w_shared_down": mats("layers.{}.mlp.shared_expert.down_proj.weight"),
        "shared_expert_gate": mats("layers.{}.mlp.shared_expert_gate.weight"),
    }
    out: Dict[str, Any] = {
        "embed": jnp.asarray(to_np(sd[pre + "embed_tokens.weight"]), dt),
        "layers": layers,
        "final_norm_scale": jnp.asarray(to_np(sd[pre + "norm.weight"]), dt),
    }
    if not cfg.tie_word_embeddings:
        out["lm_head"] = jnp.asarray(to_np(sd["lm_head.weight"]).T, dt)
    return out
