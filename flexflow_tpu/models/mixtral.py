"""Mixtral model family — sparse-MoE serving BEYOND the reference zoo
(the reference serves dense decoders only, ``inference/models/*.cc``;
its MoE support is the training-side expert ops). Runs on the generic
decoder (:mod:`.transformer`) with ``num_local_experts`` > 0: a linear
router takes the top-k experts per token (softmax over the selected k,
HF ``MixtralSparseMoeBlock`` semantics, ``transformer.route_softmax_topk``).

Which step computes the experts how (``transformer.routes_tokens``):
the PAGED serving step on one device, at a width whose (token, expert)
pairs are 16 an expert or more (every rung of the mixed step),
routes its tokens (``transformer.routed_experts_ffn``: the pairs of the
real tokens sorted by expert, grouped matmuls over them, serve/kernels
``ff_moe_grouped_*`` on the Pallas path, at the row tile the static
pairs give, serve/kernels ``grouped_tile``: 32 rows at the admission
rung's 64 pairs an expert, 128 from the 512 rung's 128 on, each
expert's weight block copied in while the expert before it computes,
so a call costs the larger of its weights' read and its matmuls; the
FLOPs and the weights read follow the tokens, and each step returns
its tokens per expert, ``step_counts``). The all-expert einsum
(``transformer._moe_ffn``) stays for the narrow C=1 step (a few rows
read every expert either way, and the einsum is the faster step by 3%:
PERF.md, PR 36), for
training (``forward``), the dense-layout ``serve_step`` and a mesh of
more than one device, where the expert weights shard over the
``expert`` mesh axis with Megatron TP inside each expert: the grouped
Pallas calls under GSPMD need a ``shard_map`` over the experts held
(ROADMAP B0/B1).

Architecture = LLaMA attention (RoPE, GQA, RMSNorm, no biases) + the
MoE FFN; weight conversion from HF ``MixtralForCausalLM``.
"""
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
        vocab_size=32000,
        hidden_size=4096,
        intermediate_size=14336,
        num_hidden_layers=32,
        num_attention_heads=32,
        num_key_value_heads=8,
        max_position_embeddings=4096,
        norm_type="rmsnorm",
        norm_bias=False,
        norm_eps=1e-5,
        positions="rope",
        rope_theta=1e6,
        activation="silu",
        glu=True,
        qkv_bias=False,
        out_bias=False,
        mlp_bias=False,
        tie_word_embeddings=False,
        num_local_experts=8,
        num_experts_per_tok=2,
    )
    d.update(kw)
    return DecoderConfig(**d)


def mixtral_8x7b(**kw) -> DecoderConfig:
    return config(**kw)


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
    )
    d.update(kw)
    return config(**d)


def from_hf(hf: Dict[str, Any], **kw) -> DecoderConfig:
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
        norm_eps=hf.get("rms_norm_eps", 1e-5),
        rope_theta=hf.get("rope_theta", 1e6),
        num_local_experts=hf.get("num_local_experts", 8),
        num_experts_per_tok=hf.get("num_experts_per_tok", 2),
        # early mixtral-8x7b configs ship sliding_window=4096; the
        # generic decoder enforces it (null/absent = full causal)
        sliding_window=hf.get("sliding_window") or 0,
        tie_word_embeddings=hf.get("tie_word_embeddings", False),
    )
    d.update(kw)
    return config(**d)


def convert_hf_state_dict(
    sd: Dict[str, Any], cfg: DecoderConfig
) -> Dict[str, Any]:
    """HF ``MixtralForCausalLM`` state dict → framework pytree. HF per-
    expert names w1 (gate), w2 (down), w3 (up) map onto the generic
    decoder's glu layout: w_gate ← w1, w_down ← w2, w_up ← w3, each
    stacked (L, E, in, out)."""
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
                            pre + f"layers.{i}.block_sparse_moe."
                                  f"experts.{e}.{which}.weight",
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
        "w_router": mats("layers.{}.block_sparse_moe.gate.weight"),
        "w_gate": experts("w1"),
        "w_up": experts("w3"),
        "w_down": experts("w2"),
    }
    out: Dict[str, Any] = {
        "embed": jnp.asarray(to_np(sd[pre + "embed_tokens.weight"]), dt),
        "layers": layers,
        "final_norm_scale": jnp.asarray(to_np(sd[pre + "norm.weight"]), dt),
    }
    if not cfg.tie_word_embeddings:
        out["lm_head"] = jnp.asarray(to_np(sd["lm_head.weight"]).T, dt)
    return out
