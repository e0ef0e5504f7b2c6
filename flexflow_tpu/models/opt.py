"""OPT model family (reference ``inference/models/opt.cc`` and
``python/flexflow/serve/models/opt.py``): decoder-only with learned
positional embeddings at offset 2, pre-LayerNorm blocks, biased MHA and
ReLU FFN, tied LM head. Runs on the generic decoder
(:mod:`.transformer`)."""
from __future__ import annotations

from typing import Any, Dict

import jax.numpy as jnp

from . import transformer
from .transformer import (  # noqa: F401  (engine serving protocol)
    DecoderConfig,
    FUSED_DECODE,
    PACKED_STEP,
    commit_kv,
    commit_kv_paged,
    copy_page_kv,
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
)
from .hf_utils import linear_w, stack, to_np


def config(**kw) -> DecoderConfig:
    d: Dict[str, Any] = dict(
        vocab_size=50272,
        hidden_size=768,
        intermediate_size=3072,
        num_hidden_layers=12,
        num_attention_heads=12,
        num_key_value_heads=12,
        max_position_embeddings=2048,
        norm_type="layernorm",
        norm_bias=True,
        norm_eps=1e-5,
        positions="learned",
        learned_pos_offset=2,
        activation="relu",
        glu=False,
        parallel_block=False,
        qkv_bias=True,
        out_bias=True,
        mlp_bias=True,
        tie_word_embeddings=True,
    )
    d.update(kw)
    return DecoderConfig(**d)


def opt_125m(**kw) -> DecoderConfig:
    return config(**kw)


def opt_6_7b(**kw) -> DecoderConfig:
    d = dict(
        hidden_size=4096,
        intermediate_size=16384,
        num_hidden_layers=32,
        num_attention_heads=32,
        num_key_value_heads=32,
    )
    d.update(kw)
    return config(**d)


def tiny(**kw) -> DecoderConfig:
    d = dict(
        vocab_size=256,
        hidden_size=64,
        intermediate_size=128,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=4,
        max_position_embeddings=128,
    )
    d.update(kw)
    return config(**d)


def from_hf(hf: Dict[str, Any], **kw) -> DecoderConfig:
    assert hf.get("word_embed_proj_dim", hf["hidden_size"]) == hf["hidden_size"], (
        "OPT word_embed_proj_dim != hidden_size (350m-style projection) "
        "is not supported"
    )
    assert hf.get("do_layer_norm_before", True), "post-norm OPT not supported"
    d = dict(
        vocab_size=hf["vocab_size"],
        hidden_size=hf["hidden_size"],
        intermediate_size=hf["ffn_dim"],
        num_hidden_layers=hf["num_hidden_layers"],
        num_attention_heads=hf["num_attention_heads"],
        num_key_value_heads=hf["num_attention_heads"],
        max_position_embeddings=hf["max_position_embeddings"],
        activation=hf.get("activation_function", "relu"),
    )
    d.update(kw)
    return config(**d)


def convert_hf_state_dict(sd: Dict[str, Any], cfg: DecoderConfig) -> Dict[str, Any]:
    """HF ``OPTForCausalLM`` state dict → framework pytree."""
    dt = cfg.dtype
    pre = "model.decoder."
    if pre + "embed_tokens.weight" not in sd and "decoder.embed_tokens.weight" in sd:
        pre = "decoder."
    L = cfg.num_hidden_layers

    def per_layer(fmt, conv):
        return [conv(sd, pre + fmt.format(i)) for i in range(L)]

    layers = {
        "attn_norm_scale": stack(
            per_layer("layers.{}.self_attn_layer_norm.weight", lambda s, n: to_np(s[n])), dt
        ),
        "attn_norm_bias": stack(
            per_layer("layers.{}.self_attn_layer_norm.bias", lambda s, n: to_np(s[n])), dt
        ),
        "wq": stack(per_layer("layers.{}.self_attn.q_proj.weight", linear_w), dt),
        "wk": stack(per_layer("layers.{}.self_attn.k_proj.weight", linear_w), dt),
        "wv": stack(per_layer("layers.{}.self_attn.v_proj.weight", linear_w), dt),
        "wo": stack(per_layer("layers.{}.self_attn.out_proj.weight", linear_w), dt),
        "bq": stack(per_layer("layers.{}.self_attn.q_proj.bias", lambda s, n: to_np(s[n])), dt),
        "bk": stack(per_layer("layers.{}.self_attn.k_proj.bias", lambda s, n: to_np(s[n])), dt),
        "bv": stack(per_layer("layers.{}.self_attn.v_proj.bias", lambda s, n: to_np(s[n])), dt),
        "bo": stack(per_layer("layers.{}.self_attn.out_proj.bias", lambda s, n: to_np(s[n])), dt),
        "mlp_norm_scale": stack(
            per_layer("layers.{}.final_layer_norm.weight", lambda s, n: to_np(s[n])), dt
        ),
        "mlp_norm_bias": stack(
            per_layer("layers.{}.final_layer_norm.bias", lambda s, n: to_np(s[n])), dt
        ),
        "w_up": stack(per_layer("layers.{}.fc1.weight", linear_w), dt),
        "b_up": stack(per_layer("layers.{}.fc1.bias", lambda s, n: to_np(s[n])), dt),
        "w_down": stack(per_layer("layers.{}.fc2.weight", linear_w), dt),
        "b_down": stack(per_layer("layers.{}.fc2.bias", lambda s, n: to_np(s[n])), dt),
    }
    return {
        "embed": jnp.asarray(to_np(sd[pre + "embed_tokens.weight"]), dt),
        "pos_embed": jnp.asarray(to_np(sd[pre + "embed_positions.weight"]), dt),
        "layers": layers,
        "final_norm_scale": jnp.asarray(to_np(sd[pre + "final_layer_norm.weight"]), dt),
        "final_norm_bias": jnp.asarray(to_np(sd[pre + "final_layer_norm.bias"]), dt),
    }
