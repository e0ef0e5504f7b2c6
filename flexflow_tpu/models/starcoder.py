"""Starcoder / GPTBigCode model family (reference
``inference/models/starcoder.cc`` and ``python/flexflow/serve/models/
starcoder.py``): learned absolute positions, multi-query attention,
biased projections, gelu-tanh FFN, tied LM head. Runs on the generic
decoder (:mod:`.transformer`)."""
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
        vocab_size=49152,
        hidden_size=6144,
        intermediate_size=4 * 6144,
        num_hidden_layers=40,
        num_attention_heads=48,
        num_key_value_heads=1,  # multi-query
        max_position_embeddings=8192,
        norm_type="layernorm",
        norm_bias=True,
        norm_eps=1e-5,
        positions="learned",
        learned_pos_offset=0,
        activation="gelu_tanh",
        glu=False,
        parallel_block=False,
        qkv_bias=True,
        out_bias=True,
        mlp_bias=True,
        tie_word_embeddings=True,
    )
    d.update(kw)
    return DecoderConfig(**d)


def starcoder_15b(**kw) -> DecoderConfig:
    return config(**kw)


def tiny(**kw) -> DecoderConfig:
    d = dict(
        vocab_size=256,
        hidden_size=64,
        intermediate_size=256,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=1,
        max_position_embeddings=128,
    )
    d.update(kw)
    return config(**d)


def from_hf(hf: Dict[str, Any], **kw) -> DecoderConfig:
    d = dict(
        vocab_size=hf["vocab_size"],
        hidden_size=hf["n_embd"],
        intermediate_size=hf.get("n_inner") or 4 * hf["n_embd"],
        num_hidden_layers=hf["n_layer"],
        num_attention_heads=hf["n_head"],
        num_key_value_heads=1 if hf.get("multi_query", True) else hf["n_head"],
        max_position_embeddings=hf["n_positions"],
        norm_eps=hf.get("layer_norm_epsilon", 1e-5),
    )
    d.update(kw)
    return config(**d)


def convert_hf_state_dict(sd: Dict[str, Any], cfg: DecoderConfig) -> Dict[str, Any]:
    """HF ``GPTBigCodeForCausalLM`` state dict → framework pytree. The
    fused ``c_attn`` packs [H*dk query | KV*dk key | KV*dk value] columns."""
    dt = cfg.dtype
    pre = "transformer."
    L = cfg.num_hidden_layers
    H, KV, dk = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    qd, kvd = H * dk, KV * dk

    wq, wk, wv, bq, bk, bv = [], [], [], [], [], []
    for i in range(L):
        w = linear_w(sd, f"{pre}h.{i}.attn.c_attn.weight")  # (D, qd+2*kvd)
        b = to_np(sd[f"{pre}h.{i}.attn.c_attn.bias"])
        wq.append(w[:, :qd])
        wk.append(w[:, qd : qd + kvd])
        wv.append(w[:, qd + kvd :])
        bq.append(b[:qd])
        bk.append(b[qd : qd + kvd])
        bv.append(b[qd + kvd :])

    def vec(fmt):
        return stack([to_np(sd[pre + fmt.format(i)]) for i in range(L)], dt)

    layers = {
        "attn_norm_scale": vec("h.{}.ln_1.weight"),
        "attn_norm_bias": vec("h.{}.ln_1.bias"),
        "wq": stack(wq, dt),
        "wk": stack(wk, dt),
        "wv": stack(wv, dt),
        "bq": stack(bq, dt),
        "bk": stack(bk, dt),
        "bv": stack(bv, dt),
        "wo": stack([linear_w(sd, f"{pre}h.{i}.attn.c_proj.weight") for i in range(L)], dt),
        "bo": vec("h.{}.attn.c_proj.bias"),
        "mlp_norm_scale": vec("h.{}.ln_2.weight"),
        "mlp_norm_bias": vec("h.{}.ln_2.bias"),
        "w_up": stack([linear_w(sd, f"{pre}h.{i}.mlp.c_fc.weight") for i in range(L)], dt),
        "b_up": vec("h.{}.mlp.c_fc.bias"),
        "w_down": stack([linear_w(sd, f"{pre}h.{i}.mlp.c_proj.weight") for i in range(L)], dt),
        "b_down": vec("h.{}.mlp.c_proj.bias"),
    }
    return {
        "embed": jnp.asarray(to_np(sd[pre + "wte.weight"]), dt),
        "pos_embed": jnp.asarray(to_np(sd[pre + "wpe.weight"]), dt),
        "layers": layers,
        "final_norm_scale": jnp.asarray(to_np(sd[pre + "ln_f.weight"]), dt),
        "final_norm_bias": jnp.asarray(to_np(sd[pre + "ln_f.bias"]), dt),
    }
