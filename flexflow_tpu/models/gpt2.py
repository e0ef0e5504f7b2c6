"""GPT-2 model family (HF ``GPT2LMHeadModel``) — beyond the reference
zoo. Runs on the generic decoder: learned absolute positions, pre-LN
blocks with biases everywhere, gelu_tanh FFN, MHA, tied embeddings.
The converter splits HF's fused ``c_attn`` QKV projection and keeps
Conv1D's (in, out) orientation (HF GPT-2 Conv1D stores weights
UN-transposed, unlike nn.Linear — no ``linear_w`` flip here)."""
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
from .hf_utils import stack, to_np


def config(**kw) -> DecoderConfig:
    d: Dict[str, Any] = dict(
        vocab_size=50257,
        hidden_size=768,
        intermediate_size=3072,
        num_hidden_layers=12,
        num_attention_heads=12,
        num_key_value_heads=12,
        max_position_embeddings=1024,
        norm_type="layernorm",
        norm_bias=True,
        norm_eps=1e-5,
        positions="learned",
        learned_pos_offset=0,
        activation="gelu_tanh",
        glu=False,
        qkv_bias=True,
        out_bias=True,
        mlp_bias=True,
        tie_word_embeddings=True,
    )
    d.update(kw)
    return DecoderConfig(**d)


def gpt2_small(**kw) -> DecoderConfig:
    return config(**kw)


def gpt2_xl(**kw) -> DecoderConfig:
    d = dict(
        hidden_size=1600,
        intermediate_size=6400,
        num_hidden_layers=48,
        num_attention_heads=25,
        num_key_value_heads=25,
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


_HF_ACTS = {
    "gelu_new": "gelu_tanh",
    "gelu_pytorch_tanh": "gelu_tanh",
    "gelu_fast": "gelu_tanh",
    "gelu": "gelu",
    "relu": "relu",
    "silu": "silu",
}


def from_hf(hf: Dict[str, Any], **kw) -> DecoderConfig:
    mt = hf.get("model_type", "gpt2")
    if mt != "gpt2":
        raise NotImplementedError(
            f"model_type {mt!r} is not GPT-2"
        )
    # attention variants this engine does not implement must fail
    # loudly, not generate silently-wrong tokens
    for knob in ("scale_attn_by_inverse_layer_idx", "reorder_and_upcast_attn"):
        if hf.get(knob):
            raise NotImplementedError(f"GPT-2 {knob}=True is not supported")
    if not hf.get("scale_attn_weights", True):
        raise NotImplementedError(
            "GPT-2 scale_attn_weights=False is not supported"
        )
    act = hf.get("activation_function", "gelu_new")
    d = dict(
        vocab_size=hf["vocab_size"],
        hidden_size=hf["n_embd"],
        intermediate_size=hf.get("n_inner") or 4 * hf["n_embd"],
        num_hidden_layers=hf["n_layer"],
        num_attention_heads=hf["n_head"],
        num_key_value_heads=hf["n_head"],
        max_position_embeddings=hf["n_positions"],
        norm_eps=hf.get("layer_norm_epsilon", 1e-5),
        activation=_HF_ACTS.get(act, act),
    )
    d.update(kw)
    return config(**d)


def convert_hf_state_dict(
    sd: Dict[str, Any], cfg: DecoderConfig
) -> Dict[str, Any]:
    """HF ``GPT2LMHeadModel`` state dict → framework pytree."""
    from .hf_utils import layer_stackers

    dt = cfg.dtype
    D = cfg.hidden_size
    L = cfg.num_hidden_layers
    pre = "transformer." if "transformer.wte.weight" in sd else ""
    _, vecs = layer_stackers(sd, pre, L, dt)
    # Conv1D already stores (in, out) — the raw vecs stacker is exactly
    # right for matmul kernels too (no linear_w transpose)
    conv1d = vecs

    # one pass per layer: slice q|k|v out of the fused c_attn
    # (D, 3D) weight / (3D,) bias without re-converting it three times
    parts: Dict[str, list] = {k: [] for k in ("wq", "wk", "wv",
                                              "bq", "bk", "bv")}
    for i in range(L):
        w = to_np(sd[pre + f"h.{i}.attn.c_attn.weight"])
        b = to_np(sd[pre + f"h.{i}.attn.c_attn.bias"])
        for s, name in enumerate("qkv"):
            parts[f"w{name}"].append(w[:, s * D:(s + 1) * D])
            parts[f"b{name}"].append(b[s * D:(s + 1) * D])
    wq, wk, wv = (stack(parts[n], dt) for n in ("wq", "wk", "wv"))
    bq, bk, bv = (stack(parts[n], dt) for n in ("bq", "bk", "bv"))
    layers = {
        "attn_norm_scale": vecs("h.{}.ln_1.weight"),
        "attn_norm_bias": vecs("h.{}.ln_1.bias"),
        "mlp_norm_scale": vecs("h.{}.ln_2.weight"),
        "mlp_norm_bias": vecs("h.{}.ln_2.bias"),
        "wq": wq, "wk": wk, "wv": wv,
        "bq": bq, "bk": bk, "bv": bv,
        "wo": conv1d("h.{}.attn.c_proj.weight"),
        "bo": vecs("h.{}.attn.c_proj.bias"),
        "w_up": conv1d("h.{}.mlp.c_fc.weight"),
        "b_up": vecs("h.{}.mlp.c_fc.bias"),
        "w_down": conv1d("h.{}.mlp.c_proj.weight"),
        "b_down": vecs("h.{}.mlp.c_proj.bias"),
    }
    return {
        "embed": jnp.asarray(to_np(sd[pre + "wte.weight"]), dt),
        "pos_embed": jnp.asarray(to_np(sd[pre + "wpe.weight"]), dt),
        "layers": layers,
        "final_norm_scale": jnp.asarray(to_np(sd[pre + "ln_f.weight"]), dt),
        "final_norm_bias": jnp.asarray(to_np(sd[pre + "ln_f.bias"]), dt),
    }
