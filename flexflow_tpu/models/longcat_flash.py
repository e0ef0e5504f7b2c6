"""LongCat-Flash model family (meituan-longcat, ``model_type:
longcat_flash``): a decoder whose layer is FIVE parts and not two. With
``x`` the residual row:

  x <- x + attn_0(norm_a0(x))
  h  = norm_f0(x)
  s  = moe(h)                      # the shortcut: read here ...
  x <- x + ffn_0(h)
  x <- x + attn_1(norm_a1(x))
  x <- x + ffn_1(norm_f1(x)) + s   # ... added here

* ``attn_j``: multi-head LATENT attention, DeepSeek-V3's line
  (models/deepseek_v3.py has the equations and the ONE copy of the
  code: ``latent_attention``), with plain rope (no YaRN) and two
  constant factors: the query, its rope channels too, times
  ``sqrt(hidden / q_lora_rank)`` (``mla_scale_q_lora``), and the
  normed compressed line ``c`` times ``sqrt(hidden / kv_lora_rank)``
  (``mla_scale_kv_lora``: on every head's keys AND values, the rope key
  unscaled). The factor on ``c`` is IN THE CACHED LINE: the pool holds
  ``sqrt(12) rmsnorm(c_raw)`` at the published widths, what the
  checkpoint's ``kv_b_proj`` reads.
* ``ffn_j``: a SiLU GLU of ``ffn_hidden_size``.
* ``moe``: a softmax router over ``n_routed_experts + zero_expert_num``
  outputs in float32; the ``moe_topk`` largest of ``p + offset`` are
  chosen (the offset chooses and does not weigh), a chosen output's
  weight is its own ``p`` times ``routed_scaling_factor``, not
  renormalised (``transformer.route_softmax_topk`` with ``offset``).
  The first ``n_routed_experts`` outputs are SiLU GLU experts of
  ``expert_ffn_hidden_size``; the others are ZERO-COMPUTE experts that
  return their input (``zero_expert_type: "identity"``). No shared
  expert. The routed half is ``transformer.routed_experts_ffn`` told
  the range of experts held (``experts_held``, the guide's usual cut:
  a pair on an expert not held, or on an identity output, enters no
  group); the identity half is ``h`` times the sum of the token's
  weights on identity outputs, computed whole by the token's own chip
  whatever range it holds.

The equations are written out in ``benchmarks/references/
longcat_flash.py``, which the tests hold this file to.

Serving only, on the paged path, through the engine's ordinary step
programs: the layer loop is :func:`transformer.run_layers` over ONE
kind of layer whose block takes its five groups' weights (``mla0``,
``mla1``, ``ffn0``, ``ffn1``, ``sparse``, each stacked by layer) and
keeps ``s`` a local across the second pair; the cache is the LATENT
pool with TWO lines a token and layer (``init_paged_kv_cache``:
leading axis ``2 * num_hidden_layers``, sublayer j of layer i at
``2 i + j``); the step takes the packed token axis and returns each
layer's real tokens per expert held and two counters a layer
(``step_counts``: ``moe_zero_pairs``, the real tokens' pairs on
identity outputs, and ``moe_routed_pairs``, all their pairs).

What it refuses, at construction: what models/deepseek_v3.py refuses,
for the same reasons (``validate_serving``). ``from_hf`` refuses by
name what nothing builds: an ``attention_method`` other than MLA, a
``zero_expert_type`` other than identity, a bias, an n-gram embedding
(``ngram_vocab_size_ratio``), a ``rope_scaling``. The release's
multi-token-prediction module is not in the published config and
nothing is built for it.

The selection offset keeps the checkpoint's own name,
``e_score_correction_bias`` (float32, one a router output; the release
registers it as zeros and its training moves it to keep the experts'
load level). It chooses and does not weigh. A softmax over 768 outputs
gives scores of 1e-3 to 5e-2, so this file's ``init_params`` draws it
at a tenth of a level score, ``0.1 / router outputs``: at a weight's
0.02 it would CHOOSE alone, the same outputs for every token.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..obs.sublayers import sublayer
from . import deepseek_v3 as _latent
from .deepseek_v3 import (  # noqa: F401  (the engine's serving protocol)
    FUSED_DECODE,
    PACKED_STEP,
    PAGE_POOLS,
    commit_kv,
    commit_kv_paged,
    copy_page_kv,
    gather_page_kv,
    init_kv_cache,
    kv_cache_pspecs,
    paged_kv_cache_pspecs,
    reorder_slots,
    reorder_slots_paged,
    scatter_page_kv,
    serve_step,
)
from .transformer import (
    DecoderConfig,
    _embed_in,
    _ffn,
    _head_logits,
    _norm,
    layer_weights,
    route_softmax_topk,
    routed_experts_ffn,
    run_layers,
    seeded_normal,
    yarn_inv_freq,
)

#: attention sublayers a layer: lines a token and layer in the pool
SUBLAYERS_A_LAYER = 2
GROUPS = ("mla0", "mla1", "ffn0", "ffn1", "sparse")


@dataclasses.dataclass(frozen=True)
class LongcatFlashConfig(DecoderConfig):
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    # constant factors on the query and on the normed compressed line
    # (sqrt(hidden / rank) where the published flags are true)
    mla_scale_q_lora: float = 1.0
    mla_scale_kv_lora: float = 1.0
    n_routed_experts: int = 512     # the router's outputs that are experts
    zero_expert_num: int = 256      # ... and those that return their input
    routed_scaling_factor: float = 6.0
    # the range of the router's outputs whose experts' weights are here
    # ((0, 0): every expert)
    experts_held: Tuple[int, int] = (0, 0)

    def __post_init__(self):
        super().__post_init__()
        lo, hi = self.held
        if not 0 <= lo < hi <= self.n_routed_experts:
            raise ValueError(
                f"experts_held {self.experts_held} of {self.n_routed_experts}")
        if self.qk_rope_head_dim % 2:
            raise ValueError(f"qk_rope_head_dim {self.qk_rope_head_dim} is odd")

    @property
    def held(self) -> Tuple[int, int]:
        return self.experts_held if any(self.experts_held) else (
            0, self.n_routed_experts)

    @property
    def router_outputs(self) -> int:
        return self.n_routed_experts + self.zero_expert_num

    @property
    def line_dim(self) -> int:
        """Values of one cached line: ``[c | kr]``."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def kinds(self) -> Tuple[Tuple[str, ...], ...]:
        return (("layer",),) * self.num_hidden_layers


def config(**kw) -> LongcatFlashConfig:
    d: Dict[str, Any] = dict(
        vocab_size=131072, hidden_size=6144, intermediate_size=12288,
        moe_intermediate_size=2048, num_hidden_layers=28,
        num_attention_heads=64, num_key_value_heads=64,
        max_position_embeddings=131072, norm_type="rmsnorm", norm_bias=False,
        norm_eps=1e-5, positions="rope", rope_theta=1e7,
        activation="silu", glu=True, tie_word_embeddings=False,
        num_experts_per_tok=12, moe_norm_topk=False,
        q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128,
        qk_rope_head_dim=64, mla_scale_q_lora=True, mla_scale_kv_lora=True,
    )
    d.update(kw)
    d.setdefault("head_dim_override",
                 d["qk_nope_head_dim"] + d["qk_rope_head_dim"])
    # the published flags are booleans: true is sqrt(hidden / rank)
    for flag, rank in (("mla_scale_q_lora", "q_lora_rank"),
                       ("mla_scale_kv_lora", "kv_lora_rank")):
        if isinstance(d[flag], bool):
            d[flag] = math.sqrt(d["hidden_size"] / d[rank]) if d[flag] else 1.0
    return LongcatFlashConfig(**d)


def tiny(**kw) -> LongcatFlashConfig:
    """CPU test size: three layers, 16 experts of 32 and 8 identity
    outputs, four chosen a token."""
    d = dict(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        moe_intermediate_size=32, num_hidden_layers=3,
        num_attention_heads=4, num_key_value_heads=4, q_lora_rank=24,
        kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, n_routed_experts=16, zero_expert_num=8,
        num_experts_per_tok=4, max_position_embeddings=512,
    )
    d.update(kw)
    return config(**d)


def from_hf(hf: Dict[str, Any], **kw) -> LongcatFlashConfig:
    """From the published ``config.json`` keys, as they are spelled
    (``num_layers``, ``ffn_hidden_size``, ``expert_ffn_hidden_size``,
    ``moe_topk``, ...).

    A benchmark configuration that holds a chip's SHARE of the experts
    gives ``n_routed_experts`` as the count held, the range as
    ``experts_held`` ([lo, hi]) and the router's width as
    ``router_outputs`` (experts and identity outputs together); the
    published file has neither key, its ``n_routed_experts`` are all
    held and its router has ``zero_expert_num`` outputs more.

    Rope pairing: as ``deepseek_v3.from_hf`` (the half-split layout;
    the checkpoint's adjacent pairs are the same model under a
    permutation of the rope columns of ``W_qb`` and ``W_kva``)."""
    def refuse(what, why):
        raise NotImplementedError(f"longcat_flash does not build {what}: {why}")

    if hf.get("ngram_vocab_size_ratio"):
        refuse(f"ngram_vocab_size_ratio {hf['ngram_vocab_size_ratio']!r}",
               "an n-gram embedding (its tables, emb_neighbor_num, "
               "emb_split_num) is no part of this family's embedding")
    if hf.get("attention_method", "MLA") != "MLA":
        refuse(f"attention_method {hf['attention_method']!r}",
               "only latent attention (MLA) is written")
    if hf.get("zero_expert_num") and hf.get("zero_expert_type") != "identity":
        refuse(f"zero_expert_type {hf.get('zero_expert_type')!r}",
               "a zero-compute expert returns its input (identity)")
    if hf.get("attention_bias", False):
        refuse("attention_bias true", "no projection has a bias")
    if hf.get("rope_scaling"):
        refuse(f"rope_scaling {hf['rope_scaling']!r}",
               "the rope table is the plain one")
    if hf.get("hidden_act", "silu") != "silu":
        refuse(f"hidden_act {hf['hidden_act']!r}", "the FFNs are SiLU GLUs")
    zero = int(hf.get("zero_expert_num", 0))
    held = tuple(hf.get("experts_held", (0, 0)))
    if any(held) and held[1] - held[0] != hf["n_routed_experts"]:
        raise ValueError(
            f"experts_held {held} is not the {hf['n_routed_experts']} "
            "experts n_routed_experts counts")
    d = dict(
        vocab_size=hf["vocab_size"], hidden_size=hf["hidden_size"],
        intermediate_size=hf["ffn_hidden_size"],
        moe_intermediate_size=hf["expert_ffn_hidden_size"],
        num_hidden_layers=hf["num_layers"],
        num_attention_heads=hf["num_attention_heads"],
        num_key_value_heads=hf["num_attention_heads"],
        max_position_embeddings=hf["max_position_embeddings"],
        norm_eps=float(hf.get("rms_norm_eps", 1e-5)),
        rope_theta=float(hf.get("rope_theta", 1e7)),
        q_lora_rank=hf["q_lora_rank"], kv_lora_rank=hf["kv_lora_rank"],
        qk_nope_head_dim=hf["qk_nope_head_dim"],
        qk_rope_head_dim=hf["qk_rope_head_dim"], v_head_dim=hf["v_head_dim"],
        mla_scale_q_lora=hf.get("mla_scale_q_lora", False),
        mla_scale_kv_lora=hf.get("mla_scale_kv_lora", False),
        n_routed_experts=(hf["router_outputs"] - zero if "router_outputs" in hf
                          else hf["n_routed_experts"]),
        zero_expert_num=zero,
        num_experts_per_tok=hf["moe_topk"],
        moe_norm_topk=bool(hf.get("norm_topk_prob", False)),
        routed_scaling_factor=float(hf.get("routed_scaling_factor", 1.0)),
        experts_held=held,
    )
    d.update(kw)
    return config(**d)


# ---------------------------------------------------------------------------
# Positions and the softmax's scale


def softmax_scale(cfg: LongcatFlashConfig) -> float:
    """``(qk_nope + qk_rope)^-0.5``: no YaRN temperature."""
    return cfg.head_dim ** -0.5


def rope_cos_sin(cfg: LongcatFlashConfig, positions):
    """(cos, sin), each positions.shape + (qk_rope_head_dim,), the plain
    table at ``rope_theta`` in the half-split layout
    :func:`transformer.apply_rope` takes."""
    inv = jnp.asarray(yarn_inv_freq(cfg.qk_rope_head_dim, cfg.rope_theta),
                      jnp.float32)
    angles = positions.astype(jnp.float32)[..., None] * inv
    angles = jnp.concatenate([angles, angles], axis=-1)
    return jnp.cos(angles), jnp.sin(angles)


# ---------------------------------------------------------------------------
# Parameters: five groups stacked by layer, and the ends


def _group_shapes(cfg: LongcatFlashConfig, group: str) -> Dict[str, Any]:
    D = cfg.hidden_size
    if group in ("mla0", "mla1"):
        return _latent._group_shapes(cfg, "mla")
    if group in ("ffn0", "ffn1"):
        F = cfg.intermediate_size
        return {"mlp_norm_scale": (D,), "w_gate": (D, F), "w_up": (D, F),
                "w_down": (F, D)}
    F, n = cfg.moe_intermediate_size, cfg.held[1] - cfg.held[0]
    return {"w_router": (D, cfg.router_outputs),
            "e_score_correction_bias": (cfg.router_outputs,),
            "w_gate": (n, D, F), "w_up": (n, D, F), "w_down": (n, F, D)}


@functools.partial(jax.jit, static_argnames=("cfg",))
def init_params(key, cfg: LongcatFlashConfig) -> Dict[str, Any]:
    """The family's own draw, one program: 0.02 (0.02 / sqrt(2 N) for
    ``wo`` and every ``w_down``); the selection offset in float32 at a
    tenth of a level score, ``0.1 / router outputs`` (module docstring)."""
    std = 0.02
    out_std = std / math.sqrt(2 * cfg.num_hidden_layers)
    keys = iter(jax.random.split(key, 64))

    def leaf(name, shape):
        if "norm_scale" in name:
            return jnp.ones(shape, cfg.dtype)
        if name == "e_score_correction_bias":
            return seeded_normal(next(keys), 0.1 / cfg.router_outputs,
                                 shape=shape, dtype=jnp.float32)
        scale = out_std if name in ("wo", "w_down") else std
        return seeded_normal(next(keys), scale, shape=shape, dtype=cfg.dtype)

    params = {
        "embed": leaf("embed", (cfg.vocab_size, cfg.hidden_size)),
        "final_norm_scale": leaf("final_norm_scale", (cfg.hidden_size,)),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = leaf("lm_head", (cfg.hidden_size, cfg.vocab_size))
    for group in GROUPS:
        params[group] = {
            name: leaf(name, (cfg.num_hidden_layers,) + shape)
            for name, shape in _group_shapes(cfg, group).items()}
    return params


def _shapes(cfg: LongcatFlashConfig):
    return jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))


def param_pspecs(cfg: LongcatFlashConfig, *, pipeline: bool = False):
    """Every weight whole on every device: one chip, or replicas
    (``validate_serving`` refuses ``model > 1``)."""
    return jax.tree.map(lambda _: P(), _shapes(cfg))


def num_params(cfg: LongcatFlashConfig) -> int:
    return sum(math.prod(a.shape) for a in jax.tree.leaves(_shapes(cfg)))


def active_params(cfg: LongcatFlashConfig, experts: int) -> int:
    """Parameters a token multiplies where ``experts`` of its
    ``num_experts_per_tok`` choices are real experts (the others cost
    nothing), the embedding's lookup not counted: every weight outside
    the expert stacks, the head, and that many experts a layer."""
    expert = 3 * cfg.hidden_size * cfg.moe_intermediate_size
    held = cfg.held[1] - cfg.held[0]
    return (num_params(cfg) - cfg.vocab_size * cfg.hidden_size
            + cfg.num_hidden_layers * (experts - held) * expert)


def step_counts(cfg: LongcatFlashConfig) -> Dict[str, Tuple[int, ...]]:
    """What a step returns in its cache that is no state (name ->
    shape, int32; ``models/lfm2_moe.py``), a layer: ``moe_counts`` the
    real tokens per expert held, ``moe_zero_pairs`` the real tokens'
    pairs on identity outputs, ``moe_routed_pairs`` all their pairs."""
    L = cfg.num_hidden_layers
    return {"moe_counts": (L, cfg.held[1] - cfg.held[0]),
            "moe_zero_pairs": (L,), "moe_routed_pairs": (L,)}


def expert_routing(cfg: LongcatFlashConfig) -> Tuple[int, Tuple[int, int], int]:
    """(The outputs a token chooses, the range of experts held, the
    router's outputs, identity ones included: what the choice is over
    and an expert's rows are reckoned from)."""
    return cfg.num_experts_per_tok, cfg.held, cfg.router_outputs


validate_serving = functools.partial(
    _latent.validate_serving, family="longcat_flash")


def init_paged_kv_cache(
    cfg: LongcatFlashConfig, num_pages: int, page_size: int, dtype=None,
    kv_quant: Optional[str] = None, extra_rows: int = 0,
):
    """The latent pool (``deepseek_v3.latent_pool``) with TWO lines a
    token and layer: sublayer j of layer i writes and reads index
    ``2 i + j`` of the leading axis."""
    return _latent.latent_pool(
        cfg, SUBLAYERS_A_LAYER * cfg.num_hidden_layers, num_pages, page_size,
        dtype, kv_quant, extra_rows)


# ---------------------------------------------------------------------------
# The routed block and the layer


def route(cfg: LongcatFlashConfig, p, h):
    """The router's choice for normed tokens h (N, D), over ALL its
    outputs: (outputs (N, k), weights (N, k))."""
    return route_softmax_topk(
        h, p["w_router"], cfg.num_experts_per_tok,
        norm_topk=cfg.moe_norm_topk, offset=p["e_score_correction_bias"],
        scaling=cfg.routed_scaling_factor)


def shortcut_moe(cfg, p, h, real, layer=None, kernels="xla"):
    """One layer's routed block over a flat token axis: h (N, D)
    normed, ``real`` (N,). ``p``: the layer's router, and the routed
    experts' weights of the layer — or, with ``layer``, of every layer,
    stacked. The experts held compute their part; the identity outputs'
    part, ``h`` times the sum of the token's weights on them (float32),
    is computed whole.
    -> (out (N, D), counts (experts held,), zero pairs, routed pairs)."""
    outputs, weights = route(cfg, p, h)
    k, held, routed = expert_routing(cfg)
    out, counts = routed_experts_ffn(
        h, real, outputs, weights, p["w_gate"], p["w_up"], p["w_down"],
        experts_held=held, routed=routed, layer=layer, kernels=kernels)
    with sublayer("moe.route"):
        zero = real[:, None] & (outputs >= cfg.n_routed_experts)
        weight = jnp.sum(jnp.where(zero, weights, 0.0), axis=-1)
        out = (out.astype(jnp.float32)
               + h.astype(jnp.float32) * weight[:, None]).astype(h.dtype)
        pairs = (jnp.sum(zero, dtype=jnp.int32),
                 jnp.sum(real, dtype=jnp.int32) * k)
    return out, counts, *pairs


def _layer_block(cfg, ctx, stacks, index, x, carried):
    """Layer ``index`` whole (module docstring): the shortcut ``s`` is
    a local of this block, from the row after the first attention to
    the sum after the second FFN."""
    attend = functools.partial(
        _latent.latent_attention, cfg, ctx, scale=softmax_scale(cfg),
        q_scale=cfg.mla_scale_q_lora, c_scale=cfg.mla_scale_kv_lora)
    B, T, D = x.shape

    p = layer_weights(stacks["mla0"], index)
    out, carried = attend(p, _norm(cfg, x, p["attn_norm_scale"], None),
                          carried, SUBLAYERS_A_LAYER * index)
    x = x + out
    p = layer_weights(stacks["ffn0"], index)
    h = _norm(cfg, x, p["mlp_norm_scale"], None)
    s, counts, zero, routed = shortcut_moe(
        cfg, layer_weights(stacks["sparse"], index,
                           whole=("w_gate", "w_up", "w_down")),
        h.reshape(B * T, D), ctx["real"], layer=index, kernels=ctx["kernels"])
    carried = dict(carried, **{
        name: jax.lax.dynamic_update_index_in_dim(carried[name], value, index, 0)
        for name, value in (("moe_counts", counts), ("moe_zero_pairs", zero),
                            ("moe_routed_pairs", routed))})
    x = x + _ffn(cfg, p, h)
    p = layer_weights(stacks["mla1"], index)
    out, carried = attend(p, _norm(cfg, x, p["attn_norm_scale"], None),
                          carried, SUBLAYERS_A_LAYER * index + 1)
    x = x + out
    p = layer_weights(stacks["ffn1"], index)
    x = x + _ffn(cfg, p, _norm(cfg, x, p["mlp_norm_scale"], None))
    return x + s.reshape(B, T, D), carried


# ---------------------------------------------------------------------------
# The step


@sublayer("glue")
def serve_step_paged(
    params: Dict[str, Any],
    cache: Dict[str, jnp.ndarray],
    tokens: jnp.ndarray,      # (R, C)
    positions: jnp.ndarray,   # (R, C); the scratch position is padding
    logits_idx: jnp.ndarray,  # (R,)
    mask, cache_positions,
    page_table: jnp.ndarray,  # (R, NP) int32
    *,
    cfg: LongcatFlashConfig,
    cache_len: int,
    all_logits: bool = False,
    kernels: str = "xla",
    pack: Optional[int] = None,
    **unsupported,
):
    """The engine's paged step (``deepseek_v3.serve_step_paged``'s
    contract: the packed token axis, causal by position with no mask
    array). The returned cache also holds ``step_counts``' entries
    (outputs, not inputs)."""
    if mask is not None or cache_positions is not None or any(
            v for v in unsupported.values()):
        _latent._no_latent_op()
    if pack is not None and all_logits:
        raise ValueError("a packed token axis returns one logits row a row")
    token_axis, ctx = _latent.step_context(
        cache, tokens, positions, page_table, cache_len, pack)
    with sublayer("attn.proj"):
        ctx["rope"] = rope_cos_sin(cfg, token_axis[1])
    ctx.update(kernels=kernels, q_start=positions[:, 0])
    x = _embed_in(cfg, params, *token_axis)
    carried = dict(cache, **{name: jnp.zeros(shape, jnp.int32)
                             for name, shape in step_counts(cfg).items()})
    x, new_cache = run_layers(
        cfg.kinds, {"layer": functools.partial(_layer_block, cfg, ctx)},
        {"layer": {group: params[group] for group in GROUPS}}, x, carried)
    return _head_logits(cfg, params, x, logits_idx, ctx["pack"],
                        all_logits), new_cache
