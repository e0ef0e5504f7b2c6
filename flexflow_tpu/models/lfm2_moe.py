"""LFM2-MoE model family (LiquidAI, ``model_type: lfm2_moe``; Hugging
Face ``Lfm2Moe*``): a decoder whose layers have one of TWO mixers, in
the order ``layer_types`` gives, and one of TWO feed-forward kinds:

* ``conv``: a gated short convolution. ``[B | C | z] = h W_in``,
  ``u = B * z``, a depthwise causal convolution of ``conv_L_cache``
  taps over ``u`` (values before a sequence's start are 0), then
  ``(C * c) W_out``. A decoding request carries the last
  ``conv_L_cache - 1`` values of ``u`` per conv layer: a fixed-size
  per-slot state, no K/V.
* ``full_attention``: grouped-query softmax attention with an RMSNorm
  over each head's own values on q and k BEFORE rope (rotate_half),
  head size 64 at the published widths.
* the first ``num_dense_layers`` layers have a dense gated FFN of
  ``intermediate_size``; the others ``num_experts`` small experts
  (``moe_intermediate_size``), ``num_experts_per_tok`` of them a token
  behind a sigmoid router whose selection offset (HF ``expert_bias``)
  chooses and does not weigh (``transformer.route_sigmoid_topk``).

The equations are written out in ``benchmarks/references/lfm2_moe.py``,
which the tests hold this file to.

Serving only, on the paged path (``kv_layout="paged"``), through the
engine's ordinary step programs:

* the layer loop is :func:`transformer.run_layers`: the static order as
  runs of one kind, a kind being (mixer group, FFN group); the K/V pool
  of the attention layers, the conv layers' states and the step's
  expert counts are its carry, updated in place
  (tests/test_chip_compile.py).
* the cache is the paged K/V pool of the ATTENTION layers only
  (``k``/``v``: (attention layers, pages+1, page, KV * d), a line's
  heads merged on the minor axis) plus per-SLOT
  state (``SLOT_STATE``): ``conv`` (conv layers, conv_L_cache - 1,
  slots, D), the newest values of ``u`` of each slot.
* what a step is handed decides everything (as
  ``models/minicpm_sala.py``): a row whose chunk starts at position 0
  starts from a zero state, the scratch position and padded rows update
  nothing, a chunk reads its first taps from the state and leaves the
  state of its last real token.
* the step takes the engine's PACKED token axis (``PACKED_STEP``):
  a row's tokens are contiguous there, so a tap is a shift along the
  axis that reads the state where it would cross the row's start; the
  attention call alone is at (slots, chunk).
* the sparse FFN is :func:`transformer.routed_experts_ffn`: grouped by
  expert, FLOPs of the routed pairs of real tokens. The step returns
  each sparse layer's real tokens per expert (``step_counts``), which
  the engine hands to the scheduler behind the sampled tokens.
  ``experts_held`` (a range of the router's outputs, all of them unless
  told) is the guide's usual cut: the weights hold that range only and
  the layer computes that range's part.

What it refuses, at construction (``validate_serving``), each because
the per-slot state has no such operation yet: prefix caching, SpecInfer
and beam search, ``kv_quant``, ``fused_decode``, ``kv_shard="context"``,
the dense layout, a mesh with ``model > 1``.

Weight names follow ``benchmarks/harness/model.py::make_params``' rule:
norm scales hold ``norm_scale`` (``attn_norm_scale`` is HF's
``operator_norm``, ``mlp_norm_scale`` its ``ffn_norm``,
``final_norm_scale`` its ``embedding_norm``), the projections that
write into the residual stream are ``wo`` (a conv layer's ``out_proj``
too) and ``w_down``; the selection offset is ``router_offset`` (HF
``expert_bias``: a name with ``bias`` would be drawn zero there).
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
from .transformer import (
    DecoderConfig,
    _embed_in,
    _ffn,
    _gather_attended,
    _head_logits,
    _layer_of,
    _mm,
    _norm,
    _pack_tokens,
    _page_lookup,
    _pallas_pools,
    _serve_attend,
    _spread_queries,
    _write_kv_lines,
    apply_rope,
    layer_weights,
    rope_freqs,
    route_sigmoid_topk,
    routed_experts_ffn,
    run_layers,
    seeded_normal,
)

CONV, ATTENTION = "conv", "full_attention"
# the cache entries that are per SLOT, not per page
SLOT_STATE = ("conv",)
FUSED_DECODE = ()
PACKED_STEP = True


@dataclasses.dataclass(frozen=True)
class Lfm2MoeConfig(DecoderConfig):
    layer_types: Tuple[str, ...] = ()
    conv_L_cache: int = 3
    num_dense_layers: int = 2
    num_experts: int = 64
    use_expert_bias: bool = True
    routed_scaling_factor: float = 1.0
    # the range of the router's outputs whose experts' weights are here
    # ((0, 0): all of them)
    experts_held: Tuple[int, int] = (0, 0)
    # slots of per-slot state where ``init_paged_kv_cache`` is not told
    # (``benchmarks/tools/fit.py``; the engine always tells)
    state_slots: int = 0

    def __post_init__(self):
        super().__post_init__()
        kinds = self.layer_types
        if len(kinds) != self.num_hidden_layers or set(kinds) - {CONV, ATTENTION}:
            raise ValueError(
                f"layer_types must name {self.num_hidden_layers} layers, "
                f"each {CONV!r} or {ATTENTION!r}: got {kinds}")
        lo, hi = self.held
        if not 0 <= lo < hi <= self.num_experts:
            raise ValueError(f"experts_held {self.experts_held} of {self.num_experts}")

    @property
    def held(self) -> Tuple[int, int]:
        return self.experts_held if any(self.experts_held) else (0, self.num_experts)

    @property
    def kinds(self) -> Tuple[Tuple[str, str], ...]:
        """A layer's kind: (mixer group, FFN group)."""
        return tuple(
            ("conv" if t == CONV else "attn",
             "dense" if i < self.num_dense_layers else "sparse")
            for i, t in enumerate(self.layer_types))

    def count(self, group: str) -> int:
        return sum(group in kind for kind in self.kinds)


def config(**kw) -> Lfm2MoeConfig:
    period = (CONV, CONV, ATTENTION, CONV)
    d: Dict[str, Any] = dict(
        vocab_size=65536, hidden_size=2048, intermediate_size=11776,
        moe_intermediate_size=1536, num_hidden_layers=40,
        num_attention_heads=32, num_key_value_heads=8, head_dim_override=64,
        max_position_embeddings=128000, norm_type="rmsnorm", norm_bias=False,
        norm_eps=1e-5, positions="rope", rope_theta=1e6, activation="silu",
        glu=True, tie_word_embeddings=True, num_experts_per_tok=4,
        moe_norm_topk=True,
    )
    d.update(kw)
    d.setdefault("layer_types", (period * 10)[: d["num_hidden_layers"]])
    return Lfm2MoeConfig(**d)


def tiny(**kw) -> Lfm2MoeConfig:
    """CPU test size: a dense conv layer, then both mixers with sparse
    FFNs, two conv layers in a row (a run of two)."""
    d = dict(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        moe_intermediate_size=32, num_hidden_layers=5, num_dense_layers=1,
        num_attention_heads=8, num_key_value_heads=4, head_dim_override=8,
        num_experts=8, num_experts_per_tok=2, max_position_embeddings=512,
        layer_types=(CONV, ATTENTION, CONV, CONV, ATTENTION),
    )
    d.update(kw)
    return config(**d)


def from_hf(hf: Dict[str, Any], **kw) -> Lfm2MoeConfig:
    """From the published ``config.json`` keys, as they are spelled.
    ``num_hidden_layers`` under ``len(layer_types)`` takes the first
    entries. ``experts_held`` ([lo, hi]) and ``head_dim`` are read where
    a benchmark configuration states them."""
    n = kw.get("num_hidden_layers", hf["num_hidden_layers"])
    if hf.get("conv_bias"):
        raise NotImplementedError("conv_bias: the published model has none")
    rope = hf.get("rope_parameters") or {}
    if rope.get("rope_type", "default") != "default":
        raise NotImplementedError(f"rope_type {rope['rope_type']!r}")
    heads = kw.get("num_attention_heads", hf["num_attention_heads"])
    hidden = kw.get("hidden_size", hf["hidden_size"])
    d = dict(
        vocab_size=hf["vocab_size"], hidden_size=hidden,
        intermediate_size=hf["intermediate_size"],
        moe_intermediate_size=hf["moe_intermediate_size"],
        num_hidden_layers=n, num_attention_heads=heads,
        num_key_value_heads=hf["num_key_value_heads"],
        head_dim_override=hf.get("head_dim") or hidden // heads,
        max_position_embeddings=hf["max_position_embeddings"],
        norm_eps=float(hf.get("norm_eps", 1e-5)),
        rope_theta=float(rope.get("rope_theta", hf.get("rope_theta", 1e6))),
        tie_word_embeddings=hf.get("tie_word_embeddings", True),
        layer_types=tuple(hf["layer_types"])[:n],
        conv_L_cache=hf["conv_L_cache"],
        num_dense_layers=min(hf["num_dense_layers"], n),
        num_experts=hf["num_experts"],
        num_experts_per_tok=hf["num_experts_per_tok"],
        moe_norm_topk=bool(hf.get("norm_topk_prob", True)),
        use_expert_bias=bool(hf.get("use_expert_bias", True)),
        routed_scaling_factor=float(hf.get("routed_scaling_factor", 1.0)),
        experts_held=tuple(hf.get("experts_held", (0, 0))),
        state_slots=int(hf.get("serving", {}).get("max_requests_per_batch", 0)),
    )
    d.update(kw)
    return config(**d)


# ---------------------------------------------------------------------------
# Parameters: four stacked groups (two mixers, two FFN kinds) and the ends


def _group_shapes(cfg: Lfm2MoeConfig, group: str) -> Dict[str, Tuple[int, ...]]:
    D = cfg.hidden_size
    H, KV, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    if group == "conv":
        return {"attn_norm_scale": (D,), "w_in": (D, 3 * D),
                "conv_w": (cfg.conv_L_cache, D), "wo": (D, D)}
    if group == "attn":
        return {"attn_norm_scale": (D,), "wq": (D, H * d), "wk": (D, KV * d),
                "wv": (D, KV * d), "q_norm_scale": (d,), "k_norm_scale": (d,),
                "wo": (H * d, D)}
    if group == "dense":
        F = cfg.intermediate_size
        return {"mlp_norm_scale": (D,), "w_gate": (D, F), "w_up": (D, F),
                "w_down": (F, D)}
    F, n = cfg.moe_intermediate_size, cfg.held[1] - cfg.held[0]
    shapes = {"mlp_norm_scale": (D,), "w_router": (D, cfg.num_experts),
              "w_gate": (n, D, F), "w_up": (n, D, F), "w_down": (n, F, D)}
    if cfg.use_expert_bias:
        shapes["router_offset"] = (cfg.num_experts,)
    return shapes


GROUPS = ("conv", "attn", "dense", "sparse")


def init_params(key, cfg: Lfm2MoeConfig) -> Dict[str, Any]:
    """The family's own draw: 0.02 (0.02 / sqrt(2 N) for ``wo`` and
    ``w_down``), the conv taps at 1 / sqrt(conv_L_cache) (PyTorch's
    depthwise init is of that order), a selection offset at 0.02."""
    std = 0.02
    out_std = std / math.sqrt(2 * cfg.num_hidden_layers)
    keys = iter(jax.random.split(key, 64))

    def leaf(name, shape):
        if "norm_scale" in name:
            return jnp.ones(shape, cfg.dtype)
        scale = {"wo": out_std, "w_down": out_std,
                 "conv_w": 1.0 / math.sqrt(cfg.conv_L_cache)}.get(name, std)
        dtype = jnp.float32 if name == "router_offset" else cfg.dtype
        return seeded_normal(next(keys), scale, shape=shape, dtype=dtype)

    params = {
        "embed": leaf("embed", (cfg.vocab_size, cfg.hidden_size)),
        "final_norm_scale": leaf("final_norm_scale", (cfg.hidden_size,)),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = leaf("lm_head", (cfg.hidden_size, cfg.vocab_size))
    for group in GROUPS:
        n = cfg.count(group)
        if n:
            params[group] = {
                name: leaf(name, (n,) + shape)
                for name, shape in _group_shapes(cfg, group).items()}
    return params


def param_pspecs(cfg: Lfm2MoeConfig, *, pipeline: bool = False) -> Dict[str, Any]:
    """Every weight whole on every device: one chip, or replicas
    (``validate_serving`` refuses ``model > 1``)."""
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    return jax.tree.map(lambda _: P(), shapes)


def num_params(cfg: Lfm2MoeConfig) -> int:
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    return sum(math.prod(a.shape) for a in jax.tree.leaves(shapes))


def step_counts(cfg: Lfm2MoeConfig) -> Dict[str, Tuple[int, ...]]:
    """What a step returns in its cache that is no state (name ->
    shape, int32): the engine takes these out of the cache a step
    returns and hands them to the scheduler behind the sampled tokens.
    ``moe_counts``: each sparse layer's real tokens per expert held."""
    return {"moe_counts": (cfg.count("sparse"), cfg.held[1] - cfg.held[0])}


def expert_routing(cfg: Lfm2MoeConfig) -> Tuple[int, Tuple[int, int], int]:
    """(The experts a token chooses, the range of experts held, the
    router's outputs): what the grouped expert matmuls' row tile is
    reckoned from (models/transformer.py ``expert_routing``)."""
    return cfg.num_experts_per_tok, cfg.held, cfg.num_experts


def validate_serving(cfg: Lfm2MoeConfig, serving, mesh, *, specinfer: bool = False) -> None:
    """The combinations this family's per-slot state cannot serve yet,
    refused at engine construction, each naming what is missing."""
    from ..core.mesh import MODEL_AXIS

    def refuse(what, why):
        raise NotImplementedError(f"lfm2_moe does not serve {what}: {why}")

    if serving.kv_layout != "paged":
        refuse(f"kv_layout={serving.kv_layout!r}",
               "only the paged step carries the conv layers' states beside "
               "the pool")
    if serving.prefix_caching:
        refuse("prefix_caching=True",
               "pages can be shared between requests, a conv layer's state "
               "at a page boundary is not kept with them (no state snapshot "
               "yet)")
    if specinfer:
        refuse("SpecInfer or beam search",
               "commit_kv / reorder_slots would have to roll the per-slot "
               "conv state back to the accepted token, and no snapshot is "
               "kept")
    if serving.kv_quant is not None:
        refuse(f"kv_quant={serving.kv_quant!r}",
               "the attention layers' pool has no scale rows in this "
               "family's cache")
    if serving.fused_decode:
        refuse(f"fused_decode={serving.fused_decode!r}",
               "the fused prologue knows one kind of layer and no q/k norm")
    if serving.kv_shard == "context":
        refuse(f"kv_shard={serving.kv_shard!r}",
               "the conv state of a row lives on one shard")
    if mesh.shape.get(MODEL_AXIS, 1) > 1:
        refuse("a mesh with model > 1",
               "neither the conv state nor the grouped expert matmul is "
               "sharded yet")


def _no_state_rollback(*_a, **_k):
    raise NotImplementedError(
        "lfm2_moe keeps per-slot conv state: committing, copying or "
        "reordering cache lines would need that state rolled back or moved "
        "with them, and no snapshot is kept")


commit_kv_paged = reorder_slots_paged = copy_page_kv = _no_state_rollback
gather_page_kv = scatter_page_kv = _no_state_rollback
init_kv_cache = kv_cache_pspecs = serve_step = _no_state_rollback
commit_kv = reorder_slots = _no_state_rollback


# ---------------------------------------------------------------------------
# Cache: the attention layers' paged pool, the conv layers' per-slot state


def init_paged_kv_cache(
    cfg: Lfm2MoeConfig, num_pages: int, page_size: int, dtype=None,
    kv_quant: Optional[str] = None, extra_rows: int = 0, *,
    num_slots: Optional[int] = None, cache_len: Optional[int] = None,
):
    """``k``/``v``: (attention layers, num_pages+1, page_size, KV * d),
    a line's heads MERGED on the minor axis (at head size 64 the
    device lays a (..., page, KV, 64) array out with the page on its
    lanes and a step would re-lay the pool for the kernel and back:
    serve/kernels._ragged_paged_attention), row ``num_pages`` the
    scratch page; ``conv``: (conv layers, conv_L_cache - 1, slots, D),
    each slot's newest ``u``, oldest first (slots and D minor: the
    layout the device gives it is the one it is written in)."""
    if kv_quant is not None or extra_rows:
        raise NotImplementedError(
            "lfm2_moe's pool is neither quantized nor row-sharded "
            "(validate_serving refuses kv_quant and kv_shard='context')")
    slots = num_slots or cfg.state_slots
    if not slots:
        raise ValueError(
            "lfm2_moe keeps per-slot state: init_paged_kv_cache needs "
            "num_slots (the engine passes its own)")
    dt = dtype or cfg.dtype
    pool = (cfg.count("attn"), num_pages + 1, page_size,
            cfg.num_key_value_heads * cfg.head_dim)
    return {
        "k": jnp.zeros(pool, dt), "v": jnp.zeros(pool, dt),
        "conv": jnp.zeros((cfg.count("conv"), cfg.conv_L_cache - 1, slots,
                           cfg.hidden_size), dt),
    }


def paged_kv_cache_pspecs(cfg: Lfm2MoeConfig = None, *, pipeline: bool = False,
                          kv_quant: Optional[str] = None,
                          kv_shard: Optional[str] = None):
    return {name: P() for name in ("k", "v") + SLOT_STATE}


# ---------------------------------------------------------------------------
# The blocks


def short_conv(u, taps, state, row, col, count, fresh, place):
    """The depthwise causal convolution of one step over the carried
    state, on a flat token axis.

    u (N, D): the step's tokens, a row's tokens contiguous and in
    order; ``row`` / ``col`` (N,): the slot each token belongs to and
    its column in the row's chunk; ``taps`` (L, D), tap L-1 on the
    token itself; ``state`` (L-1, R, D): each slot's last L-1 values of
    ``u``, oldest first; ``count`` (R,): the row's real tokens;
    ``fresh`` (R,): rows that start from zeros; ``place`` (R, C): the
    place on the token axis of each (row, column).

    Returns (c (N, D) float32, new state): the state after each row's
    last real token; a row with none keeps its own bitwise."""
    N, D = u.shape
    L = taps.shape[0]
    s0 = jnp.where(fresh[None, :, None], jnp.zeros((), state.dtype), state)
    w = taps.astype(jnp.float32)
    c = w[L - 1] * u.astype(jnp.float32)
    for back in range(1, L):
        # the token ``back`` places before: inside the row's chunk a
        # shift along the axis, across its start the carried state
        shifted = jnp.pad(u, ((back, 0), (0, 0)))[:N]
        carried = s0[jnp.clip(col + (L - 1 - back), 0, L - 2), row]
        prev = jnp.where((col >= back)[:, None], shifted, carried.astype(u.dtype))
        c = c + w[L - 1 - back] * prev.astype(jnp.float32)
    # what the next chunk reads: the last L-1 of [state, the real tokens]
    new = []
    for i in range(L - 1):
        at = count + i - (L - 1)               # column of the i-th newest-to-be
        from_u = u[jnp.take_along_axis(
            place, jnp.maximum(at, 0)[:, None], axis=1)[:, 0]]
        from_s = jnp.take_along_axis(
            s0, jnp.clip(count + i, 0, L - 2)[None, :, None], axis=0)[0]
        new.append(jnp.where((at >= 0)[:, None], from_u.astype(state.dtype), from_s))
    new = jnp.stack(new, axis=0)
    return c, jnp.where((count > 0)[None, :, None], new, state)


def _conv_block(cfg, ctx, stack, index, x, carried):
    p = layer_weights(stack, index)
    B, T, D = x.shape
    h = _norm(cfg, x, p["attn_norm_scale"], None)
    with sublayer("mixer"):
        b, c_gate, z = jnp.split(_mm(h, p["w_in"]), 3, axis=-1)
        u = (b * z).reshape(B * T, D)
        c, state = short_conv(
            u, p["conv_w"], _layer_of(carried["conv"], index), ctx["row"],
            ctx["col"], ctx["q_len"], ctx["fresh"], ctx["place"])
        y = c_gate * c.astype(x.dtype).reshape(B, T, D)
        carried = dict(carried, conv=jax.lax.dynamic_update_index_in_dim(
            carried["conv"], state, index, 0))
        out = _mm(y, p["wo"])
    return x + out, carried


def _attn_block(cfg, ctx, stack, index, x, carried):
    from ..serve import kernels as _pk

    p = layer_weights(stack, index)
    B, T, _ = x.shape
    H, KV, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    h = _norm(cfg, x, p["attn_norm_scale"], None)
    with sublayer("attn.proj"):
        q = _norm(cfg, _mm(h, p["wq"]).reshape(B, T, H, d), p["q_norm_scale"], None)
        k = _norm(cfg, _mm(h, p["wk"]).reshape(B, T, KV, d), p["k_norm_scale"], None)
        v = _mm(h, p["wv"]).reshape(B, T, KV, d)
        q, k = apply_rope(q, *ctx["rope"]), apply_rope(k, *ctx["rope"])
    kp, vp, _, _ = _write_kv_lines(
        carried["k"], carried["v"], None, None, index, ctx["phys"], ctx["off"],
        k.reshape(B, T, KV * d), v.reshape(B, T, KV * d), None)
    with sublayer("attn.core"):
        q = _spread_queries(q, ctx["pack"])                   # (R, C, H, d)
        if ctx["kernels"] == "pallas":
            k_rows, v_rows, kw = _pallas_pools(kp, vp, None, None, index)
            o = _pk.ragged_paged_attention(
                q, k_rows, v_rows, ctx["page_table"], ctx["mask"],
                row_offset=kw["row_offset"], q_len=ctx["q_len"],
                work=ctx["work"])
        else:
            k_virt, v_virt = (
                _pk.gather_pages(_layer_of(pool, index), ctx["page_table"])
                for pool in (kp, vp))
            split = k_virt.shape[:2] + (KV, d)
            o = _serve_attend(cfg, q, k_virt.reshape(split),
                              v_virt.reshape(split), None, ctx["mask"])
        o = _gather_attended(o, ctx["pack"])
    with sublayer("attn.proj"):
        out = _mm(o, p["wo"])
    return x + out, dict(carried, k=kp, v=vp)


def _dense_block(cfg, ctx, stack, index, x, carried):
    p = layer_weights(stack, index)
    return x + _ffn(cfg, p, _norm(cfg, x, p["mlp_norm_scale"], None)), carried


def sparse_ffn(cfg, p, h, real, layer=None, kernels="xla"):
    """One sparse layer's FFN over a flat token axis: h (N, D) normed,
    ``real`` (N,). ``p``: the layer's router weights, and the experts'
    weights of the layer — or, with ``layer``, of every layer, stacked.
    -> (out (N, D), counts (experts held,))."""
    experts, weights = route_sigmoid_topk(
        h, p["w_router"], p.get("router_offset"), cfg.num_experts_per_tok,
        norm_topk=cfg.moe_norm_topk, scaling=cfg.routed_scaling_factor)
    _, held, routed = expert_routing(cfg)
    return routed_experts_ffn(
        h, real, experts, weights, p["w_gate"], p["w_up"], p["w_down"],
        experts_held=held, routed=routed, layer=layer, kernels=kernels)


def _sparse_block(cfg, ctx, stack, index, x, carried):
    p = layer_weights(stack, index, whole=("w_gate", "w_up", "w_down"))
    B, T, D = x.shape
    h = _norm(cfg, x, p["mlp_norm_scale"], None).reshape(B * T, D)
    out, counts = sparse_ffn(cfg, p, h, ctx["real"], layer=index,
                             kernels=ctx["kernels"])
    carried = dict(carried, moe_counts=jax.lax.dynamic_update_index_in_dim(
        carried["moe_counts"], counts, index, 0))
    return x + out.reshape(B, T, D), carried


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
    cfg: Lfm2MoeConfig,
    cache_len: int,
    all_logits: bool = False,
    kernels: str = "xla",
    pack: Optional[int] = None,
    **unsupported,
):
    """The engine's paged step (models/transformer.serve_step_paged's
    contract, its packed token axis included) over the layer order. A
    row's real positions are its first columns, consecutive; a row
    whose first position is 0 starts from zero conv state (module
    docstring). The returned cache also holds ``moe_counts`` (sparse
    layers, experts held) int32: this step's real tokens per expert
    (``step_counts``: an output, not an input)."""
    if mask is not None or cache_positions is not None or any(
            v for v in unsupported.values()):
        _no_state_rollback()
    if pack is not None and all_logits:
        raise ValueError("a packed token axis returns one logits row a row")
    from ..serve.kernels import paged_serve_mask, real_query_lengths, step_work

    R, C = tokens.shape
    ps = cache["k"].shape[2]
    q_len = real_query_lengths(positions, cache_len)  # real columns lead
    cols = jnp.arange(C, dtype=jnp.int32)
    if pack is None:
        token_axis = (tokens, positions)
        phys, off = _page_lookup(page_table, positions, ps)
        place = jnp.arange(R * C, dtype=jnp.int32).reshape(R, C)
        row = jnp.repeat(jnp.arange(R, dtype=jnp.int32), C)
        col = jnp.tile(cols, R)
        real = (cols[None] < q_len[:, None]).reshape(-1)
        pack_idx = None
    else:
        (*token_axis, phys, off), pack_idx = _pack_tokens(
            tokens, positions, q_len, page_table, ps, cache_len, pack)
        place, flat = pack_idx
        row, col = flat // C, flat % C
        real = token_axis[1][0] < cache_len
    with sublayer("attn.proj"):
        rope = rope_freqs(cfg, token_axis[1])
    ctx = dict(
        rope=rope, phys=phys, off=off,
        page_table=page_table, kernels=kernels, q_len=q_len, pack=pack_idx,
        mask=paged_serve_mask(None, positions, page_table.shape[1], ps, cache_len),
        work=(step_work(positions, q_len, ps, page_table.shape[1])
              if kernels == "pallas" else None),
        row=row, col=col, real=real, place=place,
        fresh=(q_len > 0) & (positions[:, 0] == 0),
    )
    x = _embed_in(cfg, params, *token_axis)
    carried = dict(cache, **{name: jnp.zeros(shape, jnp.int32)
                             for name, shape in step_counts(cfg).items()})
    blocks = {
        name: functools.partial(fn, cfg, ctx)
        for name, fn in (("conv", _conv_block), ("attn", _attn_block),
                         ("dense", _dense_block), ("sparse", _sparse_block))}
    x, new_cache = run_layers(cfg.kinds, blocks, params, x, carried)
    return _head_logits(cfg, params, x, logits_idx, pack_idx,
                        all_logits), new_cache
