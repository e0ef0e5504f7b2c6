"""Laguna model family (poolside, ``model_type: laguna``:
``Laguna-XS.2``, ``Laguna-S-2.1``): a sparse decoder whose layers attend
in one of TWO ways, in the order ``layer_types`` gives (published: one
full layer, three window layers, over and over), with a head count a
KIND (``num_attention_heads_per_layer``: 48 query heads on a full layer
and 64 on a window layer of XS.2, over the same 8 K/V heads of 128).

* both kinds: ``q``, ``k`` RMS-normed a head, roped, causal softmax
  attention at ``head_dim^-0.5``, each head's output times ITS gate
  ``sigmoid(h W_g)`` (a (D, heads) projection: one scalar a head and
  token), then ``W_o``.
* ``full``: every earlier position; rope on the first
  ``partial_rotary_factor`` of a head by YaRN frequencies
  (``transformer.yarn_inv_freq``), cos and sin times
  ``attention_factor``.
* ``window``: a query's own position and the ``sliding_window - 1``
  before it; plain rope over the whole head.
* the FFN is a dense SiLU GLU where ``mlp_layer_types`` says so (the
  leading layers) and elsewhere ``num_experts`` small SiLU GLU experts,
  ``num_experts_per_tok`` a token behind a sigmoid router with a
  selection offset (``transformer.route_sigmoid_topk``: the chosen
  experts' own scores over their sum, times
  ``moe_routed_scaling_factor``), beside ONE shared expert, ungated.

The equations are written out in ``benchmarks/references/laguna.py``,
which the tests hold this file to.

ASSUMED: the published ``config.json`` fixes every shape and leaves
four element-wise choices open; each is ONE function here, so that a
correction is a line: the gate's squashing function
(:func:`head_gate`: sigmoid), the norm of q and k a head
(:func:`normed_heads`), the router's rule (:func:`route`: sigmoid
scores, a selection offset, renormalised), the shared expert without a
gate of its own (:func:`shared_expert`). Also: the FFNs' SiLU, the
``attention_factor`` on cos and sin with the softmax scale unchanged,
the window's ``sliding_window`` lines with the query's own among them.

Serving only, on the paged path (``kv_layout="paged"``), through the
engine's ordinary step programs:

* the layer loop is :func:`transformer.run_layers` over kinds
  (``full``, ``dense``), (``window``, ``sparse``) and (``full``,
  ``sparse``); the attention weights are stacked by KIND (their shapes
  differ by kind), the FFNs' by theirs, each in layer order.
* the cache is TWO CLASSES of page, as models/smallthinker.py's, and
  through the same code: ``page_classes``, the pools, ``step_context``
  (the tables, masks and places a class) and ``attend_class`` (the
  lines' write and the kernel call, ``ff_ragged_paged_c<C>`` at a group
  of 6 query heads a K/V head padded to 8, ``ff_ragged_paged_c<C>_win``
  at a group of 8) are that module's, imported.
* the step takes the engine's PACKED token axis (``PACKED_STEP``) and
  returns each sparse layer's real tokens per expert held
  (``step_counts``).
* ``experts_held`` (a range of the router's outputs, all of them unless
  told) is the guide's usual cut: the weights hold that range only and
  the layer computes that range's part, the shared expert whole.

What it refuses, at construction (``validate_serving``): what
SmallThinker refuses, for the same reasons.

Weight names follow ``benchmarks/harness/model.py::make_params``' rule:
norm scales hold ``norm_scale`` (drawn one), the selection offset is
``router_bias`` (a name with ``bias``: drawn zero), the projections
that write into the residual stream are ``wo`` and ``w_down``; the
gate's projection is ``wg``. Groups ``full`` and ``window`` (attention,
by kind), ``dense`` and ``sparse`` (the FFNs; the shared expert a
nested ``shared``).
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..obs.sublayers import sublayer
from . import smallthinker as _classes
from .smallthinker import (  # the two classes of page: ONE copy
    FULL,
    PAGE_POOLS,  # noqa: F401  (the engine reads them off this module)
    WINDOW,
    _one_table_only,
    attend_class,
    init_paged_kv_cache,  # noqa: F401
    page_classes,  # noqa: F401
    paged_kv_cache_pspecs,  # noqa: F401
    step_context,
)
from .transformer import (
    DecoderConfig,
    _embed_in,
    _ffn,
    _head_logits,
    _mm,
    _norm,
    apply_rope,
    layer_weights,
    route_sigmoid_topk,
    routed_experts_ffn,
    run_layers,
    seeded_normal,
    yarn_inv_freq,
)

DENSE, SPARSE = "dense", "sparse"
SLOT_STATE = ()
FUSED_DECODE = ()
PACKED_STEP = True


@dataclasses.dataclass(frozen=True)
class LagunaConfig(DecoderConfig):
    # a layer's attention and its FFN, in layer order
    layer_kinds: Tuple[str, ...] = ()    # FULL | WINDOW
    ffn_kinds: Tuple[str, ...] = ()      # DENSE | SPARSE
    # query heads of a layer of each kind (``num_attention_heads``, the
    # published key, is the full layers')
    full_heads: int = 48
    window_heads: int = 64
    num_experts: int = 256               # the router's outputs
    routed_scaling_factor: float = 2.5
    # the range of the router's outputs whose experts' weights are here
    # ((0, 0): all of them)
    experts_held: Tuple[int, int] = (0, 0)
    # the full layers' rope (``rope_theta`` / ``rotary_pct`` are the
    # window layers'): YaRN over the rotated channels; factor 1: plain
    full_rope_theta: float = 500000.0
    full_rotary_pct: float = 0.5
    full_rope_factor: float = 64.0
    full_rope_original_max: int = 4096
    full_rope_beta_fast: float = 64.0
    full_rope_beta_slow: float = 1.0
    full_rope_attention_factor: float = 1.4158883083359672

    def __post_init__(self):
        super().__post_init__()
        n = self.num_hidden_layers
        if len(self.layer_kinds) != n or set(self.layer_kinds) - {FULL, WINDOW}:
            raise ValueError(
                f"layer_kinds must name {n} layers, each {FULL!r} or "
                f"{WINDOW!r}: got {self.layer_kinds}")
        if len(self.ffn_kinds) != n or set(self.ffn_kinds) - {DENSE, SPARSE}:
            raise ValueError(
                f"ffn_kinds must name {n} layers, each {DENSE!r} or "
                f"{SPARSE!r}: got {self.ffn_kinds}")
        if WINDOW in self.layer_kinds and self.sliding_window <= 0:
            raise ValueError("window layers need a sliding_window")
        for kind in (FULL, WINDOW):
            if self.heads(kind) % self.num_key_value_heads:
                raise ValueError(
                    f"{self.heads(kind)} query heads on a {kind} layer over "
                    f"{self.num_key_value_heads} K/V heads")
        if int(self.head_dim * self.full_rotary_pct) % 2:
            raise ValueError(f"full_rotary_pct {self.full_rotary_pct} rotates "
                             f"an odd width of {self.head_dim}")
        lo, hi = self.held
        if not 0 <= lo < hi <= self.num_experts:
            raise ValueError(f"experts_held {self.experts_held} of {self.num_experts}")

    def heads(self, kind: str) -> int:
        return self.full_heads if kind == FULL else self.window_heads

    @property
    def held(self) -> Tuple[int, int]:
        return self.experts_held if any(self.experts_held) else (0, self.num_experts)

    @property
    def kinds(self) -> Tuple[Tuple[str, str], ...]:
        """A layer's kind: the groups its blocks take their weights
        from, in the order they run."""
        return tuple(zip(self.layer_kinds, self.ffn_kinds))

    def count(self, group: str) -> int:
        return sum(group in kind for kind in self.kinds)


def config(**kw) -> LagunaConfig:
    """Laguna-XS.2 as published."""
    d: Dict[str, Any] = dict(
        vocab_size=100352, hidden_size=2048, intermediate_size=8192,
        moe_intermediate_size=512, moe_shared_expert_intermediate_size=512,
        num_hidden_layers=40, num_attention_heads=48, num_key_value_heads=8,
        head_dim_override=128, max_position_embeddings=262144,
        norm_type="rmsnorm", norm_bias=False, norm_eps=1e-6, positions="rope",
        rope_theta=10000.0, rotary_pct=1.0, activation="silu", glu=True,
        tie_word_embeddings=False, num_experts=256, num_experts_per_tok=8,
        moe_norm_topk=True, sliding_window=512,
    )
    d.update(kw)
    n = d["num_hidden_layers"]
    d.setdefault("layer_kinds", ((FULL, WINDOW, WINDOW, WINDOW) * n)[:n])
    d.setdefault("ffn_kinds", ((DENSE,) + (SPARSE,) * n)[:n])
    return LagunaConfig(**d)


def tiny(**kw) -> LagunaConfig:
    """CPU test size: [F, S, S, S, F, S], layer 0 dense; 6 query heads
    on a full layer and 8 on a window layer over 2 K/V heads of 16 (a
    group of 3, padded, and of 4); a window of 8 lines; YaRN factor 4
    over 16 positions on half a head; 16 experts, 3 a token."""
    d = dict(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        moe_intermediate_size=32, moe_shared_expert_intermediate_size=32,
        num_hidden_layers=6, num_attention_heads=6, full_heads=6,
        window_heads=8, num_key_value_heads=2, head_dim_override=16,
        num_experts=16, num_experts_per_tok=3, max_position_embeddings=512,
        sliding_window=8, full_rope_factor=4.0, full_rope_original_max=16,
        full_rope_attention_factor=0.1 * math.log(4.0) + 1.0,
    )
    d.update(kw)
    return config(**d)


_LAYER_TYPES = {"full_attention": FULL, "sliding_attention": WINDOW}


def _one_of(values, what):
    values = set(values)
    if len(values) != 1:
        raise NotImplementedError(
            f"{what} differ among the layers of one kind ({sorted(values)}): "
            "a kind's weights are one stack of one shape")
    return values.pop()


def from_hf(hf: Dict[str, Any], **kw) -> LagunaConfig:
    """From the published ``config.json`` keys, as they are spelled
    (``layer_types``, ``mlp_layer_types``, ``num_attention_heads_per_
    layer``, ``rope_parameters``, ``gating``: ``true`` and
    ``"per-head"`` alike). ``num_hidden_layers`` under the lists'
    length takes their first entries. ``experts_held`` ([lo, hi]) is
    read where a benchmark configuration states it. What is not built
    is refused by name."""
    def refuse(key, why):
        raise NotImplementedError(f"{key} {hf.get(key)!r}: {why}")

    if hf.get("gating", True) not in (True, "per-head", "per_head"):
        refuse("gating", "only a gate a head (true / 'per-head') is built")
    if set(hf.get("gating_types", ["per_head"])) - {"per_head", "per-head"}:
        refuse("gating_types", "only a gate a head is built")
    if hf.get("moe_apply_router_weight_on_input", False):
        refuse("moe_apply_router_weight_on_input",
               "the router's weight multiplies an expert's output")
    if hf.get("moe_router_logit_softcapping"):
        refuse("moe_router_logit_softcapping", "no cap on the router's logits is built")
    if hf.get("attention_bias", False):
        refuse("attention_bias", "the projections have no bias")
    if not hf.get("norm_topk_prob", True):
        refuse("norm_topk_prob", "the chosen experts' scores are renormalised")
    n = kw.get("num_hidden_layers", hf["num_hidden_layers"])
    layer_kinds = tuple(_LAYER_TYPES[t] for t in hf["layer_types"][:n])
    ffn_kinds = tuple(hf.get("mlp_layer_types") or [
        DENSE if i in hf.get("mlp_only_layers", ()) else SPARSE
        for i in range(n)])[:n]
    first_sparse = ffn_kinds.index(SPARSE) if SPARSE in ffn_kinds else n
    if DENSE in ffn_kinds[first_sparse:]:
        refuse("mlp_layer_types", "a dense layer behind a sparse one (only "
               "leading dense layers are built)")
    per_layer = list(hf.get("num_attention_heads_per_layer")
                     or [hf["num_attention_heads"]] * n)[:n]
    heads = {
        kind: _one_of((h for h, k in zip(per_layer, layer_kinds) if k == kind),
                      f"the query heads of the {kind} layers")
        if kind in layer_kinds else hf["num_attention_heads"]
        for kind in (FULL, WINDOW)}
    ropes = hf["rope_parameters"]
    full, window = ropes["full_attention"], ropes["sliding_attention"]
    for name, r in (("full_attention", full), ("sliding_attention", window)):
        if r.get("rope_type", "default") not in ("default", "yarn"):
            raise NotImplementedError(
                f"rope_parameters.{name}.rope_type {r['rope_type']!r}")
    if window.get("rope_type", "default") != "default":
        raise NotImplementedError(
            "rope_parameters.sliding_attention.rope_type 'yarn': the window "
            "layers' rope is plain")
    yarn = full.get("rope_type", "default") == "yarn"
    factor = float(full.get("factor", 1.0)) if yarn else 1.0
    d = dict(
        vocab_size=hf["vocab_size"], hidden_size=hf["hidden_size"],
        intermediate_size=hf["intermediate_size"],
        moe_intermediate_size=hf["moe_intermediate_size"],
        moe_shared_expert_intermediate_size=hf.get(
            "shared_expert_intermediate_size", 0),
        num_hidden_layers=n, num_attention_heads=hf["num_attention_heads"],
        full_heads=heads[FULL], window_heads=heads[WINDOW],
        num_key_value_heads=hf["num_key_value_heads"],
        head_dim_override=hf["head_dim"],
        max_position_embeddings=hf["max_position_embeddings"],
        norm_eps=float(hf.get("rms_norm_eps", 1e-6)),
        tie_word_embeddings=bool(hf.get("tie_word_embeddings", False)),
        layer_kinds=layer_kinds, ffn_kinds=ffn_kinds,
        sliding_window=int(hf["sliding_window"]),
        num_experts=hf["num_experts"],
        num_experts_per_tok=hf["num_experts_per_tok"],
        routed_scaling_factor=float(hf.get("moe_routed_scaling_factor", 1.0)),
        experts_held=tuple(hf.get("experts_held", (0, 0))),
        rope_theta=float(window["rope_theta"]),
        rotary_pct=float(window.get("partial_rotary_factor", 1.0)),
        full_rope_theta=float(full["rope_theta"]),
        full_rotary_pct=float(full.get("partial_rotary_factor", 1.0)),
        full_rope_factor=factor,
        full_rope_original_max=int(full.get(
            "original_max_position_embeddings", hf["max_position_embeddings"])),
        full_rope_beta_fast=float(full.get("beta_fast", 32)),
        full_rope_beta_slow=float(full.get("beta_slow", 1)),
        full_rope_attention_factor=float(full.get(
            "attention_factor", 0.1 * math.log(factor) + 1.0 if yarn else 1.0)),
    )
    d.update(kw)
    return config(**d)


# ---------------------------------------------------------------------------
# Positions: a table a kind


def full_inv_freq(cfg: LagunaConfig):
    """The full layers' rope frequencies over their rotated channels
    (the first ``full_rotary_pct`` of a head), by YaRN's ramp."""
    return yarn_inv_freq(
        int(cfg.head_dim * cfg.full_rotary_pct), cfg.full_rope_theta,
        cfg.full_rope_factor, cfg.full_rope_original_max,
        cfg.full_rope_beta_fast, cfg.full_rope_beta_slow)


def rope_tables(cfg: LagunaConfig, positions):
    """{kind: (cos, sin)} in the half-split layout
    :func:`transformer.apply_rope` takes, each as wide as the kind
    rotates (the rest of a head passes): the window layers' plain, the
    full layers' by YaRN with cos and sin times ``attention_factor``."""
    def table(inv, factor):
        angles = positions.astype(jnp.float32)[..., None] * jnp.asarray(inv, jnp.float32)
        angles = jnp.concatenate([angles, angles], axis=-1)
        return jnp.cos(angles) * factor, jnp.sin(angles) * factor

    rot = int(cfg.head_dim * cfg.rotary_pct)
    return {FULL: table(full_inv_freq(cfg), cfg.full_rope_attention_factor),
            WINDOW: table(yarn_inv_freq(rot, cfg.rope_theta), 1.0)}


# ---------------------------------------------------------------------------
# Parameters: four stacked groups and the ends


def _group_shapes(cfg: LagunaConfig, group: str) -> Dict[str, Any]:
    D, KV, d = cfg.hidden_size, cfg.num_key_value_heads, cfg.head_dim
    if group in (FULL, WINDOW):
        H = cfg.heads(group)
        return {"attn_norm_scale": (D,), "wq": (D, H * d), "wk": (D, KV * d),
                "wv": (D, KV * d), "wg": (D, H), "q_norm_scale": (d,),
                "k_norm_scale": (d,), "wo": (H * d, D)}
    if group == DENSE:
        F = cfg.intermediate_size
        return {"mlp_norm_scale": (D,), "w_gate": (D, F), "w_up": (D, F),
                "w_down": (F, D)}
    F, n = cfg.moe_intermediate_size, cfg.held[1] - cfg.held[0]
    shapes: Dict[str, Any] = {
        "mlp_norm_scale": (D,), "w_router": (D, cfg.num_experts),
        "router_bias": (cfg.num_experts,),
        "w_gate": (n, D, F), "w_up": (n, D, F), "w_down": (n, F, D)}
    S = cfg.moe_shared_expert_intermediate_size
    if S:
        shapes["shared"] = {"w_gate": (D, S), "w_up": (D, S), "w_down": (S, D)}
    return shapes


GROUPS = (FULL, WINDOW, DENSE, SPARSE)


@functools.partial(jax.jit, static_argnames="cfg")
def init_params(key, cfg: LagunaConfig) -> Dict[str, Any]:
    """The family's own draw: 0.02 (0.02 / sqrt(2 N) for ``wo`` and
    every ``w_down``), norm scales one, the selection offset zero (in
    float32: it is added to float32 scores). One program: leaf by leaf
    the draw is seconds of small compiles at any size."""
    std = 0.02
    out_std = std / math.sqrt(2 * cfg.num_hidden_layers)
    keys = iter(jax.random.split(key, 64))

    def leaf(name, shape):
        if "norm_scale" in name:
            return jnp.ones(shape, cfg.dtype)
        if name == "router_bias":
            return jnp.zeros(shape, jnp.float32)
        scale = out_std if name in ("wo", "w_down") else std
        return seeded_normal(next(keys), scale, shape=shape, dtype=cfg.dtype)

    def leaves(shapes, n):
        return {name: leaves(s, n) if isinstance(s, dict) else leaf(name, (n,) + s)
                for name, s in shapes.items()}

    params = {
        "embed": leaf("embed", (cfg.vocab_size, cfg.hidden_size)),
        "final_norm_scale": leaf("final_norm_scale", (cfg.hidden_size,)),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = leaf("lm_head", (cfg.hidden_size, cfg.vocab_size))
    for group in GROUPS:
        if cfg.count(group):
            params[group] = leaves(_group_shapes(cfg, group), cfg.count(group))
    return params


def param_pspecs(cfg: LagunaConfig, *, pipeline: bool = False) -> Dict[str, Any]:
    """Every weight whole on every device: one chip, or replicas
    (``validate_serving`` refuses ``model > 1``)."""
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    return jax.tree.map(lambda _: P(), shapes)


def num_params(cfg: LagunaConfig) -> int:
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    return sum(math.prod(a.shape) for a in jax.tree.leaves(shapes))


def active_params(cfg: LagunaConfig) -> int:
    """The parameters one token's forward pass multiplies by: every
    weight but the experts a token does not choose (with every expert
    held: :func:`num_params` less those)."""
    lo, hi = cfg.held
    expert = 3 * cfg.hidden_size * cfg.moe_intermediate_size
    idle = max(hi - lo - cfg.num_experts_per_tok, 0)
    return num_params(cfg) - cfg.count(SPARSE) * idle * expert


def step_counts(cfg: LagunaConfig) -> Dict[str, Tuple[int, ...]]:
    """What a step returns in its cache that is no state (name ->
    shape, int32; models/lfm2_moe.py): ``moe_counts``, each sparse
    layer's real tokens per expert held."""
    return {"moe_counts": (cfg.count(SPARSE), cfg.held[1] - cfg.held[0])}


def expert_routing(cfg: LagunaConfig) -> Tuple[int, Tuple[int, int], int]:
    """(The experts a token chooses, the range of experts held, the
    router's outputs): what the grouped expert matmuls' row tile is
    reckoned from (models/transformer.py ``expert_routing``)."""
    return cfg.num_experts_per_tok, cfg.held, cfg.num_experts


validate_serving = functools.partial(_classes.validate_serving, family="laguna")

commit_kv_paged = reorder_slots_paged = copy_page_kv = _one_table_only
gather_page_kv = scatter_page_kv = _one_table_only
init_kv_cache = kv_cache_pspecs = serve_step = _one_table_only
commit_kv = reorder_slots = _one_table_only


# ---------------------------------------------------------------------------
# The blocks. The four ASSUMED element-wise choices, a function each.


def head_gate(g):
    """A head's output gate from its logit: ASSUMED a sigmoid."""
    return jax.nn.sigmoid(g.astype(jnp.float32))


def normed_heads(cfg: LagunaConfig, p, q, k):
    """q (..., H, d) and k (..., KV, d) RMS-normed a head over its
    channels, a learned scale of ``head_dim`` each (ASSUMED)."""
    return (_norm(cfg, q, p["q_norm_scale"], None),
            _norm(cfg, k, p["k_norm_scale"], None))


def route(cfg: LagunaConfig, p, h):
    """The router's choice for normed tokens h (N, D), over ALL its
    outputs: (experts (N, k), weights (N, k)). ASSUMED: sigmoid scores,
    a selection offset that chooses and does not weigh, the chosen
    scores renormalised, times ``moe_routed_scaling_factor``."""
    return route_sigmoid_topk(
        h, p["w_router"], p["router_bias"], cfg.num_experts_per_tok,
        norm_topk=cfg.moe_norm_topk, scaling=cfg.routed_scaling_factor,
        eps=1e-20)


def shared_expert(cfg: LagunaConfig, p, h):
    """The shared expert's part: ASSUMED ungated (added whole)."""
    return _ffn(cfg, p, h)


def _attn_block(kind, cfg, ctx, stack, index, x, carried):
    p = layer_weights(stack, index)
    B, T, _ = x.shape
    H, KV, d = cfg.heads(kind), cfg.num_key_value_heads, cfg.head_dim
    h = _norm(cfg, x, p["attn_norm_scale"], None)
    with sublayer("attn.proj"):
        q = _mm(h, p["wq"]).reshape(B, T, H, d)
        k = _mm(h, p["wk"]).reshape(B, T, KV, d)
        v = _mm(h, p["wv"]).reshape(B, T, KV, d)
        gate = head_gate(_mm(h, p["wg"]))                     # (B, T, H)
        q, k = normed_heads(cfg, p, q, k)
        rope = ctx["rope"][kind]
        q, k = apply_rope(q, *rope), apply_rope(k, *rope)
    o, pools = attend_class(cfg, ctx, kind, carried, index, q, k, v)
    with sublayer("attn.proj"):
        o = (o.reshape(B, T, H, d) * gate[..., None].astype(o.dtype))
        out = _mm(o.reshape(B, T, H * d), p["wo"])
    return x + out, dict(carried, **pools)


def _dense_block(cfg, ctx, stack, index, x, carried):
    p = layer_weights(stack, index)
    return x + _ffn(cfg, p, _norm(cfg, x, p["mlp_norm_scale"], None)), carried


def sparse_ffn(cfg, p, h, real, layer=None, kernels="xla"):
    """One sparse layer's FFN over a flat token axis: h (N, D) normed,
    ``real`` (N,). ``p``: the layer's router and shared-expert weights,
    and the routed experts' weights of the layer — or, with ``layer``,
    of every sparse layer, stacked. The experts held compute their
    part, the shared expert the whole of its own.
    -> (out (N, D), counts (experts held,))."""
    experts, weights = route(cfg, p, h)
    _, held, routed = expert_routing(cfg)
    out, counts = routed_experts_ffn(
        h, real, experts, weights, p["w_gate"], p["w_up"], p["w_down"],
        experts_held=held, routed=routed, layer=layer, kernels=kernels)
    if "shared" in p:
        out = out + shared_expert(cfg, p["shared"], h)
    return out, counts


def _sparse_block(cfg, ctx, stack, index, x, carried):
    routed = {k: v for k, v in stack.items() if k != "shared"}
    p = layer_weights(routed, index, whole=("w_gate", "w_up", "w_down"))
    if "shared" in stack:
        p["shared"] = layer_weights(stack["shared"], index)
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
    page_table,               # {"full": (R, NP), "window": (R, NPw), "window_start": (R,)}
    *,
    cfg: LagunaConfig,
    cache_len: int,
    all_logits: bool = False,
    kernels: str = "xla",
    pack: Any = None,
    **unsupported,
):
    """The engine's paged step (models/transformer.serve_step_paged's
    contract, its packed token axis included) over the layer order,
    with a table a class of page (``smallthinker.step_context``) and a
    rope table a kind. The returned cache also holds ``moe_counts``
    (``step_counts``: an output, not an input)."""
    if mask is not None or cache_positions is not None or any(
            v for v in unsupported.values()):
        _one_table_only()
    if pack is not None and all_logits:
        raise ValueError("a packed token axis returns one logits row a row")
    tok, pos, ctx = step_context(
        cache, tokens, positions, page_table, window=cfg.sliding_window,
        cache_len=cache_len, pack=pack, kernels=kernels,
        rope=functools.partial(rope_tables, cfg))
    x = _embed_in(cfg, params, tok, pos)
    carried = dict(cache, **{name: jnp.zeros(shape, jnp.int32)
                             for name, shape in step_counts(cfg).items()})
    blocks = {
        FULL: functools.partial(_attn_block, FULL, cfg, ctx),
        WINDOW: functools.partial(_attn_block, WINDOW, cfg, ctx),
        DENSE: functools.partial(_dense_block, cfg, ctx),
        SPARSE: functools.partial(_sparse_block, cfg, ctx),
    }
    x, new_cache = run_layers(cfg.kinds, blocks, params, x, carried)
    return _head_logits(cfg, params, x, logits_idx, ctx["pack"],
                        all_logits), new_cache
