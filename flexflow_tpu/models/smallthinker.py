"""SmallThinker model family (PowerInfer, ``SmallThinker-21BA3B-Instruct``,
``SmallThinker-4BA0.6B-Instruct``): a sparse decoder whose layers
attend in one of TWO ways, in the order ``sliding_window_layout`` /
``rope_layout`` give (at the published 21B: one full layer, three
window layers, thirteen times over):

* ``full``: grouped-query softmax attention over the whole context,
  with NO positions at all (no rope on q or k).
* ``window``: the same over a query's own position and the
  ``sliding_window_size - 1`` before it, with rope (rotate_half over
  the whole head).
* every layer's FFN is ``moe_num_primary_experts`` small ReGLU experts
  (``relu(h W_gate) * (h W_up)) W_down``), ``moe_num_active_primary_
  experts`` of them a token behind a linear router with a softmax over
  the chosen (``transformer.route_softmax_topk``) that reads the
  layer's INPUT: the residual stream as it enters the layer, ahead of
  the input norm and of attention.

The equations are written out in ``benchmarks/references/smallthinker.py``,
which the tests hold this file to.

Serving only, on the paged path (``kv_layout="paged"``), through the
engine's ordinary step programs:

* the layer loop is :func:`transformer.run_layers` over kinds
  (``route``, ``full``, ``sparse``) and (``route``, ``window``,
  ``sparse``): a layer is the router's choice from its input, its
  attention, then the routed experts with the choice made at the top.
  The choice rides in the loop's carry beside the pools and the step's
  expert counts (``step_counts``).
* the cache is TWO CLASSES of page (``page_classes``;
  serve/paging.PageClasses): ``k`` / ``v`` (full layers, pages+1, page,
  KV * d) hold a request's every line; ``k_win`` / ``v_win`` (window
  layers, window pages+1, page, KV * d) hold the lines a later query
  may still see. The engine keeps an allocator and a table a class;
  the window class's allocator frees a slot's pages that lie wholly
  behind the window of its next query, and its table ROLLS: a step is
  handed ``{"full": (R, NP), "window": (R, NPw), "window_start": (R,)}``
  where ``window_start`` is the position of the first line of a row's
  window table. A window layer writes its lines, gathers and masks by
  TRUE positions from there, and its attention call walks the NPw live
  pages, not the context's (``ff_ragged_paged_c<C>_win`` in a trace).
* the step takes the engine's PACKED token axis (``PACKED_STEP``).
* ``experts_held`` (a range of the router's outputs, all of them unless
  told) is the guide's usual cut, as models/lfm2_moe.py takes it.

What it refuses, at construction (``validate_serving``), each because
two classes of page have no such operation yet: prefix caching,
SpecInfer and beam search, ``kv_quant``, ``fused_decode``,
``kv_shard="context"``, the dense layout, a mesh with ``model > 1``.

The two classes of page are written ONCE, here, and serve every family
that has them (models/laguna.py imports them: window layers of 512
lines with 64 query heads beside full layers with 48): ``page_classes``
and its contract, the pools (``init_paged_kv_cache``), ``step_context``
(a class's table, mask and places, from the step's arrays),
``attend_class`` (a layer's lines written and attended through ITS
class, the head count read off the queries), ``_class_places``,
``_window_mask``, ``_pad_groups``, ``validate_serving`` and the refused
one-table operations. They read a config's ``sliding_window``,
``num_key_value_heads``, ``head_dim`` and ``count(kind)`` and nothing
else of it.

Weight names follow ``benchmarks/harness/model.py::make_params``' rule:
norm scales hold ``norm_scale``, the projections that write into the
residual stream are ``wo`` and ``w_down``; groups ``route``
(``w_router``, every layer), ``full`` and ``window`` (the attention
weights, by kind) and ``sparse`` (the experts, every layer), each
stacked over its layers in layer order.
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
    _gather_attended,
    _head_logits,
    _layer_of,
    _mm,
    _norm,
    _pack_tokens,
    _pallas_pools,
    _serve_attend,
    _spread_queries,
    _write_kv_lines,
    apply_rope,
    layer_weights,
    rope_freqs,
    route_softmax_topk,
    routed_experts_ffn,
    run_layers,
    seeded_normal,
)

FULL, WINDOW = "full", "window"
PAGE_POOLS = ("k", "v", "k_win", "v_win")
SLOT_STATE = ()
FUSED_DECODE = ()
PACKED_STEP = True


@dataclasses.dataclass(frozen=True)
class SmallThinkerConfig(DecoderConfig):
    # a layer's attention, in layer order: FULL (every line, no rope)
    # or WINDOW (``sliding_window`` lines, rope)
    layer_kinds: Tuple[str, ...] = ()
    num_experts: int = 64
    # the range of the router's outputs whose experts' weights are here
    # ((0, 0): all of them)
    experts_held: Tuple[int, int] = (0, 0)

    def __post_init__(self):
        super().__post_init__()
        kinds = self.layer_kinds
        if len(kinds) != self.num_hidden_layers or set(kinds) - {FULL, WINDOW}:
            raise ValueError(
                f"layer_kinds must name {self.num_hidden_layers} layers, "
                f"each {FULL!r} or {WINDOW!r}: got {kinds}")
        if WINDOW in kinds and self.sliding_window <= 0:
            raise ValueError("window layers need a sliding_window")
        lo, hi = self.held
        if not 0 <= lo < hi <= self.num_experts:
            raise ValueError(f"experts_held {self.experts_held} of {self.num_experts}")

    @property
    def held(self) -> Tuple[int, int]:
        return self.experts_held if any(self.experts_held) else (0, self.num_experts)

    @property
    def kinds(self) -> Tuple[Tuple[str, ...], ...]:
        """A layer's kind: the groups its blocks take their weights
        from, in the order they run."""
        return tuple(("route", kind, "sparse") for kind in self.layer_kinds)

    def count(self, group: str) -> int:
        return sum(group in kind for kind in self.kinds)


def config(**kw) -> SmallThinkerConfig:
    """SmallThinker-21BA3B-Instruct as published."""
    d: Dict[str, Any] = dict(
        vocab_size=151936, hidden_size=2560, intermediate_size=768,
        moe_intermediate_size=768, num_hidden_layers=52,
        num_attention_heads=28, num_key_value_heads=4, head_dim_override=128,
        max_position_embeddings=16384, norm_type="rmsnorm", norm_bias=False,
        norm_eps=1e-6, positions="rope", rope_theta=1.5e6, activation="relu",
        glu=True, tie_word_embeddings=False, num_experts=64,
        num_experts_per_tok=6, moe_norm_topk=True, sliding_window=4096,
    )
    d.update(kw)
    d.setdefault("layer_kinds",
                 ((FULL, WINDOW, WINDOW, WINDOW) * 13)[: d["num_hidden_layers"]])
    return SmallThinkerConfig(**d)


def tiny(**kw) -> SmallThinkerConfig:
    """CPU test size: two periods of one full and three window layers,
    a window of a few pages, a group of 3 query heads a KV head."""
    d = dict(
        vocab_size=256, hidden_size=64, intermediate_size=32,
        moe_intermediate_size=32, num_hidden_layers=8,
        num_attention_heads=6, num_key_value_heads=2, head_dim_override=16,
        num_experts=8, num_experts_per_tok=2, max_position_embeddings=512,
        sliding_window=24,
    )
    d.update(kw)
    return config(**d)


def from_hf(hf: Dict[str, Any], **kw) -> SmallThinkerConfig:
    """From the published ``config.json`` keys, as they are spelled.
    ``num_hidden_layers`` under ``len(sliding_window_layout)`` takes the
    first entries. A file whose ``rope_layout`` differs from its
    ``sliding_window_layout`` (a window layer without rope, a full layer
    with it) or whose router applies no softmax is refused: the
    published files need neither form and none is built. ``experts_held``
    ([lo, hi]) is read where a benchmark configuration states it."""
    n = kw.get("num_hidden_layers", hf["num_hidden_layers"])
    window_layout = list(hf["sliding_window_layout"])[:n]
    if list(hf.get("rope_layout", window_layout))[:n] != window_layout:
        raise NotImplementedError(
            "rope_layout differs from sliding_window_layout: only window "
            "layers with rope and full layers without are built")
    if not hf.get("moe_primary_router_apply_softmax", True):
        raise NotImplementedError(
            "moe_primary_router_apply_softmax false: only the softmax over "
            "the chosen experts is built")
    if hf.get("rope_scaling"):
        raise NotImplementedError(f"rope_scaling {hf['rope_scaling']!r}")
    heads = kw.get("num_attention_heads", hf["num_attention_heads"])
    hidden = kw.get("hidden_size", hf["hidden_size"])
    d = dict(
        vocab_size=hf["vocab_size"], hidden_size=hidden,
        intermediate_size=hf["moe_ffn_hidden_size"],
        moe_intermediate_size=hf["moe_ffn_hidden_size"],
        num_hidden_layers=n, num_attention_heads=heads,
        num_key_value_heads=hf["num_key_value_heads"],
        head_dim_override=hf.get("head_dim") or hidden // heads,
        max_position_embeddings=hf["max_position_embeddings"],
        norm_eps=float(hf.get("rms_norm_eps", 1e-6)),
        rope_theta=float(hf.get("rope_theta", 1.5e6)),
        tie_word_embeddings=bool(hf.get("tie_word_embeddings", False)),
        layer_kinds=tuple(WINDOW if w else FULL for w in window_layout),
        sliding_window=int(hf["sliding_window_size"]),
        num_experts=hf["moe_num_primary_experts"],
        num_experts_per_tok=hf["moe_num_active_primary_experts"],
        experts_held=tuple(hf.get("experts_held", (0, 0))),
    )
    d.update(kw)
    return config(**d)


# ---------------------------------------------------------------------------
# Parameters: four stacked groups and the ends


def _group_shapes(cfg: SmallThinkerConfig, group: str) -> Dict[str, Tuple[int, ...]]:
    D = cfg.hidden_size
    H, KV, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    if group == "route":
        return {"w_router": (D, cfg.num_experts)}
    if group in (FULL, WINDOW):
        return {"attn_norm_scale": (D,), "wq": (D, H * d), "wk": (D, KV * d),
                "wv": (D, KV * d), "wo": (H * d, D)}
    F, n = cfg.moe_intermediate_size, cfg.held[1] - cfg.held[0]
    return {"mlp_norm_scale": (D,), "w_gate": (n, D, F), "w_up": (n, D, F),
            "w_down": (n, F, D)}


GROUPS = ("route", FULL, WINDOW, "sparse")


def init_params(key, cfg: SmallThinkerConfig) -> Dict[str, Any]:
    """The family's own draw: 0.02 (0.02 / sqrt(2 N) for ``wo`` and
    ``w_down``), norm scales one."""
    std = 0.02
    out_std = std / math.sqrt(2 * cfg.num_hidden_layers)
    keys = iter(jax.random.split(key, 32))

    def leaf(name, shape):
        if "norm_scale" in name:
            return jnp.ones(shape, cfg.dtype)
        scale = out_std if name in ("wo", "w_down") else std
        return seeded_normal(next(keys), scale, shape=shape, dtype=cfg.dtype)

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


def param_pspecs(cfg: SmallThinkerConfig, *, pipeline: bool = False) -> Dict[str, Any]:
    """Every weight whole on every device: one chip, or replicas
    (``validate_serving`` refuses ``model > 1``)."""
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    return jax.tree.map(lambda _: P(), shapes)


def num_params(cfg: SmallThinkerConfig) -> int:
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    return sum(math.prod(a.shape) for a in jax.tree.leaves(shapes))


def step_counts(cfg: SmallThinkerConfig) -> Dict[str, Tuple[int, ...]]:
    """What a step returns in its cache that is no state (name ->
    shape, int32; models/lfm2_moe.py): ``moe_counts``, each layer's
    real tokens per expert held."""
    return {"moe_counts": (cfg.count("sparse"), cfg.held[1] - cfg.held[0])}


def expert_routing(cfg: SmallThinkerConfig) -> Tuple[int, Tuple[int, int], int]:
    """(The experts a token chooses, the range of experts held, the
    router's outputs): what the grouped expert matmuls' row tile is
    reckoned from (models/transformer.py ``expert_routing``)."""
    return cfg.num_experts_per_tok, cfg.held, cfg.num_experts


def page_classes(cfg):
    """The classes of page this family's (and models/laguna.py's) cache is made of, beside
    ``PAGE_POOLS``: name -> (the class's pools, its window in lines or
    None for a class that keeps every line). The engine keeps a pool
    (sized by ``init_paged_kv_cache``'s ``class_pages``), an allocator
    and a table a class, and hands a step the tables by these names
    (serve/paging.PageClasses, ``InferenceEngine._class_pagers``)."""
    return {FULL: (("k", "v"), None),
            WINDOW: (("k_win", "v_win"), cfg.sliding_window)}


def validate_serving(cfg, serving, mesh, *, specinfer: bool = False,
                     family: str = "smallthinker") -> None:
    """The combinations two classes of page cannot serve yet, refused
    at engine construction, each naming what is missing. (``family``:
    the name in the message; models/laguna.py refuses the same seven.)"""
    from ..core.mesh import MODEL_AXIS

    def refuse(what, why):
        raise NotImplementedError(f"{family} does not serve {what}: {why}")

    if serving.kv_layout != "paged":
        refuse(f"kv_layout={serving.kv_layout!r}",
               "only the paged step knows the window layers' own pool and "
               "table")
    if serving.prefix_caching:
        refuse("prefix_caching=True",
               "the radix tree shares pages of one class: a cached prefix's "
               "window pages were freed behind the window, so a request that "
               "attaches mid-prefix would find no lines to attend")
    if specinfer:
        refuse("SpecInfer or beam search",
               "commit_kv / reorder_slots move lines through one table; a "
               "rolled window table has no entry for a line behind its start")
    if serving.kv_quant is not None:
        refuse(f"kv_quant={serving.kv_quant!r}",
               "neither class's pool has scale rows in this family's cache")
    if serving.fused_decode:
        refuse(f"fused_decode={serving.fused_decode!r}",
               "the fused prologue knows one kind of layer, one table and "
               "rope on every layer")
    if serving.kv_shard == "context":
        refuse(f"kv_shard={serving.kv_shard!r}",
               "striping puts logical page j on shard j % n; a window "
               "class's table is indexed from its first live page")
    if mesh.shape.get(MODEL_AXIS, 1) > 1:
        refuse("a mesh with model > 1",
               "the merged-head pools and the grouped expert matmul are not "
               "sharded yet")


def _one_table_only(*_a, **_k):
    raise NotImplementedError(
        "this family keeps two classes of page: committing, copying or "
        "reordering cache lines goes through one table, and the window "
        "class's has rolled past the lines behind its start")


commit_kv_paged = reorder_slots_paged = copy_page_kv = _one_table_only
gather_page_kv = scatter_page_kv = _one_table_only
init_kv_cache = kv_cache_pspecs = serve_step = _one_table_only
commit_kv = reorder_slots = _one_table_only


# ---------------------------------------------------------------------------
# Cache: a pool a class of page


def init_paged_kv_cache(
    cfg, num_pages: int, page_size: int, dtype=None,
    kv_quant: Optional[str] = None, extra_rows: int = 0, *,
    class_pages: Optional[Dict[str, int]] = None,
):
    """``k`` / ``v``: (full layers, pages+1, page_size, KV * d);
    ``k_win`` / ``v_win``: (window layers, window pages+1, page_size,
    KV * d): a line's heads MERGED on the minor axis (as
    models/lfm2_moe.py; serve/kernels._ragged_paged_attention), the
    last row of each the class's scratch page. ``class_pages`` (class
    name -> pages, the engine's allocators') sizes them; ``num_pages``
    is the full class's and says nothing of the window class's."""
    if kv_quant is not None or extra_rows:
        raise NotImplementedError(
            "the pools of two classes of page are neither quantized nor row-sharded "
            "(validate_serving refuses kv_quant and kv_shard='context')")
    if class_pages is None:
        raise ValueError(
            "a pool a class of page: init_paged_kv_cache "
            "needs class_pages (the engine passes its allocators')")
    pages = class_pages
    dt = dtype or cfg.dtype
    line = cfg.num_key_value_heads * cfg.head_dim
    cache = {}
    for kind, (k, v) in ((FULL, ("k", "v")), (WINDOW, ("k_win", "v_win"))):
        pool = (cfg.count(kind), pages[kind] + 1, page_size, line)
        cache[k], cache[v] = jnp.zeros(pool, dt), jnp.zeros(pool, dt)
    return cache


def paged_kv_cache_pspecs(cfg=None, *, pipeline: bool = False,
                          kv_quant: Optional[str] = None,
                          kv_shard: Optional[str] = None):
    return {name: P() for name in PAGE_POOLS}


# ---------------------------------------------------------------------------
# The blocks


def _route_block(cfg, ctx, stack, index, x, carried):
    """The router's choice, from the residual stream as it ENTERS the
    layer: no norm, ahead of attention. It is kept in the carry for the
    layer's experts."""
    B, T, D = x.shape
    with sublayer("moe.route"):
        experts, weights = route_softmax_topk(
            x.reshape(B * T, D), _layer_of(stack["w_router"], index),
            cfg.num_experts_per_tok, norm_topk=cfg.moe_norm_topk)
    return x, dict(carried, route_experts=experts.astype(jnp.int32),
                   route_weights=weights)


def _pad_groups(q, kv_heads: int, back: int = 0):
    """(R, C, H, d) queries with each K/V head's GROUP of query heads
    padded with zero heads to a whole float32 sublane tile (8), for the
    ragged paged kernel; ``back``: the kernel's result cut to its
    ``back`` real heads a group again (SmallThinker's group of 7,
    Laguna's full layers' of 6). The kernel's body works on
    (group x chunk) rows a K/V head, and a group of 7 costs it five
    times a group of 8 (6.31 against 1.19 ms for a window layer's call
    at C=128, 10.06 against 2.17 for a full layer's: my chip runs,
    PR 50): an eighth more query and result bytes buy that back."""
    R, C, H, d = q.shape
    G = H // kv_heads
    if back:
        return q.reshape(R, C, kv_heads, G, d)[:, :, :, :back].reshape(R, C, -1, d)
    pad = -G % 8
    if not pad:
        return q
    q = jnp.pad(q.reshape(R, C, kv_heads, G, d),
                ((0, 0), (0, 0), (0, 0), (0, pad), (0, 0)))
    return q.reshape(R, C, -1, d)


def _roped(kind: str) -> bool:
    """Rope on the window layers only: a full layer has no positions
    at all (the published ``rope_layout``)."""
    return kind == WINDOW


def attend_class(cfg, ctx, kind, carried, index, q, k, v):
    """One layer's attention over ITS class of page: the step's lines
    ``k`` / ``v`` (B, T, KV, d) written through the class's table, the
    queries ``q`` (B, T, H, d) against the class's pool under its mask
    (the Pallas kernel with each K/V head's group padded to 8 and cut
    back, tagged ``_win`` for the window class, or its XLA twin).
    ``H`` is read off ``q``: the layer's own count. -> (attended
    (B, T, H * d), the class's two pools as written)."""
    from ..serve import kernels as _pk

    windowed = kind == WINDOW
    kn, vn = ("k_win", "v_win") if windowed else ("k", "v")
    at = ctx[kind]  # this class's table, write places and mask
    B, T, H, d = q.shape
    KV = k.shape[2]
    kp, vp, _, _ = _write_kv_lines(
        carried[kn], carried[vn], None, None, index, at["phys"], ctx["off"],
        k.reshape(B, T, KV * d), v.reshape(B, T, KV * d), None)
    with sublayer("attn.core"):
        q = _spread_queries(q, ctx["pack"])                   # (R, C, H, d)
        if ctx["kernels"] == "pallas":
            k_rows, v_rows, kw = _pallas_pools(kp, vp, None, None, index)
            o = _pk.ragged_paged_attention(
                _pad_groups(q, KV), k_rows, v_rows, at["table"], at["mask"],
                row_offset=kw["row_offset"], q_len=ctx["q_len"],
                work=at["work"], tag="_win" if windowed else "")
            o = _pad_groups(o, KV, back=H // KV)
        else:
            k_virt, v_virt = (
                _pk.gather_pages(_layer_of(pool, index), at["table"])
                for pool in (kp, vp))
            split = k_virt.shape[:2] + (KV, d)
            o = _serve_attend(cfg, q, k_virt.reshape(split),
                              v_virt.reshape(split), None, at["mask"])
        o = _gather_attended(o, ctx["pack"])
    return o, {kn: kp, vn: vp}


def _attn_block(kind, cfg, ctx, stack, index, x, carried):
    p = layer_weights(stack, index)
    B, T, _ = x.shape
    H, KV, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    h = _norm(cfg, x, p["attn_norm_scale"], None)
    with sublayer("attn.proj"):
        q = _mm(h, p["wq"]).reshape(B, T, H, d)
        k = _mm(h, p["wk"]).reshape(B, T, KV, d)
        v = _mm(h, p["wv"]).reshape(B, T, KV, d)
        if _roped(kind):
            q, k = apply_rope(q, *ctx["rope"]), apply_rope(k, *ctx["rope"])
    o, pools = attend_class(cfg, ctx, kind, carried, index, q, k, v)
    with sublayer("attn.proj"):
        out = _mm(o, p["wo"])
    return x + out, dict(carried, **pools)


def _sparse_block(cfg, ctx, stack, index, x, carried):
    p = layer_weights(stack, index, whole=("w_gate", "w_up", "w_down"))
    B, T, D = x.shape
    h = _norm(cfg, x, p["mlp_norm_scale"], None).reshape(B * T, D)
    _, held, routed = expert_routing(cfg)
    out, counts = routed_experts_ffn(
        h, ctx["real"], carried["route_experts"], carried["route_weights"],
        p["w_gate"], p["w_up"], p["w_down"], experts_held=held,
        routed=routed, layer=index, kernels=ctx["kernels"],
        activation=cfg.activation)
    carried = dict(carried, moe_counts=jax.lax.dynamic_update_index_in_dim(
        carried["moe_counts"], counts, index, 0))
    return x + out.reshape(B, T, D), carried


# ---------------------------------------------------------------------------
# The step


def _class_places(table, start, positions, rows, page_size, cache_len, scratch):
    """Where a class's table puts the lines at ``positions`` (any
    shape; ``rows`` the slot of each): the physical page of each, the
    scratch page for a padding position. ``start`` (R,): the position
    of the first line of each row's table (zeros: the table starts at
    the context's first line)."""
    entry = positions // page_size - (start // page_size)[rows]
    phys = table[rows, jnp.clip(entry, 0, table.shape[1] - 1)]
    return jnp.where(positions < cache_len, phys, scratch)


def _window_mask(positions, start, lines, window, cache_len):
    """(R, C, lines) bool over a window table's lines, from TRUE
    positions: line ``j`` of row ``r`` is position ``start[r] + j``; a
    query at ``i`` sees ``i - window < position <= i``."""
    key_pos = start[:, None] + jnp.arange(lines, dtype=jnp.int32)[None]
    key_pos = key_pos[:, None, :]
    q = positions[:, :, None]
    return (key_pos <= q) & (key_pos > q - window) & (key_pos < cache_len)


def step_context(cache, tokens, positions, page_table, *, window, cache_len,
                 pack, rope, kernels):
    """What the blocks of a step over two classes of page read, from
    the step's (R, C) arrays (a row's real positions are its first
    columns, consecutive) and its tables ``{"full": (R, NP), "window":
    (R, NPw), "window_start": (R,)}`` (``window_start`` absent: the
    window table starts at the context's first line, as where the class
    keeps every page). The token axis is padded as it comes or PACKED
    to ``pack`` places (``transformer._pack_tokens``). ``rope``:
    positions -> the family's rope table or tables, traced under
    ``ff.attn.proj``. -> (tokens, positions of the token axis, ctx):
    ``ctx[FULL]`` / ``ctx[WINDOW]`` hold a class's table, its mask over
    its lines from TRUE positions, the Pallas kernel's work list over
    its entries (serve/kernels.step_work, by the same window and table
    start as the mask) and the physical page of each place;
    ``off`` a place's line within its page; ``rope``, ``kernels``,
    ``q_len``, ``pack``, ``real`` (:func:`attend_class` and
    ``routed_experts_ffn`` read them)."""
    from ..serve.kernels import paged_serve_mask, real_query_lengths, step_work

    R, C = tokens.shape
    ps = cache["k"].shape[2]
    tables = {FULL: page_table[FULL], WINDOW: page_table[WINDOW]}
    starts = {FULL: jnp.zeros((R,), jnp.int32),
              WINDOW: page_table.get("window_start", jnp.zeros((R,), jnp.int32))}
    q_len = real_query_lengths(positions, cache_len)  # real columns lead
    cols = jnp.arange(C, dtype=jnp.int32)
    if pack is None:
        tok, pos = tokens, positions
        rows = jnp.broadcast_to(jnp.arange(R, dtype=jnp.int32)[:, None], (R, C))
        real = (cols[None] < q_len[:, None]).reshape(-1)
        pack_idx = None
    else:
        (tok, pos, _, _), pack_idx = _pack_tokens(
            tokens, positions, q_len, tables[FULL], ps, cache_len, pack)
        rows = (pack_idx[1] // C)[None]
        real = pos[0] < cache_len
    with sublayer("attn.proj"):
        rope_tables = rope(pos)
    NPw = tables[WINDOW].shape[1]
    masks = {
        FULL: paged_serve_mask(None, positions, tables[FULL].shape[1], ps,
                               cache_len),
        WINDOW: _window_mask(positions, starts[WINDOW], NPw * ps, window,
                             cache_len),
    }
    ctx = dict(rope=rope_tables, off=pos % ps, kernels=kernels, q_len=q_len,
               pack=pack_idx, real=real)
    for kind, k in ((FULL, "k"), (WINDOW, "k_win")):
        ctx[kind] = dict(
            table=tables[kind], mask=masks[kind],
            work=(step_work(positions, q_len, ps, tables[kind].shape[1],
                            window if kind == WINDOW else 0, starts[kind])
                  if kernels == "pallas" else None),
            phys=_class_places(tables[kind], starts[kind], pos, rows, ps,
                               cache_len, cache[k].shape[1] - 1))
    return tok, pos, ctx


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
    cfg: SmallThinkerConfig,
    cache_len: int,
    all_logits: bool = False,
    kernels: str = "xla",
    pack: Optional[int] = None,
    **unsupported,
):
    """The engine's paged step (models/transformer.serve_step_paged's
    contract, its packed token axis included) over the layer order,
    with a table a class of page (module docstring;
    :func:`step_context`). A row's real positions are its
    first columns, consecutive. The returned cache also holds
    ``moe_counts`` (``step_counts``: an output, not an input)."""
    if mask is not None or cache_positions is not None or any(
            v for v in unsupported.values()):
        _one_table_only()
    if pack is not None and all_logits:
        raise ValueError("a packed token axis returns one logits row a row")
    tok, pos, ctx = step_context(
        cache, tokens, positions, page_table, window=cfg.sliding_window,
        cache_len=cache_len, pack=pack, kernels=kernels,
        rope=functools.partial(rope_freqs, cfg))
    x = _embed_in(cfg, params, tok, pos)
    N, k = x.shape[0] * x.shape[1], cfg.num_experts_per_tok
    carried = dict(
        cache,
        route_experts=jnp.zeros((N, k), jnp.int32),
        route_weights=jnp.zeros((N, k), jnp.float32),
        **{name: jnp.zeros(shape, jnp.int32)
           for name, shape in step_counts(cfg).items()})
    blocks = {
        "route": functools.partial(_route_block, cfg, ctx),
        FULL: functools.partial(_attn_block, FULL, cfg, ctx),
        WINDOW: functools.partial(_attn_block, WINDOW, cfg, ctx),
        "sparse": functools.partial(_sparse_block, cfg, ctx),
    }
    x, new_cache = run_layers(cfg.kinds, blocks, params, x, carried)
    new_cache = {name: a for name, a in new_cache.items()
                 if not name.startswith("route_")}
    return _head_logits(cfg, params, x, logits_idx, ctx["pack"],
                        all_logits), new_cache
