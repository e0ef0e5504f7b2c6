"""DeepSeek-V3 model family (deepseek-ai, ``model_type: deepseek_v3``):
a decoder whose every layer has multi-head LATENT attention (MLA) and
whose feed-forward is dense in the first ``first_k_dense_replace``
layers and sparse in the others.

* MLA. Queries come through a low-rank pair, ``c_q = rmsnorm(x W_qa)``,
  ``q = c_q W_qb``: H heads of ``[q_nope | q_rope]``. Keys and values
  come from ONE line a token, ``[c_raw | kr_raw] = x W_kva``:
  ``c = rmsnorm(c_raw)`` (``kv_lora_rank`` values) and
  ``kr = rope(kr_raw)`` (``qk_rope_head_dim`` values, one key every
  head shares). A head's key and value are ``[k_nope_h | v_h] = c
  W_kvb`` (its columns of ``W_kvb``), its score ``(q_nope_h . k_nope_h +
  q_rope_h . kr) * s``. The cache holds the LINE ``[c | kr]``, after
  the norm and the rope: 576 values a token and layer at the published
  widths where K and V a head would be 32768.
* the served step computes the ABSORBED form, the same numbers:
  ``q'_h = q_nope_h W_UK_h^T``, ``score_h = (q'_h . c + q_rope_h . kr)
  * s``, ``o_h = (sum p c) W_UV_h`` (``W_UK_h`` / ``W_UV_h``: head h's
  halves of ``W_kvb``). Over the pool that is attention with H query
  heads on one key of the line's width and one value of
  ``kv_lora_rank`` a token: serve/kernels ``mla_paged_attention``
  (``ff_mla_paged_c<C>``; its XLA twin on the CPU), at every chunk
  width.
* rope on the rope channels only, YaRN frequencies
  (:func:`yarn_inv_freq`), cos and sin unscaled where ``mscale`` equals
  ``mscale_all_dim``; the softmax scale carries ``mscale^2``
  (:func:`softmax_scale`).
* the sparse FFN: a sigmoid router over ``n_routed_experts`` outputs
  with a selection offset, chosen by groups
  (``transformer.route_sigmoid_topk`` with ``groups``), the chosen
  experts' own scores renormalised and scaled, beside
  ``n_shared_experts`` always-on experts (one GLU of their summed
  width). The routed half is ``transformer.routed_experts_ffn``;
  ``experts_held`` (a range of the router's outputs, all of them unless
  told) is the guide's usual cut: the weights hold that range only and
  the layer computes that range's part, the shared expert whole.
* ``num_nextn_predict_layers`` (the multi-token-prediction module
  behind the last layer) is read and nothing is built for it.

The equations are written out in ``benchmarks/references/deepseek_v3.py``,
which the tests hold this file to.

Serving only, on the paged path (``kv_layout="paged"``), through the
engine's ordinary step programs: the layer loop is
:func:`transformer.run_layers` over the kinds ("mla", "dense") and
("mla", "sparse"); the cache is the LATENT pool alone (``latent`` and
``latent_rope``: a line's ``c`` and its rope key, no ``k`` / ``v``;
``PAGE_POOLS``, ``init_paged_kv_cache``), the loop's carry, written in
place by the page table; the step takes the engine's packed token axis (``PACKED_STEP``)
and returns each sparse layer's real tokens per expert held
(``step_counts``).

What it refuses, at construction (``validate_serving``): prefix caching
(no copy or gather of latent pages yet), SpecInfer and beam search
(also: the draft head it was trained with is the module this file does
not build), ``kv_quant``, ``fused_decode``, ``kv_shard="context"``, the
dense layout, a mesh with ``model > 1``.

THE LATENT POOL'S ONE COPY. What any family that keeps the latent pool
needs of a step lives here and takes the family's own numbers from its
caller: :func:`latent_pool` (the two arrays, any number of lines a
token), :func:`step_context`, :func:`latent_line` and
:func:`absorbed_queries` (each with a constant factor that defaults to
none), :func:`latent_attention` (the pool's write, the kernel's call
and the way out, at the pool index and softmax scale it is told),
:func:`validate_serving` (told who refuses). ``models/longcat_flash.py``
(two attention sublayers a layer, so two lines, and two factors) is
the second caller; this family's step programs trace to what they were
(``str(jax.make_jaxpr(serve_step_paged))`` on two checkouts).

Weight names follow ``benchmarks/harness/model.py::make_params``' rule
(it zeroes a leaf whose name holds ``bias`` or starts with ``b``, and
draws ``wo`` / ``w_down`` at the residual scale): norm scales hold
``norm_scale``, the selection offset is ``router_offset`` (HF
``e_score_correction_bias``), the shared expert's weights are a nested
``shared`` group whose leaves are ``w_gate`` / ``w_up`` / ``w_down``.
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
    _head_logits,
    _layer_of,
    _mm,
    _norm,
    _pack_tokens,
    _page_lookup,
    _spread_queries,
    apply_rope,
    layer_weights,
    route_sigmoid_topk,
    routed_experts_ffn,
    run_layers,
    seeded_normal,
    yarn_inv_freq as _yarn_ramp,
)

FUSED_DECODE = ()
PACKED_STEP = True
#: the cache entries that are page pools (what a page's bytes are
#: counted from: serve/engine.kv_bytes_per_line): a token's line lies
#: in two arrays, ``c`` and the rope key (``init_paged_kv_cache``)
PAGE_POOLS = ("latent", "latent_rope")


@dataclasses.dataclass(frozen=True)
class DeepseekV3Config(DecoderConfig):
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    first_k_dense_replace: int = 3
    n_routed_experts: int = 256          # the router's outputs
    n_shared_experts: int = 1
    n_group: int = 8
    topk_group: int = 4
    routed_scaling_factor: float = 2.5
    # YaRN (``rope_scaling``); factor 1: plain rope
    rope_factor: float = 40.0
    rope_original_max: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 1.0
    num_nextn_predict_layers: int = 0    # read, never built
    # the range of the router's outputs whose experts' weights are here
    # ((0, 0): all of them)
    experts_held: Tuple[int, int] = (0, 0)

    def __post_init__(self):
        super().__post_init__()
        lo, hi = self.held
        if not 0 <= lo < hi <= self.n_routed_experts:
            raise ValueError(
                f"experts_held {self.experts_held} of {self.n_routed_experts}")
        if self.n_routed_experts % self.n_group or not (
                0 < self.topk_group <= self.n_group):
            raise ValueError(
                f"{self.n_routed_experts} router outputs in {self.n_group} "
                f"groups, {self.topk_group} kept")
        if self.qk_rope_head_dim % 2:
            raise ValueError(f"qk_rope_head_dim {self.qk_rope_head_dim} is odd")

    @property
    def held(self) -> Tuple[int, int]:
        return self.experts_held if any(self.experts_held) else (
            0, self.n_routed_experts)

    @property
    def line_dim(self) -> int:
        """Values of one cached line: ``[c | kr]``."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def kinds(self) -> Tuple[Tuple[str, str], ...]:
        return tuple(
            ("mla", "dense" if i < self.first_k_dense_replace else "sparse")
            for i in range(self.num_hidden_layers))

    def count(self, group: str) -> int:
        return sum(group in kind for kind in self.kinds)


def config(**kw) -> DeepseekV3Config:
    d: Dict[str, Any] = dict(
        vocab_size=129280, hidden_size=7168, intermediate_size=18432,
        moe_intermediate_size=2048, num_hidden_layers=61,
        num_attention_heads=128, num_key_value_heads=128,
        max_position_embeddings=163840, norm_type="rmsnorm", norm_bias=False,
        norm_eps=1e-6, positions="rope", rope_theta=10000.0,
        activation="silu", glu=True, tie_word_embeddings=False,
        num_experts_per_tok=8, moe_norm_topk=True, num_nextn_predict_layers=1,
    )
    d.update(kw)
    # the query's head size, ``[q_nope | q_rope]``: what the base
    # config's ``head_dim`` reads
    d.setdefault("head_dim_override",
                 d.get("qk_nope_head_dim", 128) + d.get("qk_rope_head_dim", 64))
    return DeepseekV3Config(**d)


def tiny(**kw) -> DeepseekV3Config:
    """CPU test size: one dense layer, then sparse ones; 16 router
    outputs in 4 groups of which 2 stay, 4 experts a token."""
    d = dict(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        moe_intermediate_size=32, num_hidden_layers=3, first_k_dense_replace=1,
        num_attention_heads=4, num_key_value_heads=4, q_lora_rank=24,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, n_routed_experts=16, n_group=4, topk_group=2,
        num_experts_per_tok=4, max_position_embeddings=512,
        rope_original_max=64, num_nextn_predict_layers=0,
    )
    d.update(kw)
    return config(**d)


def from_hf(hf: Dict[str, Any], **kw) -> DeepseekV3Config:
    """From the published ``config.json`` keys, as they are spelled.

    A benchmark configuration that holds a chip's SHARE of the experts
    gives ``n_routed_experts`` as the count held, the range as
    ``experts_held`` ([lo, hi]) and the router's width as
    ``router_outputs``; the published file has neither key and its
    ``n_routed_experts`` is the router's width, every expert held.

    Rope pairing: this family rotates the rope channels in the
    HALF-SPLIT layout (``transformer.apply_rope``: channel i with
    channel i + qk_rope_head_dim / 2). The published checkpoint pairs
    ADJACENT channels (2i with 2i + 1), so a loader of its weights puts
    the rope columns of every head of ``W_qb`` and of ``W_kva`` in the
    order [0, 2, 4, ..., 1, 3, 5, ...]; a score is a dot product over
    those channels and does not change under a permutation q and k
    share. Seeded weights are drawn in this file's layout."""
    rope = hf.get("rope_scaling") or {}
    if rope and rope.get("type", rope.get("rope_type")) != "yarn":
        raise NotImplementedError(f"rope_scaling {rope!r}")
    for key, want in (("scoring_func", "sigmoid"), ("topk_method", "noaux_tc"),
                      ("hidden_act", "silu"), ("moe_layer_freq", 1),
                      ("attention_bias", False)):
        if hf.get(key, want) != want:
            raise NotImplementedError(f"{key} {hf[key]!r}")
    held = tuple(hf.get("experts_held", (0, 0)))
    if any(held) and held[1] - held[0] != hf["n_routed_experts"]:
        raise ValueError(
            f"experts_held {held} is not the {hf['n_routed_experts']} "
            "experts n_routed_experts counts")
    n = kw.get("num_hidden_layers", hf["num_hidden_layers"])
    d = dict(
        vocab_size=hf["vocab_size"], hidden_size=hf["hidden_size"],
        intermediate_size=hf["intermediate_size"],
        moe_intermediate_size=hf["moe_intermediate_size"],
        num_hidden_layers=n,
        num_attention_heads=hf["num_attention_heads"],
        num_key_value_heads=hf.get("num_key_value_heads",
                                   hf["num_attention_heads"]),
        max_position_embeddings=hf["max_position_embeddings"],
        norm_eps=float(hf.get("rms_norm_eps", 1e-6)),
        rope_theta=float(hf.get("rope_theta", 10000.0)),
        tie_word_embeddings=bool(hf.get("tie_word_embeddings", False)),
        q_lora_rank=hf["q_lora_rank"], kv_lora_rank=hf["kv_lora_rank"],
        qk_nope_head_dim=hf["qk_nope_head_dim"],
        qk_rope_head_dim=hf["qk_rope_head_dim"], v_head_dim=hf["v_head_dim"],
        first_k_dense_replace=min(hf["first_k_dense_replace"], n),
        n_routed_experts=hf.get("router_outputs", hf["n_routed_experts"]),
        n_shared_experts=hf.get("n_shared_experts", 0),
        n_group=hf.get("n_group", 1), topk_group=hf.get("topk_group", 1),
        num_experts_per_tok=hf["num_experts_per_tok"],
        moe_norm_topk=bool(hf.get("norm_topk_prob", True)),
        routed_scaling_factor=float(hf.get("routed_scaling_factor", 1.0)),
        rope_factor=float(rope.get("factor", 1.0)),
        rope_original_max=int(rope.get("original_max_position_embeddings",
                                       hf["max_position_embeddings"])),
        rope_beta_fast=float(rope.get("beta_fast", 32)),
        rope_beta_slow=float(rope.get("beta_slow", 1)),
        rope_mscale=float(rope.get("mscale", 1)),
        rope_mscale_all_dim=float(rope.get("mscale_all_dim", 0)),
        num_nextn_predict_layers=int(hf.get("num_nextn_predict_layers", 0)),
        experts_held=held,
    )
    d.update(kw)
    return config(**d)


# ---------------------------------------------------------------------------
# Positions: YaRN on the rope channels


def _yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def softmax_scale(cfg: DeepseekV3Config) -> float:
    """``(qk_nope + qk_rope)^-0.5`` times ``m^2``, ``m = 0.1 *
    mscale_all_dim * ln(factor) + 1`` (YaRN's attention temperature,
    which the published model folds into the scale)."""
    m = _yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim)
    return cfg.head_dim ** -0.5 * m * m


def yarn_inv_freq(cfg: DeepseekV3Config):
    """The qk_rope_head_dim / 2 rope frequencies, by YaRN's ramp
    (``transformer.yarn_inv_freq``: the one copy, which
    models/laguna.py's full layers share)."""
    return _yarn_ramp(
        cfg.qk_rope_head_dim, cfg.rope_theta, cfg.rope_factor,
        cfg.rope_original_max, cfg.rope_beta_fast, cfg.rope_beta_slow)


def rope_cos_sin(cfg: DeepseekV3Config, positions):
    """(cos, sin), each positions.shape + (qk_rope_head_dim,), in the
    half-split layout :func:`transformer.apply_rope` takes, times
    ``mscale / mscale_all_dim`` (1 at the published values)."""
    inv = jnp.asarray(yarn_inv_freq(cfg), jnp.float32)
    angles = positions.astype(jnp.float32)[..., None] * inv
    angles = jnp.concatenate([angles, angles], axis=-1)
    m = (_yarn_mscale(cfg.rope_factor, cfg.rope_mscale)
         / _yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim))
    return jnp.cos(angles) * m, jnp.sin(angles) * m


# ---------------------------------------------------------------------------
# Parameters: three stacked groups and the ends


def _group_shapes(cfg: DeepseekV3Config, group: str) -> Dict[str, Any]:
    D, H = cfg.hidden_size, cfg.num_attention_heads
    if group == "mla":
        return {
            "attn_norm_scale": (D,), "w_qa": (D, cfg.q_lora_rank),
            "q_norm_scale": (cfg.q_lora_rank,),
            "w_qb": (cfg.q_lora_rank, H * cfg.head_dim),
            "w_kva": (D, cfg.line_dim), "kv_norm_scale": (cfg.kv_lora_rank,),
            "w_kvb": (cfg.kv_lora_rank,
                      H * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
            "wo": (H * cfg.v_head_dim, D)}
    if group == "dense":
        F = cfg.intermediate_size
        return {"mlp_norm_scale": (D,), "w_gate": (D, F), "w_up": (D, F),
                "w_down": (F, D)}
    F, n = cfg.moe_intermediate_size, cfg.held[1] - cfg.held[0]
    shapes: Dict[str, Any] = {
        "mlp_norm_scale": (D,), "w_router": (D, cfg.n_routed_experts),
        "router_offset": (cfg.n_routed_experts,),
        "w_gate": (n, D, F), "w_up": (n, D, F), "w_down": (n, F, D)}
    if cfg.n_shared_experts:
        S = F * cfg.n_shared_experts
        shapes["shared"] = {"w_gate": (D, S), "w_up": (D, S), "w_down": (S, D)}
    return shapes


GROUPS = ("mla", "dense", "sparse")


def init_params(key, cfg: DeepseekV3Config) -> Dict[str, Any]:
    """The family's own draw: 0.02 (0.02 / sqrt(2 N) for ``wo`` and
    every ``w_down``), a selection offset at 0.02 in float32."""
    std = 0.02
    out_std = std / math.sqrt(2 * cfg.num_hidden_layers)
    keys = iter(jax.random.split(key, 64))

    def leaf(name, shape):
        if "norm_scale" in name:
            return jnp.ones(shape, cfg.dtype)
        scale = out_std if name in ("wo", "w_down") else std
        dtype = jnp.float32 if name == "router_offset" else cfg.dtype
        return seeded_normal(next(keys), scale, shape=shape, dtype=dtype)

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


def param_pspecs(cfg: DeepseekV3Config, *, pipeline: bool = False) -> Dict[str, Any]:
    """Every weight whole on every device: one chip, or replicas
    (``validate_serving`` refuses ``model > 1``)."""
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    return jax.tree.map(lambda _: P(), shapes)


def num_params(cfg: DeepseekV3Config) -> int:
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    return sum(math.prod(a.shape) for a in jax.tree.leaves(shapes))


def step_counts(cfg: DeepseekV3Config) -> Dict[str, Tuple[int, ...]]:
    """What a step returns in its cache that is no state (name ->
    shape, int32; ``models/lfm2_moe.py``). ``moe_counts``: each sparse
    layer's real tokens per expert held."""
    return {"moe_counts": (cfg.count("sparse"), cfg.held[1] - cfg.held[0])}


def expert_routing(cfg: DeepseekV3Config) -> Tuple[int, Tuple[int, int], int]:
    """(The experts a token chooses, the range of experts held, the
    router's outputs): what the grouped expert matmuls' row tile is
    reckoned from (models/transformer.py ``expert_routing``)."""
    return cfg.num_experts_per_tok, cfg.held, cfg.n_routed_experts


def validate_serving(cfg, serving, mesh, *, specinfer: bool = False,
                     family: str = "deepseek_v3") -> None:
    """The combinations the latent pool cannot serve yet, refused at
    engine construction, each naming what is missing (``family``: who
    refuses: models/longcat_flash.py keeps the same pool and refuses
    the same)."""
    from ..core.mesh import MODEL_AXIS

    def refuse(what, why):
        raise NotImplementedError(f"{family} does not serve {what}: {why}")

    if serving.kv_layout != "paged":
        refuse(f"kv_layout={serving.kv_layout!r}",
               "only the paged step keeps the latent lines (no dense "
               "latent cache)")
    if serving.prefix_caching:
        refuse("prefix_caching=True",
               "sharing, copying and spilling pages are written for k / v "
               "pools, not for the latent pool")
    if specinfer:
        refuse("SpecInfer or beam search",
               "commit_kv / reorder_slots are not written for the latent "
               "pool, and the draft module the model was trained with "
               "(its multi-token-prediction layers) is not built")
    if serving.kv_quant is not None:
        refuse(f"kv_quant={serving.kv_quant!r}",
               "the latent pool has no scale rows and its kernel no "
               "dequantizing variant")
    if serving.fused_decode:
        refuse(f"fused_decode={serving.fused_decode!r}",
               "the fused prologue writes K / V heads, not a latent line")
    if serving.kv_shard == "context":
        refuse(f"kv_shard={serving.kv_shard!r}",
               "the latent kernel has no ring over a sequence-sharded pool")
    if mesh.shape.get(MODEL_AXIS, 1) > 1:
        refuse("a mesh with model > 1",
               "neither the latent kernel's heads nor the grouped expert "
               "matmul is sharded yet")


def _no_latent_op(*_a, **_k):
    raise NotImplementedError(
        "the latent page pool: committing, copying, gathering or "
        "reordering cache lines is not written for it, and a family that "
        "keeps it has no dense-layout step (validate_serving)")


commit_kv_paged = reorder_slots_paged = copy_page_kv = _no_latent_op
gather_page_kv = scatter_page_kv = _no_latent_op
init_kv_cache = kv_cache_pspecs = serve_step = _no_latent_op
commit_kv = reorder_slots = _no_latent_op


# ---------------------------------------------------------------------------
# Cache: the latent pool


def init_paged_kv_cache(
    cfg: DeepseekV3Config, num_pages: int, page_size: int, dtype=None,
    kv_quant: Optional[str] = None, extra_rows: int = 0,
):
    """A token's line ``[c | kr]`` (after the norm and the rope) in two
    arrays, row ``num_pages`` of each the scratch page: ``latent``
    (layers, num_pages+1, page_size, kv_lora_rank), the lines' ``c``,
    and ``latent_rope`` (layers, num_pages+1, page_size / 2,
    2 * qk_rope_head_dim), their rope keys PAIRED: row j of a page
    holds tokens j and j + page_size / 2 side by side. Both minor axes
    are whole lane tiles, which keeps the pool where it is through the
    step's line write (serve/kernels, "Latent paged attention"); the
    bytes are the line's own, kv_lora_rank + qk_rope_head_dim values a
    token and layer."""
    return latent_pool(cfg, cfg.num_hidden_layers, num_pages, page_size,
                       dtype, kv_quant, extra_rows)


def latent_pool(cfg, lines: int, num_pages: int, page_size: int, dtype=None,
                kv_quant: Optional[str] = None, extra_rows: int = 0):
    """The two arrays of :func:`init_paged_kv_cache` with ``lines``
    lines a token on their leading axis: one a layer here, one an
    attention SUBLAYER where a layer attends more than once
    (models/longcat_flash.py: two)."""
    if kv_quant is not None or extra_rows:
        raise NotImplementedError(
            "the latent pool is neither quantized nor row-sharded "
            "(validate_serving refuses kv_quant and kv_shard='context')")
    if page_size % 2:
        raise ValueError(f"page_size {page_size} is odd: rope keys lie in pairs")
    dt = dtype or cfg.dtype
    pages = (lines, num_pages + 1)
    return {
        "latent": jnp.zeros(pages + (page_size, cfg.kv_lora_rank), dt),
        "latent_rope": jnp.zeros(
            pages + (page_size // 2, 2 * cfg.qk_rope_head_dim), dt)}


def paged_kv_cache_pspecs(cfg: DeepseekV3Config = None, *, pipeline: bool = False,
                          kv_quant: Optional[str] = None,
                          kv_shard: Optional[str] = None):
    return {name: P() for name in PAGE_POOLS}


# ---------------------------------------------------------------------------
# The blocks


def kv_up_halves(cfg: DeepseekV3Config, w_kvb):
    """``W_kvb`` (kv_lora_rank, H * (nope + v)) as (W_UK (c, H, nope),
    W_UV (c, H, v)): head h's columns are ``[k_nope_h | v_h]``."""
    w = w_kvb.reshape(cfg.kv_lora_rank, cfg.num_attention_heads,
                      cfg.qk_nope_head_dim + cfg.v_head_dim)
    return w[..., :cfg.qk_nope_head_dim], w[..., cfg.qk_nope_head_dim:]


def latent_line(cfg, p, h, rope, c_scale: float = 1.0):
    """One token's cached line from its normed input h (..., D):
    (``c_scale * rmsnorm(c_raw)``, ``rope(kr_raw)``). ``c_scale``: a
    constant factor on ``c`` alone, so on every head's keys AND values
    (models/longcat_flash.py's ``mla_scale_kv_lora``), applied in
    float32; 1: the line as it was."""
    raw = _mm(h, p["w_kva"])
    c = _norm(cfg, raw[..., :cfg.kv_lora_rank], p["kv_norm_scale"], None)
    if c_scale != 1.0:
        c = (c.astype(jnp.float32) * c_scale).astype(c.dtype)
    kr = apply_rope(raw[..., None, cfg.kv_lora_rank:], *rope)[..., 0, :]
    return c, kr


def absorbed_queries(cfg, p, h, rope, q_scale: float = 1.0):
    """What a head's query is against a cached line: (``q_nope_h
    W_UK_h^T`` (B, T, H, kv_lora_rank), ``rope(q_rope_h)`` (B, T, H,
    qk_rope)). ``q_scale``: a constant factor on the whole query, its
    rope channels too (``mla_scale_q_lora``); 1: as it was."""
    B, T, _ = h.shape
    cq = _norm(cfg, _mm(h, p["w_qa"]), p["q_norm_scale"], None)
    q = _mm(cq, p["w_qb"]).reshape(B, T, cfg.num_attention_heads, cfg.head_dim)
    if q_scale != 1.0:
        q = (q.astype(jnp.float32) * q_scale).astype(q.dtype)
    w_uk, _ = kv_up_halves(cfg, p["w_kvb"])
    q_abs = jnp.einsum("bthd,chd->bthc", q[..., :cfg.qk_nope_head_dim], w_uk,
                       preferred_element_type=jnp.float32).astype(h.dtype)
    return q_abs, apply_rope(q[..., cfg.qk_nope_head_dim:], *rope)


def latent_attention(cfg, ctx, p, h, carried, line, *, scale: float,
                     q_scale: float = 1.0, c_scale: float = 1.0):
    """One latent-attention sublayer of a paged step, for every family
    that keeps the latent pool (this one; models/longcat_flash.py):
    ``h`` (B, T, D) the sublayer's normed input, ``p`` its weights,
    ``line`` the index of its lines on the pool's leading axis (the
    layer here; ``2 i + j`` where layer i attends twice), ``scale``
    the softmax's, ``ctx`` the step's (rope table, page places, rows,
    kernels, pack). Writes the tokens' lines at their places, attends
    in the absorbed form through ``serve/kernels.mla_paged_attention``
    (its XLA twin), and projects out. -> (out (B, T, D), carried)."""
    from ..serve import kernels as _pk

    B, T, _ = h.shape
    H = cfg.num_attention_heads
    with sublayer("attn.proj"):
        c, kr = latent_line(cfg, p, h, ctx["rope"], c_scale)
    with sublayer("attn.write"):
        cp, krp = carried["latent"], carried["latent_rope"]
        phys, off = ctx["phys"], ctx["off"]
        cp = cp.at[line, phys, off].set(c.astype(cp.dtype))
        krp = _pk.write_rope_lines(
            krp, line, _spread_queries(kr, ctx["pack"]), ctx["page_table"],
            ctx["q_start"], ctx["q_len"])
    with sublayer("attn.proj"):
        q = absorbed_queries(cfg, p, h, ctx["rope"], q_scale)
    rows = (ctx["page_table"], ctx["q_start"], ctx["q_len"])
    with sublayer("attn.core"):
        q = [_spread_queries(q, ctx["pack"]) for q in q]    # (R, C, H, .)
        if ctx["kernels"] == "pallas":
            o = _pk.mla_paged_attention(
                *q, *(a.reshape((-1,) + a.shape[2:]) for a in (cp, krp)),
                *rows, scale=scale, row_offset=line * cp.shape[1])
        else:
            o = _pk.mla_paged_attention_xla(
                *q, _layer_of(cp, line), _layer_of(krp, line), *rows,
                scale=scale)
        # back on the token axis with (H, c) kept as they lie: folded
        # into one axis first, the whole (R, C) result is laid out anew
        if ctx["pack"] is not None:
            o = jnp.take(o.reshape((-1,) + o.shape[2:]), ctx["pack"][1],
                         axis=0, mode="clip")
        o = o.reshape(B, T, H, cfg.kv_lora_rank)
    with sublayer("attn.proj"):
        _, w_uv = kv_up_halves(cfg, p["w_kvb"])
        o = jnp.einsum("bthc,chd->bthd", o, w_uv,
                       preferred_element_type=jnp.float32).astype(h.dtype)
        out = _mm(o.reshape(B, T, H * cfg.v_head_dim), p["wo"])
    return out, dict(carried, latent=cp, latent_rope=krp)


def step_context(cache, tokens, positions, page_table, cache_len, pack):
    """What a paged step over the latent pool derives from its
    arguments before its layers run: (``token_axis`` (tokens,
    positions), padded (R, C) or packed (1, width); ``ctx`` without
    the family's own entries (``rope``, ``kernels``) and the rows'
    first positions ``q_start``: the lines' places, the rows' real
    queries, the real places, and ``pack``,
    :func:`transformer._pack_tokens`' second result, None unpacked)."""
    from ..serve.kernels import real_query_lengths

    C = tokens.shape[1]
    ps = cache["latent"].shape[2]
    q_len = real_query_lengths(positions, cache_len)  # real columns lead
    if pack is None:
        token_axis = (tokens, positions)
        phys, off = _page_lookup(page_table, positions, ps)
        real = (jnp.arange(C, dtype=jnp.int32)[None] < q_len[:, None]).reshape(-1)
        pack_idx = None
    else:
        (*token_axis, phys, off), pack_idx = _pack_tokens(
            tokens, positions, q_len, page_table, ps, cache_len, pack)
        real = token_axis[1][0] < cache_len
    return token_axis, dict(phys=phys, off=off, page_table=page_table,
                            q_len=q_len, pack=pack_idx, real=real)


def _mla_block(cfg, ctx, stack, index, x, carried):
    p = layer_weights(stack, index)
    h = _norm(cfg, x, p["attn_norm_scale"], None)
    out, carried = latent_attention(cfg, ctx, p, h, carried, index,
                                    scale=softmax_scale(cfg))
    return x + out, carried


def _dense_block(cfg, ctx, stack, index, x, carried):
    p = layer_weights(stack, index)
    return x + _ffn(cfg, p, _norm(cfg, x, p["mlp_norm_scale"], None)), carried


def route(cfg: DeepseekV3Config, p, h):
    """The router's choice for normed tokens h (N, D), over ALL its
    outputs: (experts (N, k), weights (N, k))."""
    return route_sigmoid_topk(
        h, p["w_router"], p["router_offset"], cfg.num_experts_per_tok,
        norm_topk=cfg.moe_norm_topk, scaling=cfg.routed_scaling_factor,
        groups=(cfg.n_group, cfg.topk_group), eps=1e-20)


def sparse_ffn(cfg, p, h, real, layer=None, kernels="xla"):
    """One sparse layer's FFN over a flat token axis: h (N, D) normed,
    ``real`` (N,). ``p``: the layer's router and shared-expert weights,
    and the routed experts' weights of the layer — or, with ``layer``,
    of every layer, stacked. The experts held compute their part, the
    shared expert the whole of its own.
    -> (out (N, D), counts (experts held,))."""
    experts, weights = route(cfg, p, h)
    _, held, routed = expert_routing(cfg)
    out, counts = routed_experts_ffn(
        h, real, experts, weights, p["w_gate"], p["w_up"], p["w_down"],
        experts_held=held, routed=routed, layer=layer, kernels=kernels)
    if cfg.n_shared_experts:
        out = out + _ffn(cfg, p["shared"], h)
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
    page_table: jnp.ndarray,  # (R, NP) int32
    *,
    cfg: DeepseekV3Config,
    cache_len: int,
    all_logits: bool = False,
    kernels: str = "xla",
    pack: Optional[int] = None,
    **unsupported,
):
    """The engine's paged step (models/transformer.serve_step_paged's
    contract, its packed token axis included). A row's real positions
    are its first columns, CONSECUTIVE from the first (every dispatch
    builds them so): attention is causal by position from that first
    position and the count of real columns, with no mask array. The
    returned cache also holds ``moe_counts`` (``step_counts``: an
    output, not an input)."""
    if mask is not None or cache_positions is not None or any(
            v for v in unsupported.values()):
        _no_latent_op()
    if pack is not None and all_logits:
        raise ValueError("a packed token axis returns one logits row a row")
    token_axis, ctx = step_context(
        cache, tokens, positions, page_table, cache_len, pack)
    with sublayer("attn.proj"):
        ctx["rope"] = rope_cos_sin(cfg, token_axis[1])
    ctx.update(kernels=kernels, q_start=positions[:, 0])
    x = _embed_in(cfg, params, *token_axis)
    carried = dict(cache, **{name: jnp.zeros(shape, jnp.int32)
                             for name, shape in step_counts(cfg).items()})
    blocks = {
        name: functools.partial(fn, cfg, ctx)
        for name, fn in (("mla", _mla_block), ("dense", _dense_block),
                         ("sparse", _sparse_block))}
    x, new_cache = run_layers(cfg.kinds, blocks, params, x, carried)
    return _head_logits(cfg, params, x, logits_idx, ctx["pack"],
                        all_logits), new_cache
