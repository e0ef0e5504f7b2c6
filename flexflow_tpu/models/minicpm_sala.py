"""MiniCPM-SALA model family (openbmb, ``model_type: minicpm_sala``): a
decoder whose layers are of TWO kinds, in the order ``mixer_types``
gives — ``lightning-attn`` (linear attention: a decayed d x d state per
head and REQUEST, constant in the context length) and ``minicpm4``
(grouped-query softmax attention with no rope, and above ``dense_len``
keys InfLLM-v2's block-sparse choice: each query attends block 0, the
blocks of its last ``window`` tokens and the best-scoring others,
``topk`` blocks in all, one choice a KV group) — with muP scaling
(``scale_emb`` on the embedding, ``scale_depth / sqrt(depth)`` on both
residual branches, ``hidden / dim_model_base`` under the head). The
equations are written out in ``benchmarks/references/minicpm_sala.py``,
which the tests hold this file to.

Serving only, on the paged path (``kv_layout="paged"``), through the
engine's ordinary step programs:

* the layer loop lives HERE and not in :mod:`.transformer`: that loop
  scans one stack of identical layers, and a second kind of layer there
  would put a branch into every other family's step. This one walks the
  static order as runs of one kind (``_runs``), each run a
  ``fori_loop`` over its kind's stacked weights. Its carry holds the
  sparse layers' K/V pools, their compressed keys and the lightning
  layers' states, all updated in place (tests/test_chip_compile.py).
  Embedding, norms, FFN, rope, the K/V line write, the page lookup and
  the head are :mod:`.transformer`'s.
* the cache is the paged K/V pool of the SPARSE layers only
  (``k``/``v``: (sparse layers, pages+1, page, KV, d)) plus per-SLOT
  state (``SLOT_STATE``): ``state`` (lightning layers, slots, heads, d,
  d) float32, and ``kbar`` (sparse layers, slots, positions / stride,
  KV, d) float32, the mean of every ``kernel`` keys at every
  ``stride``, kept per slot (it is indexed by position alone, so a
  second class of page would add a table and save nothing at these
  sizes), brought up to date as lines arrive. The engine hands
  ``init_paged_kv_cache`` its slot count because this module declares
  ``SLOT_STATE``.
* what a step is handed decides everything: a row whose chunk starts at
  position 0 starts from a zero state (a new request in a reused slot,
  a preempted request's recompute: no scheduler hook), a position equal
  to the scratch position updates nothing, a row with no real position
  keeps its state bitwise.
* a step in which no row's last position is above ``dense_len`` runs
  the ragged paged kernel every family runs (``ff_ragged_paged_c<C>``);
  otherwise the choice is computed and applied as a mask a KV group
  over the dense paged read (``ff_sparse_paged_c<C>``,
  serve/kernels.sparse_paged_attention): exact, no page fetch saved
  yet. Both kernels are told each row's real queries (``q_len``), so
  the padding columns beside a decoding row's one query, which choose
  every block, keep none of its pages alive.
  The lightning layers are plain XLA: the chunked form for C > 1 (a
  decay-masked ``q k^T`` times ``v``, plus ``q S`` from the carried
  state, then the state's update), the recurrence itself for C = 1;
  state and accumulation in float32 at ``highest`` precision.
* ``cache["chosen"]`` keeps, for each sparse layer, slot and KV group,
  the blocks the row's LAST real position chose in the newest step
  (all False below ``dense_len``): what a comparison of choices reads.

What it refuses, at construction (``validate_serving``), each because
the per-slot state has no such operation yet: prefix caching (pages
can be shared, a state cannot be rebuilt from them), SpecInfer's tree
verify and beam search (``commit_kv`` / ``reorder_slots`` would need
the state rolled back), ``kv_quant``, ``fused_decode``,
``kv_shard="context"``, the dense layout, a mesh with ``model > 1``.

Weight names follow ``benchmarks/harness/model.py::make_params``'
rule: every norm's scale holds ``norm_scale`` (ones: ``q_norm_scale``,
``k_norm_scale``, ``o_norm_scale``), the mixers' output gate is
``w_ogate`` (normal, like the other projections; ``w_gate`` is the
FFN's), ``wo`` and ``w_down`` write into the residual stream (the
smaller std); there are no biases.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..obs.sublayers import sublayer
from .transformer import (
    DecoderConfig,
    _embed_in,
    _ffn,
    _layer_of,
    _lm_logits,
    _mm,
    _norm,
    _page_lookup,
    _pallas_pools,
    _write_kv_lines,
    apply_rope,
    rope_freqs,
    seeded_normal,
)

LIGHTNING, SPARSE = "lightning-attn", "minicpm4"
# the cache entries that are per SLOT, not per page: the engine passes
# its slot count to ``init_paged_kv_cache`` and counts their bytes apart
SLOT_STATE = ("state", "kbar", "chosen")
# the one of them a real token updates by a recurrence
# (SchedulerStats.recurrent_updates)
RECURRENT_STATE = "state"
FUSED_DECODE = ()
HIGHEST = lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class SalaConfig(DecoderConfig):
    mixer_types: Tuple[str, ...] = ()
    lightning_heads: int = 32
    lightning_head_dim: int = 128
    scale_emb: float = 12.0
    scale_depth: float = 1.4
    # the depth in the residual scale: the PUBLISHED one, also where
    # ``num_hidden_layers`` is cut (a depth-slice of the same model)
    scale_depth_layers: int = 32
    dim_model_base: int = 256
    # InfLLM-v2's sizes (MiniCPM4's ``sparse_config``)
    sparse_kernel: int = 32
    sparse_stride: int = 16
    sparse_block: int = 64
    sparse_topk: int = 64
    sparse_window: int = 2048
    sparse_init_blocks: int = 1
    dense_len: int = 8192
    # slots of per-slot state where ``init_paged_kv_cache`` is not told
    # (``benchmarks/tools/fit.py``; the engine always tells): a
    # configuration file's ``serving.max_requests_per_batch``
    state_slots: int = 0

    def __post_init__(self):
        super().__post_init__()
        kinds = self.mixer_types
        if len(kinds) != self.num_hidden_layers or set(kinds) - {LIGHTNING, SPARSE}:
            raise ValueError(
                f"mixer_types must name {self.num_hidden_layers} layers, "
                f"each {LIGHTNING!r} or {SPARSE!r}: got {kinds}")
        if self.sparse_block % self.sparse_stride or self.sparse_kernel % self.sparse_stride:
            raise ValueError(
                "sparse_config: block and kernel must be multiples of stride")
        if self.lightning_head_dim != self.head_dim:
            raise NotImplementedError(
                "one rope table serves both kinds of layer: "
                "lightning_head_dim must equal head_dim")

    def count(self, kind: str) -> int:
        return sum(k == kind for k in self.mixer_types)


def config(**kw) -> SalaConfig:
    d: Dict[str, Any] = dict(
        vocab_size=73448, hidden_size=4096, intermediate_size=16384,
        num_hidden_layers=32, num_attention_heads=32, num_key_value_heads=2,
        head_dim_override=128, max_position_embeddings=524288,
        norm_type="rmsnorm", norm_bias=False, norm_eps=1e-6,
        positions="rope", rope_theta=10000.0, activation="silu", glu=True,
        tie_word_embeddings=False,
    )
    d.update(kw)
    return SalaConfig(**d)


def tiny(**kw) -> SalaConfig:
    """CPU test size: one of each kind first, sparse sizes cut so that a
    few hundred tokens cross ``dense_len``."""
    d = dict(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=4, num_attention_heads=8, num_key_value_heads=2,
        head_dim_override=16, lightning_heads=4, lightning_head_dim=16,
        mixer_types=(SPARSE, LIGHTNING, LIGHTNING, SPARSE),
        scale_depth_layers=4, dim_model_base=32, max_position_embeddings=512,
        sparse_kernel=8, sparse_stride=4, sparse_block=16, sparse_topk=6,
        sparse_window=32, sparse_init_blocks=1, dense_len=96,
    )
    d.update(kw)
    return config(**d)


def from_hf(hf: Dict[str, Any], **kw) -> SalaConfig:
    """From the published ``config.json`` keys. ``num_hidden_layers``
    under ``len(mixer_types)`` takes the first entries. ``sparse_config``
    (kernel_size, kernel_stride, block_size, topk, window_size,
    init_blocks, dense_len) is MiniCPM4's group; the published file of
    this model does not carry it, a benchmark configuration states it."""
    n = kw.get("num_hidden_layers", hf["num_hidden_layers"])
    kinds = tuple(hf["mixer_types"])[:n]
    if hf.get("lightning_nkv", hf["lightning_nh"]) != hf["lightning_nh"]:
        raise NotImplementedError("lightning layers with grouped KV heads")
    if hf.get("lightning_use_rope") is False or hf.get("attn_use_rope"):
        raise NotImplementedError(
            "only the published rope placement is built: lightning layers "
            "rotate, minicpm4 layers do not")
    sp = hf.get("sparse_config", {})
    d = dict(
        vocab_size=hf["vocab_size"], hidden_size=hf["hidden_size"],
        intermediate_size=hf["intermediate_size"], num_hidden_layers=n,
        num_attention_heads=hf["num_attention_heads"],
        num_key_value_heads=hf["num_key_value_heads"],
        head_dim_override=hf["head_dim"],
        max_position_embeddings=hf["max_position_embeddings"],
        norm_eps=hf.get("rms_norm_eps", 1e-6),
        rope_theta=float(hf.get("rope_theta", 10000.0)),
        tie_word_embeddings=hf.get("tie_word_embeddings", False),
        mixer_types=kinds,
        lightning_heads=hf["lightning_nh"],
        lightning_head_dim=hf["lightning_head_dim"],
        scale_emb=float(hf["scale_emb"]), scale_depth=float(hf["scale_depth"]),
        scale_depth_layers=int(hf.get("scale_depth_layers", len(hf["mixer_types"]))),
        dim_model_base=hf["dim_model_base"],
        state_slots=int(hf.get("serving", {}).get("max_requests_per_batch", 0)),
    )
    for key, field in (("kernel_size", "sparse_kernel"), ("kernel_stride", "sparse_stride"),
                       ("block_size", "sparse_block"), ("topk", "sparse_topk"),
                       ("window_size", "sparse_window"), ("init_blocks", "sparse_init_blocks"),
                       ("dense_len", "dense_len")):
        if key in sp:
            d[field] = int(sp[key])
    d.update(kw)
    return config(**d)


# ---------------------------------------------------------------------------
# Parameters: two stacked groups (one a kind of layer) and the ends


def _group_shapes(cfg: SalaConfig, kind: str) -> Dict[str, Tuple[int, ...]]:
    D, F = cfg.hidden_size, cfg.intermediate_size
    if kind == LIGHTNING:
        H = KV = cfg.lightning_heads
        d = cfg.lightning_head_dim
    else:
        H, KV, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    shapes = {
        "attn_norm_scale": (D,), "wq": (D, H * d), "wk": (D, KV * d),
        "wv": (D, KV * d), "w_ogate": (D, H * d), "q_norm_scale": (d,),
        "k_norm_scale": (d,), "wo": (H * d, D), "mlp_norm_scale": (D,),
        "w_gate": (D, F), "w_up": (D, F), "w_down": (F, D),
    }
    if kind == LIGHTNING:
        shapes["o_norm_scale"] = (H * d,)
    return shapes


def init_params(key, cfg: SalaConfig) -> Dict[str, Any]:
    std = 0.02
    out_std = std / math.sqrt(2 * cfg.num_hidden_layers)
    keys = iter(jax.random.split(key, 64))

    def leaf(name, shape):
        if "norm_scale" in name:
            return jnp.ones(shape, cfg.dtype)
        scale = out_std if name in ("wo", "w_down") else std
        return seeded_normal(next(keys), scale, shape=shape, dtype=cfg.dtype)

    params = {
        "embed": leaf("embed", (cfg.vocab_size, cfg.hidden_size)),
        "final_norm_scale": leaf("final_norm_scale", (cfg.hidden_size,)),
        "lm_head": leaf("lm_head", (cfg.hidden_size, cfg.vocab_size)),
    }
    for group, kind in (("lightning", LIGHTNING), ("sparse", SPARSE)):
        n = cfg.count(kind)
        params[group] = {
            name: leaf(name, (n,) + shape)
            for name, shape in _group_shapes(cfg, kind).items()
        }
    return params


def param_pspecs(cfg: SalaConfig, *, pipeline: bool = False) -> Dict[str, Any]:
    """Every weight whole on every device: one chip, or replicas
    (``validate_serving`` refuses ``model > 1``)."""
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    return jax.tree.map(lambda _: P(), shapes)


def num_params(cfg: SalaConfig) -> int:
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    return sum(math.prod(a.shape) for a in jax.tree.leaves(shapes))


def validate_serving(cfg: SalaConfig, serving, mesh, *, specinfer: bool = False) -> None:
    """The combinations this family's per-slot state cannot serve yet,
    refused at engine construction, each naming what is missing."""
    from ..core.mesh import MODEL_AXIS

    def refuse(what, why):
        raise NotImplementedError(f"minicpm_sala does not serve {what}: {why}")

    if serving.kv_layout != "paged":
        refuse(f"kv_layout={serving.kv_layout!r}",
               "only the paged step carries the lightning states and the "
               "compressed keys beside the pool")
    if serving.prefix_caching:
        refuse("prefix_caching=True",
               "pages can be shared between requests, a lightning layer's "
               "recurrent state cannot be rebuilt from them (no state "
               "snapshot at a page boundary yet)")
    if specinfer:
        refuse("SpecInfer or beam search",
               "commit_kv / reorder_slots would have to roll the per-slot "
               "recurrent state back to the accepted token, and no snapshot "
               "is kept")
    if serving.kv_quant is not None:
        refuse(f"kv_quant={serving.kv_quant!r}",
               "the sparse layers' compressed keys and block scores are "
               "computed from full-precision lines")
    if serving.fused_decode:
        refuse(f"fused_decode={serving.fused_decode!r}",
               "the fused prologue knows one kind of layer")
    if serving.kv_shard == "context":
        refuse(f"kv_shard={serving.kv_shard!r}",
               "the block choice reads a row's whole context through one "
               "page table")
    if mesh.shape.get(MODEL_AXIS, 1) > 1:
        refuse("a mesh with model > 1",
               "neither the lightning state nor the per-group block choice "
               "is sharded over heads yet")


def _no_state_rollback(*_a, **_k):
    raise NotImplementedError(
        "minicpm_sala keeps per-slot recurrent state: committing, copying "
        "or reordering cache lines would need that state rolled back or "
        "moved with them, and no snapshot is kept")


commit_kv_paged = reorder_slots_paged = copy_page_kv = _no_state_rollback
gather_page_kv = scatter_page_kv = _no_state_rollback
init_kv_cache = kv_cache_pspecs = serve_step = _no_state_rollback
commit_kv = reorder_slots = _no_state_rollback


# ---------------------------------------------------------------------------
# Cache: the sparse layers' paged pool, and per-slot state beside it


def _virtual_len(page_size: int, cache_len: int) -> int:
    """Lines of one slot's page-aligned virtual cache: what the page
    table's width (``ServingConfig.pages_per_slot``) covers."""
    return -(-(cache_len + 1) // page_size) * page_size


def init_paged_kv_cache(
    cfg: SalaConfig, num_pages: int, page_size: int, dtype=None,
    kv_quant: Optional[str] = None, extra_rows: int = 0, *,
    num_slots: Optional[int] = None, cache_len: Optional[int] = None,
):
    """``k``/``v``: (sparse layers, num_pages+1, page_size, KV, d), row
    ``num_pages`` the scratch page; ``state``, ``kbar``, ``chosen``: per
    slot (module docstring). ``cache_len``: the longest context a slot
    may hold (the engine's); without it, all the pool's pages."""
    if kv_quant is not None or extra_rows:
        raise NotImplementedError(
            "minicpm_sala's pool is neither quantized nor row-sharded "
            "(validate_serving refuses kv_quant and kv_shard='context')")
    slots = num_slots or cfg.state_slots
    if not slots:
        raise ValueError(
            "minicpm_sala keeps per-slot state: init_paged_kv_cache needs "
            "num_slots (the engine passes its own)")
    lines = _virtual_len(page_size, cache_len or num_pages * page_size - 1)
    ns, nl = cfg.count(SPARSE), cfg.count(LIGHTNING)
    KV, d = cfg.num_key_value_heads, cfg.head_dim
    H, dl = cfg.lightning_heads, cfg.lightning_head_dim
    pool = (ns, num_pages + 1, page_size, KV, d)
    dt = dtype or cfg.dtype
    return {
        "k": jnp.zeros(pool, dt), "v": jnp.zeros(pool, dt),
        "state": jnp.zeros((nl, slots, H, dl, dl), jnp.float32),
        "kbar": jnp.zeros((ns, slots, lines // cfg.sparse_stride, KV, d),
                          jnp.float32),
        "chosen": jnp.zeros((ns, slots, KV, -(-lines // cfg.sparse_block)), bool),
    }


def paged_kv_cache_pspecs(cfg: SalaConfig = None, *, pipeline: bool = False,
                          kv_quant: Optional[str] = None,
                          kv_shard: Optional[str] = None):
    return {name: P() for name in ("k", "v") + SLOT_STATE}


# ---------------------------------------------------------------------------
# The two mixers


def _head_norm(cfg, x, scale):
    """RMSNorm over each head's own d values (q/k norm)."""
    return _norm(cfg, x, scale, None)


def lightning_slopes(heads: int) -> jnp.ndarray:
    """Lightning Attention's decay: lambda_h = exp(-slope_h), slope_h =
    2^(-8 (h + 1) / heads); no per-layer factor (ASSUMED: the published
    file gives none)."""
    return 2.0 ** (-8.0 * jnp.arange(1, heads + 1, dtype=jnp.float32) / heads)


def lightning_attend(q, k, v, state, real, fresh):
    """Linear attention of one step over the carried state.

    q, k, v (R, C, H, d); ``state`` (R, H, d, d) float32; ``real``
    (R, C): the positions that exist, a row's first ``count`` columns;
    ``fresh`` (R,): rows that start from zero state. Returns
    (o (R, C, H, d) float32, not yet divided by sqrt(d); new state).
    A row with no real position keeps its state bitwise."""
    R, C, H, d = q.shape
    f32 = jnp.float32
    q, k, v = q.astype(f32), k.astype(f32), v.astype(f32)
    slope = lightning_slopes(H)
    active = real[:, 0]
    s0 = jnp.where(fresh[:, None, None, None], 0.0, state)
    if C == 1:  # the recurrence itself
        lam = jnp.exp(-slope)[None, :, None, None]
        s1 = lam * s0 + k[:, 0, :, :, None] * v[:, 0, :, None, :]
        o = jnp.einsum("rhd,rhde->rhe", q[:, 0], s1, precision=HIGHEST)[:, None]
    else:
        i = jnp.arange(C, dtype=f32)
        count = jnp.sum(real, axis=1).astype(f32)                  # (R,)
        # what the carried state gives position i: decayed i + 1 steps
        carried = jnp.einsum("rchd,rhde->rche", q, s0, precision=HIGHEST)
        carried = carried * jnp.exp(-slope[None, :] * (i[:, None] + 1.0))[None, :, :, None]
        # inside the chunk: position i sees j <= i, decayed i - j steps
        gap = i[:, None] - i[None, :]
        decay = jnp.where(gap >= 0, jnp.exp(-slope[:, None, None] * jnp.maximum(gap, 0.0)), 0.0)
        a = jnp.einsum("rihd,rjhd->rhij", q, k, precision=HIGHEST)
        a = a * decay[None] * real[:, None, None, :]
        o = carried + jnp.einsum("rhij,rjhe->rihe", a, v, precision=HIGHEST)
        # the state after the row's last real position
        left = count[:, None] - 1.0 - i[None, :]                  # (R, C)
        w = jnp.where(real[..., None], jnp.exp(-slope[None, None, :] * jnp.maximum(left, 0.0)[..., None]), 0.0)
        s1 = (jnp.exp(-slope[None, :] * count[:, None])[..., None, None] * s0
              + jnp.einsum("rjhd,rjhe->rhde", k * w[..., None], v, precision=HIGHEST))
    return o, jnp.where(active[:, None, None, None], s1, state)


@sublayer("mixer")
def _lightning_mixer(cfg, p, u, rope, state, real, fresh):
    R, C, _ = u.shape
    H, d = cfg.lightning_heads, cfg.lightning_head_dim
    q = _head_norm(cfg, _mm(u, p["wq"]).reshape(R, C, H, d), p["q_norm_scale"])
    k = _head_norm(cfg, _mm(u, p["wk"]).reshape(R, C, H, d), p["k_norm_scale"])
    v = _mm(u, p["wv"]).reshape(R, C, H, d)
    q, k = apply_rope(q, *rope), apply_rope(k, *rope)
    o, state = lightning_attend(q, k, v, state, real, fresh)
    o = (o / math.sqrt(d)).astype(u.dtype).reshape(R, C, H * d)
    o = _norm(cfg, o, p["o_norm_scale"], None) * jax.nn.sigmoid(_mm(u, p["w_ogate"]))
    return _mm(o, p["wo"]), state


@sublayer("attn.select")
def _update_kbar(cfg, kbar, k_pool, layer, page_table, first, last, active, C):
    """Bring one sparse layer's compressed keys up to date with the
    lines a step of chunk ``C`` has just written: entry j is the mean of
    the ``kernel`` keys from position ``stride * j`` on, complete once
    its last key is there, so a step completes the entries whose last
    key lies among its own positions ``first..last`` (at most
    ceil(C / stride) of them). Their keys are read back from the pool
    through the page table: an entry may begin in an earlier chunk."""
    R = first.shape[0]
    ps = k_pool.shape[2]
    ker, st = cfg.sparse_kernel, cfg.sparse_stride
    n_new = -(-C // st)
    j0 = jnp.maximum(0, (first - ker + st) // st)                   # (R,)
    js = j0[:, None] + jnp.arange(n_new)[None, :]                    # (R, n_new)
    ends = st * js + ker - 1
    done = active[:, None] & (ends >= first[:, None]) & (ends <= last[:, None])
    pos = st * j0[:, None] + jnp.arange(st * (n_new - 1) + ker)[None, :]
    pos = jnp.minimum(pos, page_table.shape[1] * ps - 1)
    phys, off = _page_lookup(page_table, pos, ps)
    lines = k_pool[layer, phys, off].astype(jnp.float32)            # (R, W, KV, d)
    means = jnp.stack(
        [lines[:, st * i: st * i + ker].mean(axis=1) for i in range(n_new)],
        axis=1)                                                      # (R, n_new, KV, d)
    js = jnp.where(done, js, kbar.shape[2])                          # out of range: dropped
    rows = jnp.arange(R)[:, None]
    return kbar.at[layer, rows, js].set(means, mode="drop")


@sublayer("attn.select")
def choose_blocks(cfg, q, kbar, positions, real):
    """InfLLM-v2's choice for every query: (R, C, KV, blocks) bool, the
    ``topk`` blocks a query at position t (n = t + 1 keys visible)
    attends, one choice a KV group: block scores are the group's summed
    softmax over the compressed keys, a block taking the best of the
    entries that overlap it; the first ``init_blocks`` blocks and those
    of the last ``window`` tokens always count among the ``topk``.
    Positions at or under ``dense_len`` keys, and padding, choose every
    block (the caller's causal mask is then all there is).

    q (R, C, H, d) normed; kbar (R, entries, KV, d) float32."""
    R, C, H, d = q.shape
    KV = kbar.shape[2]
    ker, st, blk = cfg.sparse_kernel, cfg.sparse_stride, cfg.sparse_block
    NJ = kbar.shape[1]
    NB = -(-NJ * st // blk)
    n = positions + 1                                                # (R, C)
    qg = q.astype(jnp.float32).reshape(R, C, KV, H // KV, d)
    s = jnp.einsum("rckgd,rjkd->rckgj", qg, kbar, precision=HIGHEST) / math.sqrt(d)
    whole = (st * jnp.arange(NJ) + ker)[None, None, :] <= n[:, :, None]   # (R, C, NJ)
    s = jnp.where(whole[:, :, None, None, :], s, -1e30)
    p = jnp.where(whole[:, :, None, None, :], jax.nn.softmax(s, axis=-1), 0.0)
    r = jnp.where(whole[:, :, None, :], p.sum(axis=3), -1.0)         # (R, C, KV, NJ)
    # block m takes the best entry j with [st j, st j + ker) meeting
    # [blk m, blk m + blk): j from per*m - (span - 1) to per*m + per - 1
    per, span = blk // st, ker // st
    rp = jnp.pad(r, ((0, 0),) * 3 + ((span - 1, per * NB - NJ + per + span),),
                 constant_values=-1.0)
    score = rp[..., : per * NB].reshape(R, C, KV, NB, per).max(axis=-1)
    for e in range(span - 1):
        score = jnp.maximum(score, rp[..., per + e:: per][..., :NB])
    m = jnp.arange(NB)
    t = positions[:, :, None]                                        # (R, C, 1)
    visible = blk * m[None, None, :] <= t
    forced = (m[None, None, :] < cfg.sparse_init_blocks) | (
        blk * (m[None, None, :] + 1) > t + 1 - cfg.sparse_window)
    score = jnp.where(forced[:, :, None, :], 1e9, score)
    score = jnp.where(visible[:, :, None, :], score, -2.0)
    # rank by score, the lower index first among equals
    above = (score[..., None, :] > score[..., :, None]) | (
        (score[..., None, :] == score[..., :, None])
        & (m[None, :] < m[:, None]))
    chosen = (above.sum(axis=-1) < cfg.sparse_topk) & visible[:, :, None, :]
    dense = ~real | (n <= cfg.dense_len)
    return chosen | dense[:, :, None, None]


def _sparse_mixer(cfg, p, u, k_pool, v_pool, kbar, chosen_last, layer, ctx):
    from ..serve import kernels as _pk

    R, C, _ = u.shape
    H, KV, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    with sublayer("attn.proj"):
        q = _head_norm(cfg, _mm(u, p["wq"]).reshape(R, C, H, d), p["q_norm_scale"])
        k = _head_norm(cfg, _mm(u, p["wk"]).reshape(R, C, KV, d), p["k_norm_scale"])
        v = _mm(u, p["wv"]).reshape(R, C, KV, d)
    k_pool, v_pool, _, _ = _write_kv_lines(
        k_pool, v_pool, None, None, layer, ctx["phys"], ctx["off"], k, v, None)
    kbar = _update_kbar(cfg, kbar, k_pool, layer, ctx["page_table"],
                        ctx["first"], ctx["last"], ctx["active"], C)
    blk = cfg.sparse_block

    @sublayer("attn.core")
    def attend(mask, group_mask):
        if ctx["kernels"] == "pallas":
            k_rows, v_rows, kw = _pallas_pools(k_pool, v_pool, None, None, layer)
            fn = _pk.sparse_paged_attention if group_mask else _pk.ragged_paged_attention
            return fn(q, k_rows, v_rows, ctx["page_table"], mask,
                      row_offset=kw["row_offset"], q_len=ctx["q_len"],
                      work=ctx["work"])
        k_virt = _pk.gather_pages(_layer_of(k_pool, layer), ctx["page_table"])
        v_virt = _pk.gather_pages(_layer_of(v_pool, layer), ctx["page_table"])
        qg = q.reshape(R, C, KV, H // KV, d)
        s = jnp.einsum("rckgd,rskd->rkgcs", qg, k_virt,
                       preferred_element_type=jnp.float32) / math.sqrt(d)
        m = mask[:, :, None] if group_mask else mask[:, None, None]
        probs = jax.nn.softmax(jnp.where(m, s, -1e30), axis=-1).astype(q.dtype)
        return jnp.einsum("rkgcs,rskd->rckgd", probs, v_virt).reshape(R, C, H, d)

    # this layer's entries over this step's virtual cache (the per-slot
    # arrays may be sized for a longer one), taken out here: the choice
    # then reads one layer's entries and the carry stays out of the
    # conditional, which would copy all of it
    entries = ctx["causal"].shape[-1] // cfg.sparse_stride
    kbar_l = _layer_of(kbar, layer)[:, :entries]
    blocks = chosen_last.shape[1:]
    wider = blocks[-1] - -(-entries * cfg.sparse_stride // blk)  # of the kept choice

    def sparse(_):
        chosen = choose_blocks(cfg, q, kbar_l, ctx["positions"],
                               ctx["real"])                          # (R, C, KV, NB)
        with sublayer("attn.select"):
            lines = jnp.repeat(chosen.transpose(0, 2, 1, 3), blk, axis=-1)
            mask = lines[..., :ctx["causal"].shape[-1]] & ctx["causal"][:, None]  # (R, KV, C, S)
            at_last = jnp.take_along_axis(
                chosen, ctx["last_col"][:, None, None, None], axis=1)[:, 0]
            at_last = at_last & ctx["sparse_row"][:, None, None]
        o = attend(mask, True)
        with sublayer("attn.select"):
            kept = jnp.pad(at_last, ((0, 0), (0, 0), (0, wider)))
        return o, kept

    def dense(_):
        return attend(ctx["causal"], False), jnp.zeros(blocks, bool)

    o, at_last = lax.cond(ctx["any_sparse"], sparse, dense, None)
    with sublayer("attn.select"):
        chosen_last = lax.dynamic_update_index_in_dim(
            chosen_last, at_last, layer, 0)
    with sublayer("attn.proj"):
        o = o.reshape(R, C, H * d) * jax.nn.sigmoid(_mm(u, p["w_ogate"]))
        out = _mm(o, p["wo"])
    return out, k_pool, v_pool, kbar, chosen_last


# ---------------------------------------------------------------------------
# The step


def _runs(kinds):
    """The static layer order as runs of one kind: (kind, index of the
    run's first layer within its kind's stack, length)."""
    runs, seen = [], {LIGHTNING: 0, SPARSE: 0}
    for kind in kinds:
        if runs and runs[-1][0] == kind:
            runs[-1][2] += 1
        else:
            runs.append([kind, seen[kind], 1])
        seen[kind] += 1
    return [tuple(r) for r in runs]


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
    cfg: SalaConfig,
    cache_len: int,
    all_logits: bool = False,
    kernels: str = "xla",
    **unsupported,
):
    """The engine's paged step (models/transformer.serve_step_paged's
    contract) over the hybrid layer order. A row's real positions are
    its first columns, consecutive; a row whose first position is 0
    starts from zero state (module docstring)."""
    if mask is not None or cache_positions is not None or any(
            v for v in unsupported.values()):
        _no_state_rollback()
    from ..serve.kernels import paged_serve_mask, real_query_lengths, step_work

    R, C = tokens.shape
    ps = cache["k"].shape[2]
    a = cfg.scale_depth / math.sqrt(cfg.scale_depth_layers)
    x = _embed_in(cfg, params, tokens, positions)
    x = x * jnp.asarray(cfg.scale_emb, x.dtype)
    real = positions < cache_len
    first = positions[:, 0]
    q_len = real_query_lengths(positions, cache_len)  # real columns lead
    last = first + q_len - 1
    phys, off = _page_lookup(page_table, positions, ps)
    sparse_row = real[:, 0] & (last >= cfg.dense_len)
    ctx = dict(
        positions=positions, real=real, first=first, last=last,
        active=real[:, 0], phys=phys, off=off, page_table=page_table,
        kernels=kernels, sparse_row=sparse_row,
        any_sparse=jnp.any(sparse_row),
        last_col=jnp.maximum(last - first, 0),
        q_len=q_len,
        causal=paged_serve_mask(None, positions, page_table.shape[1], ps, cache_len),
        # the causal range for the sparse call too: the pages no query
        # CHOSE inside it stay the body's guard's
        work=(step_work(positions, q_len, ps, page_table.shape[1])
              if kernels == "pallas" else None),
    )
    fresh = real[:, 0] & (first == 0)
    with sublayer("mixer"):  # the lightning layers alone take RoPE
        rope = rope_freqs(cfg, positions)

    def ffn(p, x):
        n = _norm(cfg, x, p["mlp_norm_scale"], None)
        return x + (a * _ffn(cfg, p, n)).astype(x.dtype)

    def lightning_body(l, carry):
        x, pools, state = carry
        p = jax.tree.map(lambda w: _layer_of(w, l), params["lightning"])
        u = _norm(cfg, x, p["attn_norm_scale"], None)
        o, s_l = _lightning_mixer(cfg, p, u, rope, _layer_of(state, l), real, fresh)
        state = lax.dynamic_update_index_in_dim(state, s_l, l, 0)
        return ffn(p, x + (a * o).astype(x.dtype)), pools, state

    def sparse_body(l, carry):
        x, (kp, vp, kbar, chosen), state = carry
        p = jax.tree.map(lambda w: _layer_of(w, l), params["sparse"])
        u = _norm(cfg, x, p["attn_norm_scale"], None)
        o, kp, vp, kbar, chosen = _sparse_mixer(cfg, p, u, kp, vp, kbar, chosen, l, ctx)
        return ffn(p, x + (a * o).astype(x.dtype)), (kp, vp, kbar, chosen), state

    carry = (x, (cache["k"], cache["v"], cache["kbar"], cache["chosen"]),
             cache["state"])
    for kind, start, n in _runs(cfg.mixer_types):
        body = lightning_body if kind == LIGHTNING else sparse_body
        carry = lax.fori_loop(start, start + n, body, carry)
    x, (kp, vp, kbar, chosen), state = carry
    new_cache = {"k": kp, "v": vp, "kbar": kbar, "chosen": chosen, "state": state}
    with sublayer("head"):
        x = _norm(cfg, x, params["final_norm_scale"], None)
        x = x / jnp.asarray(cfg.hidden_size / cfg.dim_model_base, x.dtype)
        if not all_logits:
            x = jnp.take_along_axis(x, logits_idx[:, None, None], axis=1)
            return _lm_logits(cfg, params, x)[:, 0], new_cache
        return _lm_logits(cfg, params, x), new_cache
