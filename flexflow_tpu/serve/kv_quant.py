"""Quantized paged KV cache — write-side math + layout registry.

FlexFlow Serve ships int4/int8 quantization as a first-class serving
feature (SURVEY.md, ``--4bit/8bit-quantization``); this repo already
quantizes *weights* (flexflow_tpu/quantization.py). KV-cache
quantization is the other half of the byte budget: at high concurrency
the paged pool (serve/paging.py) is what gates both pool capacity and
decode read bandwidth, so storing pages as int8 doubles the pages a
fixed HBM budget holds and halves the KV bytes the decode hot loop
streams (the EQuARX observation — arxiv 2506.17615 — applied to cache
reads instead of collectives).

Layout
------
A quantized page pool stores, per cache tensor (K and V):

* ``(L, num_pages+1, page_size, KV, dk/pack)`` code elements in place
  of the bf16/f32 pool (int8: one code per byte; int4: two nibble
  codes per byte along dk), and
* ``(L, num_pages+1, KV)`` **float32** scales — one symmetric amax
  scale per page per KV head (``k_scale``/``v_scale`` cache keys).

Dequantization happens *inside* attention (serve/kernels.py: the fused
Pallas ragged-paged kernel multiplies per-page scales into the
QK^T scores and the PV product; the XLA fallback dequantizes the
gathered virtual cache) — full-precision K/V never round-trip HBM.

Write-side contract (:func:`quant_line_write`)
----------------------------------------------
``serve_step``'s KV commit quantizes in the jitted step itself:

1. **amax scaling at commit time.** Each page's scale is the running
   amax (per KV head) of every line committed to it, divided by qmax.
2. **Rescale on growth.** When a new line's amax exceeds the page's
   scale, the page's existing codes are requantized to the new scale
   (``round(q * s_old / s_new)``) so one scale stays exact for the
   whole page. When the scale is unchanged the ratio is exactly 1.0
   and the rewrite is a bitwise identity.
3. **History independence.** A write at in-page offset 0 is by
   construction the first line a slot commits to that physical page
   (cache lines fill pages front to back; spliced prefix-cache pages
   are never written, and a COW'd tail page continues at offset > 0),
   so it RESETS the page's scale instead of inheriting a stale amax
   from the page's previous occupant. Quantized page content is
   therefore a pure function of the tokens written, never of
   allocation history — which is what keeps run-to-run generation
   bitwise deterministic and preemption/recompute parity exact.

int4 (``SPECS["int4"]``: qmax 7, pack=2) stores TWO codes per byte
packed along dk — byte ``j`` of a line carries head-dim entries ``j``
(low nibble) and ``j + dk/2`` (high nibble), each biased by +8 into
[1, 15] exactly like quantization.py's packed int4 weights (garbage
bytes of never-written lines decode to the out-of-band code -8, which
a zero page scale maps to 0.0). The halves-of-dk split (rather than
even/odd interleave) unpacks as one concatenate — no lane-crossing
reshuffle in the Pallas kernel. A fixed HBM budget holds ~4x the bf16
pages (less the scale rows); the same write-side contract
(running amax, rescale-on-growth, offset-0 reset) applies on the
unpacked code values, so int4 generation keeps the bitwise
run-to-run and preemption/recompute guarantees, at a wider
quantization tolerance than int8 (documented in README "Hierarchical
KV cache" and tests/test_kv_hierarchy.py).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class KVQuantSpec:
    """One quantized-KV storage layout (see module docstring)."""

    name: str
    bits: int
    qmax: float       # symmetric clip: codes live in [-qmax, qmax]
    dtype: Any        # storage dtype of the page pool
    pack: int = 1     # codes per storage element (int4 packs 2 along dk)

    @property
    def itemsize(self) -> int:
        return jnp.dtype(self.dtype).itemsize


SPECS = {
    "int8": KVQuantSpec("int8", 8, 127.0, jnp.int8, 1),
    # Packed nibbles along dk (halves split, bias +8 — see the module
    # docstring); uint8 storage is the pack=2 discriminator, matching
    # quantization.py's packed int4 weights.
    "int4": KVQuantSpec("int4", 4, 7.0, jnp.uint8, 2),
}


def resolve_spec(kv_quant: Optional[str]) -> Optional[KVQuantSpec]:
    """Validate a ``ServingConfig.kv_quant`` value. None passes
    through; unknown names are a ValueError."""
    if kv_quant is None:
        return None
    spec = SPECS.get(kv_quant)
    if spec is None:
        raise ValueError(
            f"unknown kv_quant {kv_quant!r} (expected one of "
            f"{sorted(SPECS)} or None)"
        )
    return spec


# ---------------------------------------------------------------------------
# nibble packing (pack=2 layouts). The pair lives in ONE place so the
# XLA write/read paths and the in-kernel Pallas unpack (serve/kernels.py
# mirrors the arithmetic op-for-op) can never drift: integer adds,
# shifts and masks only — exact on every backend.


def pack_nibbles(codes: jnp.ndarray) -> jnp.ndarray:
    """(..., dk) signed codes in [-8, 7] → (..., dk//2) uint8: byte j
    holds code j (low nibble) and code j + dk/2 (high nibble), each
    biased +8. dk must be even (the engine validates head_dim % pack
    up front)."""
    dk = codes.shape[-1]
    c = codes.astype(jnp.int32) + 8
    lo, hi = c[..., : dk // 2], c[..., dk // 2 :]
    return (lo | (hi << 4)).astype(jnp.uint8)


def unpack_nibbles(packed: jnp.ndarray) -> jnp.ndarray:
    """(..., dkp) uint8 → (..., 2*dkp) f32 signed codes (the inverse of
    :func:`pack_nibbles`; all-zero garbage bytes decode to -8, which a
    zero page scale maps to 0.0)."""
    b = packed.astype(jnp.int32)
    lo = (b & 0xF) - 8
    hi = ((b >> 4) & 0xF) - 8
    return jnp.concatenate([lo, hi], axis=-1).astype(jnp.float32)


def pool_pack(pool: jnp.ndarray) -> int:
    """Codes per storage element of a quantized page pool — uint8 IS
    the packed-nibble layout (int8 pools store one code per byte), the
    same storage-dtype convention quantization.py's weight path uses."""
    return 2 if pool.dtype == jnp.dtype(jnp.uint8) else 1


def quant_line_write(
    kq: jnp.ndarray,     # (P+1, ps, KV, dk) quantized page pool (one layer)
    scale: jnp.ndarray,  # (P+1, KV) f32 per-page-per-head scales
    phys: jnp.ndarray,   # (R, C) int32 physical page per new line
    off: jnp.ndarray,    # (R, C) int32 in-page offset per new line
    vals: jnp.ndarray,   # (R, C, KV, dk) full-precision lines to commit
    qmax: float,
    layer=None,          # int32 scalar: kq/scale are (L, P+1, ...) stacks
):
    """Commit full-precision K/V lines into a quantized page pool
    (the quantized twin of ``pool.at[phys, off].set(...)``) — running
    per-page amax scales, rescale-on-growth, offset-0 scale reset; see
    the module docstring for the contract. Returns ``(kq, scale)``.

    Duplicate page indices are safe throughout: the scale update is a
    commutative scatter-max, and every rescale scatter writes values
    that depend only on the page, so colliding writes are identical.
    Shared (refcounted > 1) pages are never the target of a line write
    — the prefix cache COWs the tail page before any slot appends — so
    rescaling page content in place cannot perturb another reader.

    Packed layouts (int4): the pool's trailing dim is dk/pack and the
    pack factor is inferred from the shapes; rescale unpacks the
    touched pages' nibbles, requantizes on code VALUES, and repacks —
    arithmetically identical to the int8 path per code, so every
    determinism guarantee above carries over unchanged.

    With ``layer`` the pool and scales are every layer's, stacked, and
    the commit touches that layer's pages inside them — the same values
    at ``[layer, page]`` as the per-layer call writes at ``[page]``, with
    no layer sliced out (the serving step's layer loop carries the
    stack; models/transformer.py).
    """
    at = () if layer is None else (layer,)  # index prefix of the layer
    scales = scale
    if at:
        scale = jax.lax.dynamic_index_in_dim(scales, layer, keepdims=False)
    P1, ps, KV, dkp = kq.shape[len(at):]
    R, C = phys.shape
    pack = vals.shape[-1] // dkp  # 1 (int8) or 2 (packed int4 nibbles)
    vf = vals.astype(jnp.float32)
    amax = jnp.max(jnp.abs(vf), axis=-1)  # (R, C, KV)

    def _codes(stored):
        return unpack_nibbles(stored) if pack == 2 else stored.astype(
            jnp.float32
        )

    def _store(codes):
        return pack_nibbles(codes) if pack == 2 else codes.astype(kq.dtype)

    # offset-0 writes mark the page's first use by its current owner:
    # drop the previous occupant's stale amax (history independence)
    first = jnp.zeros((P1,), jnp.int32).at[phys.reshape(-1)].max(
        (off.reshape(-1) == 0).astype(jnp.int32)
    )
    old = jnp.where(first[:, None] > 0, 0.0, scale)     # (P1, KV)
    new = old.at[phys].max(amax / qmax)                 # (P1, KV)

    # Rescale existing codes of every touched page to the grown scale
    # (identity when the scale did not move). Below the crossover the
    # per-line page gather is cheaper; past it (wide prefill chunks
    # touching few distinct pages many times) the full-pool elementwise
    # form does strictly less work than R*C duplicate page gathers.
    if R * C < P1:
        pages = phys.reshape(-1)                        # (R*C,)
        ratio = jnp.where(
            new[pages] > 0.0,
            old[pages] / jnp.maximum(new[pages], 1e-30),
            0.0,
        )                                               # (R*C, KV)
        content = _codes(kq[at + (pages,)])             # (R*C, ps, KV, dk)
        requant = jnp.round(content * ratio[:, None, :, None])
        kq = kq.at[at + (pages,)].set(_store(requant))
    else:
        ratio = jnp.where(
            new > 0.0, old / jnp.maximum(new, 1e-30), 0.0
        )                                               # (P1, KV)
        requant = jnp.round(
            _codes(kq[layer] if at else kq) * ratio[:, None, :, None]
        )
        kq = kq.at[layer].set(_store(requant)) if at else _store(requant)

    # quantize the new lines at their page's (final) scale and scatter
    s_line = new[phys]                                  # (R, C, KV)
    q = jnp.round(vf / jnp.maximum(s_line[..., None], 1e-30))
    q = jnp.clip(q, -qmax, qmax)
    kq = kq.at[at + (phys, off)].set(_store(q))
    return kq, scales.at[layer].set(new) if at else new


def quant_commit_lines(
    buf: jnp.ndarray,     # (L, P+1, ps, KV, dk) quantized pool
    scale: jnp.ndarray,   # (L, P+1, KV) f32
    s_phys: jnp.ndarray,  # (R, K) source physical pages
    s_off: jnp.ndarray,   # (R, K) source in-page offsets
    d_phys: jnp.ndarray,  # (R, K) destination physical pages
    d_off: jnp.ndarray,   # (R, K) destination in-page offsets
    qmax: float,
):
    """Move quantized lines between table-resolved positions (the
    SpecInfer KV commit, models/*.commit_kv_paged): dequantize the
    source lines at their page scales, then re-commit them through
    :func:`quant_line_write` so destination page scales stay exact
    (codes cannot move between pages verbatim — the pages' scales
    differ). Vectorized over the layer dim. Packed (int4) pools unpack
    the source nibbles here; the write side repacks. Returns
    ``(buf, scale)``."""
    rows = buf[:, s_phys, s_off]                        # (L, R, K, KV, dkp)
    rows = (
        unpack_nibbles(rows) if pool_pack(buf) == 2
        else rows.astype(jnp.float32)
    )                                                   # (L, R, K, KV, dk)
    rows = rows * scale[:, s_phys][..., None]           # dequant at src scale
    return jax.vmap(
        lambda b, s, r: quant_line_write(b, s, d_phys, d_off, r, qmax)
    )(buf, scale, rows)


def page_bytes(
    page_size: int,
    kv_heads: int,
    head_dim: int,
    itemsize: int,
    *,
    scale_heads: int = 0,
) -> int:
    """K+V bytes one physical page costs per layer: two pools of
    ``page_size × kv_heads × head_dim`` elements plus (quantized
    layouts) two f32 scale rows of ``scale_heads`` entries."""
    return 2 * (page_size * kv_heads * head_dim * itemsize
                + 4 * scale_heads)


def quantized_pool_pages(
    fp_pages: int,
    page_size: int,
    kv_heads: int,
    head_dim: int,
    fp_itemsize: int,
    spec: KVQuantSpec,
) -> int:
    """Bytes-per-page accounting: the number of QUANTIZED pages the HBM
    budget of ``fp_pages`` full-precision pages buys. This is how
    ``ServingConfig.max_cached_tokens`` keeps meaning "this much KV
    HBM" with ``kv_quant`` on — the same budget simply holds ~2x the
    pages at int8 and ~4x at packed int4 (vs bf16; the per-page f32
    scales cost ``8·KV / (2·KV·dk·itemsize)`` of a page, well under 1%
    at real head dims, which is why the measured ratios land at ≥1.9x
    and ≥3.8x rather than exactly 2x/4x)."""
    budget = fp_pages * page_bytes(page_size, kv_heads, head_dim,
                                   fp_itemsize)
    # pack>1 stores several codes per element along dk
    qpage = page_bytes(
        page_size, kv_heads, -(-head_dim // spec.pack), spec.itemsize,
        scale_heads=kv_heads,
    )
    return max(fp_pages, budget // qpage)
