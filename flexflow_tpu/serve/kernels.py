"""Pallas TPU kernels for the serving hot path.

The reference hand-writes CUDA kernels for generation-phase attention
(reference ``inc_multihead_self_attention.cu:46`` custom decode kernel,
``spec_inc_…`` beam and ``tree_inc_…`` verify variants). On TPU the
prefill path is MXU-shaped already (big GEMMs — XLA does it well), but
**decode** attention (one query token against a long KV cache) is
bandwidth-bound and benefits from a fused flash-style kernel: QK^T →
online softmax → PV in VMEM, one pass over the cache, no (R, H, S)
score tensor ever hitting HBM.

:func:`decode_attention` — grid (request, cache-chunk); per-request
online-softmax accumulators persist in VMEM scratch across the chunk
dimension. Per-request ``seq_lens`` mask invalid cache lines, so one
static-shape program serves every request length (the reference pads to
MAX_NUM_TOKENS the same way, batch_config.h:58-60).

:func:`verify_attention` — the tree-verify variant: C query tokens per
request with an explicit (C, S) boolean mask (the reference's causal
``BitMask``), same online-softmax core.

:func:`ragged_paged_attention` — the paged-KV variant (PAPERS.md,
arxiv 2604.15464 Ragged Paged Attention): K/V live in a pool of
fixed-size token pages and the kernel gathers them **through the page
table** — the grid is (request, logical page) and the K/V BlockSpec
index maps read the scalar-prefetched table to DMA the right physical
page, so no (R, S) virtual cache is ever materialised in HBM. One
kernel serves decode (C=1), chunked prefill and tree verify (C>1, any
mask) — the single ragged kernel for mixed batches the paper argues
for. Told each row's real queries (``q_len``,
:func:`real_query_lengths`) it works in proportion to them: a padding
column keeps no page alive, and a row of a few real queries (a decode
row of a mixed step) is computed at :func:`narrow_query_extent` and
not at the chunk's width. :func:`ragged_paged_attention_xla` is the
shape-identical ``jnp.take``-based fallback (via :func:`gather_pages`)
used on CPU and as the correctness reference.

:func:`fused_rope_paged_attention` — the **megakernel decode step**
prologue (MPK, "Mega-Kernelizing Tensor Programs", PAPERS.md): RoPE on
Q/K and the (optionally int8-quantizing) KV page write move INSIDE the
ragged paged grid, so a decode step's fresh K/V lines are rotated,
quantized and committed in VMEM and read back by attention in the same
kernel — they never round-trip HBM between the step's QKV projection
and the attention read, and the separate rope/scatter XLA ops (and
their dispatch latency) disappear from the step program.

Kernel-variant matrix — every Pallas variant of the ragged paged
kernel is emitted by ONE parameterized builder
(:func:`_build_ragged_paged_kernel`), so the quant and fused axes
compose instead of multiplying hand-written kernel bodies:

====================  =======================  =========================
variant               Pallas entry point       XLA fallback (CPU parity)
====================  =======================  =========================
plain                 ragged_paged_attention   ragged_paged_attention_xla
int8 pages            ragged_paged_attention   ragged_paged_attention_xla
                      (k_scale/v_scale)        (k_scale/v_scale)
int4 pages            ragged_paged_attention   ragged_paged_attention_xla
(packed nibbles)      (uint8 pool; nibble      (dequant_pages unpacks the
                      unpack in VMEM)          gathered codes)
fused RoPE+KV-write   fused_rope_paged_        the unfused serving step
                      attention                itself: rope + scatter +
                                               gather is ALREADY the
                                               reference math, so
                                               ``fused_decode`` with
                                               kernels="xla" is a no-op
fused + int8/int4     fused_rope_paged_        same, via quant_line_write
                      attention (qmax)
====================  =======================  =========================

The quant axis carries a ``pack`` factor inferred from the pool shapes
(``dk // pool.shape[-1]``): pack=2 pools (int4) DMA uint8 pages of
half the int8 bytes and unpack two nibble codes per byte in VMEM
(``kv_quant.unpack_nibbles`` arithmetic, mirrored op-for-op by
:func:`_unpack_codes` below — integer masks/shifts, exact on every
backend) before the same scale-folded dots; the fused write side packs
through the in-kernel twin of ``kv_quant.pack_nibbles``.

Every fused variant is bitwise-identical to its unfused counterpart on
the same backend: the builder reuses one attention body (same op
order, same online-softmax accumulation over the same (request, page)
grid), the in-kernel RoPE mirrors ``apply_rope`` op-for-op, and the
in-kernel quantized commit mirrors ``kv_quant.quant_line_write``
page-locally (running amax, rescale-on-growth, offset-0 reset). The
only unspecified bytes are the shared scratch page's, which both paths
write with padding garbage and neither ever reads.

On the CPU backend the Pallas kernels run with ``interpret=True`` so
tests run on the CPU mesh; any backend other than tpu/cpu is an error.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..ops.flash_attention import _block_positions, _interpret

NEG_INF = -1e30


def _decode_kernel(
    seq_ref,      # scalar-prefetch: (R,) int32 valid cache length per slot
    q_ref,        # (1, KV, G, dk)
    k_ref,        # (1, CS, KV, dk)
    v_ref,        # (1, CS, KV, dk)
    out_ref,      # (1, KV, G, dk)
    o_scr,        # VMEM (KV, G, dk) f32
    m_scr,        # VMEM (KV, G) f32
    l_scr,        # VMEM (KV, G) f32
    *,
    block_s: int,
    scale: float,
):
    r = pl.program_id(0)
    s = pl.program_id(1)

    @pl.when(s == 0)
    def _():
        o_scr[:] = jnp.zeros_like(o_scr)
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)

    def valid(shape, axis):
        return _block_positions(s, block_s, shape, axis) < seq_ref[r]

    @pl.when(s * block_s < seq_ref[r])  # any line of this block valid
    def _():
        q = q_ref[0].astype(jnp.float32)                    # (KV, G, dk)
        # Mosaic batched matmul needs both batch dims leading: lay K/V
        # out as (KV, CS, dk) for the chunk
        k = k_ref[0].astype(jnp.float32).transpose(1, 0, 2)  # (KV, CS, dk)
        v = v_ref[0].astype(jnp.float32).transpose(1, 0, 2)
        # zero out-of-bounds/invalid rows: p is 0 there, but 0·NaN from
        # block padding would still poison the PV product
        v = jnp.where(valid(v.shape, 1), v, 0.0)
        # scores (KV, G, CS): batch over KV heads, contract dk
        scores = jax.lax.dot_general(
            q, k,
            dimension_numbers=(((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        ) * scale
        ok = valid(scores.shape, 2)
        scores = jnp.where(ok, scores, NEG_INF)
        m_new = jnp.maximum(m_scr[:], scores.max(axis=-1))
        p = jnp.exp(scores - m_new[..., None])
        p = jnp.where(ok, p, 0.0)
        corr = jnp.exp(m_scr[:] - m_new)
        l_scr[:] = l_scr[:] * corr + p.sum(axis=-1)
        pv = jax.lax.dot_general(
            p, v,
            dimension_numbers=(((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )  # (KV, G, dk)
        o_scr[:] = o_scr[:] * corr[..., None] + pv
        m_scr[:] = m_new

    @pl.when(s == pl.num_programs(1) - 1)
    def _():
        l = jnp.maximum(l_scr[:], 1e-20)
        out_ref[0] = (o_scr[:] / l[..., None]).astype(out_ref.dtype)


def decode_attention(
    q: jnp.ndarray,        # (R, H, dk)
    k_cache: jnp.ndarray,  # (R, S1, KV, dk)
    v_cache: jnp.ndarray,  # (R, S1, KV, dk)
    seq_lens: jnp.ndarray, # (R,) int32 — lines [0, seq_len) are attended
    *,
    block_s: int = 256,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """Fused decode attention: one query token per request slot against
    its cache prefix. Returns (R, H, dk)."""
    R, H, dk = q.shape
    _, S1, KV, _ = k_cache.shape
    G = H // KV
    scale = scale if scale is not None else 1.0 / math.sqrt(dk)
    # keep blocks lane-aligned: a non-multiple-of-128 block (e.g. the
    # cache's odd S1 = max_len+1) tiles catastrophically in Mosaic
    block_s = 128 * pl.cdiv(min(block_s, S1), 128)
    qg = q.reshape(R, KV, G, dk)
    grid = (R, pl.cdiv(S1, block_s))

    out = pl.pallas_call(
        functools.partial(_decode_kernel, block_s=block_s, scale=scale),
        out_shape=jax.ShapeDtypeStruct((R, KV, G, dk), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                # index maps receive the scalar-prefetch ref as a trailing arg
                pl.BlockSpec((1, KV, G, dk), lambda r, s, seq: (r, 0, 0, 0)),
                pl.BlockSpec((1, block_s, KV, dk), lambda r, s, seq: (r, s, 0, 0)),
                pl.BlockSpec((1, block_s, KV, dk), lambda r, s, seq: (r, s, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, KV, G, dk), lambda r, s, seq: (r, 0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((KV, G, dk), jnp.float32),
                pltpu.VMEM((KV, G), jnp.float32),
                pltpu.VMEM((KV, G), jnp.float32),
            ],
        ),
        name="ff_decode_attention",
        interpret=_interpret(),
    )(seq_lens.astype(jnp.int32), qg, k_cache, v_cache)
    return out.reshape(R, H, dk)


def _verify_kernel(
    q_ref,        # (1, C, KV, G, dk)
    k_ref,        # (1, CS, KV, dk)
    v_ref,        # (1, CS, KV, dk)
    mask_ref,     # (1, C, CS) bool
    out_ref,      # (1, C, KV, G, dk)
    o_scr,        # VMEM (C, KV, G, dk) f32
    m_scr,        # VMEM (C, KV, G) f32
    l_scr,        # VMEM (C, KV, G) f32
    *,
    block_s: int,
    total_s: int,
    scale: float,
):
    s = pl.program_id(1)

    @pl.when(s == 0)
    def _():
        o_scr[:] = jnp.zeros_like(o_scr)
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)

    # When S1 % block_s != 0 the mask block's tail is out-of-bounds
    # padding with unspecified contents on TPU — bound it explicitly.
    def inbounds(shape, axis):
        return _block_positions(s, block_s, shape, axis) < total_s

    mask = mask_ref[0]
    mask = mask & inbounds(mask.shape, 1)  # (C, CS)

    @pl.when(jnp.any(mask))
    def _():
        q = q_ref[0].astype(jnp.float32)           # (C, KV, G, dk)
        k = k_ref[0].astype(jnp.float32).transpose(1, 0, 2)  # (KV, CS, dk)
        v = v_ref[0].astype(jnp.float32).transpose(1, 0, 2)
        v = jnp.where(inbounds(v.shape, 1), v, 0.0)
        C = q.shape[0]
        # (KV, C*G, dk) grouped layout so one batched dot serves all KV heads
        qkv = q.transpose(1, 0, 2, 3).reshape(q.shape[1], -1, q.shape[-1])
        # (KV, C*G, dk) × (KV, CS, dk) -> (KV, C*G, CS)
        scores = jax.lax.dot_general(
            qkv, k,
            dimension_numbers=(((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        ) * scale
        KV = q.shape[1]
        G = q.shape[2]
        scores = scores.reshape(KV, C, G, -1).transpose(1, 0, 2, 3)  # (C,KV,G,CS)
        scores = jnp.where(mask[:, None, None, :], scores, NEG_INF)
        m_new = jnp.maximum(m_scr[:], scores.max(axis=-1))
        p = jnp.exp(scores - m_new[..., None])
        p = jnp.where(mask[:, None, None, :], p, 0.0)
        corr = jnp.exp(m_scr[:] - m_new)
        l_scr[:] = l_scr[:] * corr + p.sum(axis=-1)
        pk = p.transpose(1, 0, 2, 3).reshape(KV, C * G, -1)   # (KV, C*G, CS)
        pv = jax.lax.dot_general(
            pk, v,
            dimension_numbers=(((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )  # (KV, C*G, dk)
        pv = pv.reshape(KV, C, G, -1).transpose(1, 0, 2, 3)
        o_scr[:] = o_scr[:] * corr[..., None] + pv
        m_scr[:] = m_new

    @pl.when(s == pl.num_programs(1) - 1)
    def _():
        l = jnp.maximum(l_scr[:], 1e-20)
        out_ref[0] = (o_scr[:] / l[..., None]).astype(out_ref.dtype)


def verify_attention(
    q: jnp.ndarray,        # (R, C, H, dk) — C tree tokens per request
    k_cache: jnp.ndarray,  # (R, S1, KV, dk)
    v_cache: jnp.ndarray,  # (R, S1, KV, dk)
    mask: jnp.ndarray,     # (R, C, S1) bool — the spec-tree BitMask
    *,
    block_s: int = 256,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """Fused tree-verify attention: every speculative tree token attends
    its causal-bitmask cache subset in one pass (reference
    ``tree_inc_multihead_self_attention.cu``). Returns (R, C, H, dk)."""
    R, C, H, dk = q.shape
    _, S1, KV, _ = k_cache.shape
    G = H // KV
    scale = scale if scale is not None else 1.0 / math.sqrt(dk)
    block_s = 128 * pl.cdiv(min(block_s, S1), 128)  # lane-aligned blocks
    qg = q.reshape(R, C, KV, G, dk)
    grid = (R, pl.cdiv(S1, block_s))

    out = pl.pallas_call(
        functools.partial(_verify_kernel, block_s=block_s, total_s=S1,
                          scale=scale),
        out_shape=jax.ShapeDtypeStruct((R, C, KV, G, dk), q.dtype),
        grid_spec=pl.GridSpec(
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, C, KV, G, dk), lambda r, s: (r, 0, 0, 0, 0)),
                pl.BlockSpec((1, block_s, KV, dk), lambda r, s: (r, s, 0, 0)),
                pl.BlockSpec((1, block_s, KV, dk), lambda r, s: (r, s, 0, 0)),
                pl.BlockSpec((1, C, block_s), lambda r, s: (r, 0, s)),
            ],
            out_specs=pl.BlockSpec(
                (1, C, KV, G, dk), lambda r, s: (r, 0, 0, 0, 0)
            ),
            scratch_shapes=[
                pltpu.VMEM((C, KV, G, dk), jnp.float32),
                pltpu.VMEM((C, KV, G), jnp.float32),
                pltpu.VMEM((C, KV, G), jnp.float32),
            ],
        ),
        name=f"ff_verify_attention_c{C}",
        interpret=_interpret(),
    )(qg, k_cache, v_cache, mask)
    return out.reshape(R, C, H, dk)


# ---------------------------------------------------------------------------
# Shared serving-mask construction. Every serving step — sync chunked
# prefill, fused decode, and the mixed continuous-batching step — uses
# the same causal-by-position contract: a query token attends every
# cache line whose position is <= its own, never the scratch line, so
# one static-shape program serves ragged rows (padding columns sit at
# the scratch position and are masked out of nothing real). These were
# previously duplicated across the model-family modules.


def causal_serve_mask(positions: jnp.ndarray, S1: int) -> jnp.ndarray:
    """Causal-by-position mask over a dense cache: positions (R, C) →
    (R, C, S1) bool. Line S1-1 is the per-slot scratch row and is never
    attended; only positions already written satisfy ``<=``, so stale
    lines from an evicted slot occupant are never read."""
    key_pos = jnp.arange(S1, dtype=jnp.int32)
    mask = key_pos[None, None, :] <= positions[:, :, None]
    return mask & (key_pos[None, None, :] < S1 - 1)


def paged_serve_mask(
    mask: Optional[jnp.ndarray],
    positions: jnp.ndarray,
    num_logical_pages: int,
    page_size: int,
    cache_len: int,
) -> jnp.ndarray:
    """Paged twin of :func:`causal_serve_mask` over the page-aligned
    virtual cache (S_virt = NP * page_size): builds the causal mask when
    ``mask`` is None, otherwise pads an explicit (R, C, cache_len+1)
    mask out to S_virt (padding is never-attended). The scratch LINE
    (index ``cache_len``, where padding tokens write) is excluded."""
    S_virt = num_logical_pages * page_size
    if mask is None:
        key_pos = jnp.arange(S_virt, dtype=jnp.int32)
        mask = key_pos[None, None, :] <= positions[:, :, None]
        return mask & (key_pos[None, None, :] < cache_len)
    if mask.shape[-1] < S_virt:
        pad = S_virt - mask.shape[-1]
        mask = jnp.pad(mask, ((0, 0), (0, 0), (0, pad)))
    return mask


def real_query_lengths(positions: jnp.ndarray, cache_len: int) -> jnp.ndarray:
    """How many leading columns of each row may hold a real query:
    positions (R, C) → (R,) int32, one past the last column whose
    position is not the scratch position (``cache_len``; 0: the row is
    all padding). Every dispatch fills a row's real columns first, so
    this is their count; were one ever to leave a gap, the columns up
    to its last real one count, and no real query is dropped. The
    ``q_len`` operand of :func:`ragged_paged_attention`, derived here
    and nowhere else — on the device inside a step, and by the engine
    on the host's own (numpy) positions, whose sum is the step's real
    tokens (serve/engine.run_mixed)."""
    xp = np if isinstance(positions, np.ndarray) else jnp
    cols = xp.arange(1, positions.shape[1] + 1, dtype=xp.int32)
    return xp.max(xp.where(positions < cache_len, cols, 0), axis=1)


def narrow_query_extent(C: int) -> int:
    """The query extent of the ragged paged kernel's narrow body at
    chunk ``C``: a row with at most this many real queries (a decode row
    of a mixed step, the tail of a prompt) is computed at this extent
    and not at ``C``. One float32 sublane tile; 0 where the chunk is no
    wider (no narrow body). Static, from ``C`` alone: the host's count of
    the rows that take it (``SchedulerStats.note_attn_steps``) reads it
    here too."""
    return 8 if C > 8 else 0


# ---------------------------------------------------------------------------
# Ragged paged attention (paged KV pool + per-request page table)


def gather_pages(pool: jnp.ndarray, page_table: jnp.ndarray) -> jnp.ndarray:
    """``jnp.take``-gather of a request's logical cache from the page
    pool: pool (P+1, ps, ...) × table (R, NP) → virtual cache
    (R, NP*ps, ...). Unallocated table entries point at the scratch page
    (pool row P) — the caller's mask never exposes those lines."""
    R, NP = page_table.shape
    ps = pool.shape[1]
    flat = jnp.take(pool, page_table.reshape(-1), axis=0)
    return flat.reshape((R, NP * ps) + pool.shape[2:])


def _quant_suffix(quant: bool, pack: int) -> str:
    """Kernel-name suffix of a quantized pool's variant. Every
    ``pallas_call`` here carries ``name=``: the kernel and its variant
    (``ff_ragged_paged_c128``, ``ff_ragged_paged_c1_int8``, …), so a
    profile or the HLO says which kernel ran without reading shapes."""
    if not quant:
        return ""
    return "_int4" if pack == 2 else "_int8"


def _unpack_codes(block: jnp.ndarray, pack: int) -> jnp.ndarray:
    """Stored code block → f32 code values: identity cast for pack=1
    (int8), nibble unpack for pack=2 (uint8 int4 pages — op-for-op
    ``kv_quant.unpack_nibbles``: low nibble = head-dim entries
    [0, dk/2), high nibble = [dk/2, dk), bias +8; integer arithmetic,
    so the Pallas and XLA paths decode identical values)."""
    if pack == 1:
        return block.astype(jnp.float32)
    b = block.astype(jnp.int32)
    lo = (b & 0xF) - 8
    hi = ((b >> 4) & 0xF) - 8
    return jnp.concatenate([lo, hi], axis=-1).astype(jnp.float32)


def _pack_codes(codes: jnp.ndarray, dtype, pack: int) -> jnp.ndarray:
    """f32 code values → stored block (inverse of :func:`_unpack_codes`;
    the in-kernel twin of ``kv_quant.pack_nibbles``)."""
    if pack == 1:
        return codes.astype(dtype)
    dk = codes.shape[-1]
    c = codes.astype(jnp.int32) + 8
    lo, hi = c[..., : dk // 2], c[..., dk // 2 :]
    return (lo | (hi << 4)).astype(dtype)


def dequant_pages(
    pool: jnp.ndarray,        # (P+1, ps, KV, dk/pack) int8/uint8 codes
    scale: jnp.ndarray,       # (P+1, KV) f32 per-page-per-head scales
    page_table: jnp.ndarray,  # (R, NP) int32
    dtype,
) -> jnp.ndarray:
    """Quantized twin of :func:`gather_pages`: gather the quantized
    virtual cache through the table and dequantize each line at its
    page's per-KV-head scale (serve/kv_quant.py layout; uint8 pools
    unpack two nibble codes per byte first). Returns the
    (R, NP*ps, KV, dk) full-precision virtual cache in ``dtype``."""
    from .kv_quant import pool_pack

    R, NP = page_table.shape
    ps, KV = pool.shape[1], pool.shape[2]
    codes = gather_pages(pool, page_table)        # (R, S, KV, dk/pack)
    codes = _unpack_codes(codes, pool_pack(pool))  # (R, S, KV, dk) f32
    s = jnp.take(scale, page_table.reshape(-1), axis=0)  # (R*NP, KV)
    s = jnp.broadcast_to(
        s.reshape(R, NP, 1, KV), (R, NP, ps, KV)
    ).reshape(R, NP * ps, KV)
    return (codes * s[..., None]).astype(dtype)


def ragged_paged_attention_xla(
    q: jnp.ndarray,           # (R, C, H, dk)
    k_pool: jnp.ndarray,      # (P+1, ps, KV, dk)
    v_pool: jnp.ndarray,      # (P+1, ps, KV, dk)
    page_table: jnp.ndarray,  # (R, NP) int32 physical page per logical page
    mask: jnp.ndarray,        # (R, C, NP*ps) bool
    *,
    scale: Optional[float] = None,
    k_scale: Optional[jnp.ndarray] = None,  # (P+1, KV) f32 (quantized pool)
    v_scale: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Shape-identical XLA fallback: gather the virtual cache through
    the page table, then the standard grouped-query masked softmax —
    bit-for-bit the dense ``serve_attention`` math on the gathered
    lines. With ``k_scale``/``v_scale`` the pools hold quantized codes
    (serve/kv_quant.py; packed int4 nibbles unpack first) and the
    gathered lines are dequantized at their page scales. Returns
    (R, C, H, dk)."""
    R, C, H, dk = q.shape
    KV = k_pool.shape[2]
    G = H // KV
    scale = scale if scale is not None else 1.0 / math.sqrt(dk)
    if k_scale is not None:
        k_virt = dequant_pages(k_pool, k_scale, page_table, q.dtype)
        v_virt = dequant_pages(v_pool, v_scale, page_table, q.dtype)
    else:
        k_virt = gather_pages(k_pool, page_table)  # (R, S, KV, dk)
        v_virt = gather_pages(v_pool, page_table)
    qg = q.reshape(R, C, KV, G, dk)
    scores = jnp.einsum(
        "rckgd,rskd->rkgcs", qg, k_virt, preferred_element_type=jnp.float32
    ) * scale
    scores = jnp.where(mask[:, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("rkgcs,rskd->rckgd", probs, v_virt)
    return out.reshape(R, C, H, dk)


def _rope_rotate(x, cos, sin):
    """Rotate-half RoPE on the trailing head dim, op-for-op the XLA
    ``apply_rope`` (models/llama.py, models/transformer.py) so the
    in-kernel prologue stays bitwise-identical to the unfused path.
    ``cos``/``sin`` arrive pre-broadcast against ``x``; partial rotary
    widths (``cos.shape[-1] < head_dim``, Phi-style) pass the tail of
    each head through untouched."""
    rot = cos.shape[-1]
    xr = x[..., :rot]
    half = rot // 2
    x1, x2 = xr[..., :half], xr[..., half:]
    rotated = jnp.concatenate([-x2, x1], axis=-1)
    out = xr * cos + rotated * sin
    if x.shape[-1] > rot:
        out = jnp.concatenate([out, x[..., rot:].astype(out.dtype)], axis=-1)
    return out.astype(x.dtype)


def _lanes_to_major(x):
    """(n,) → (n, 1, 1), exactly, without the lane→leading-dim reshape
    Mosaic refuses ("unsupported shape cast"): broadcast the lane vector
    down a new leading dim, keep the diagonal, sum the lanes back out —
    each sum is one value plus zeros."""
    n = x.shape[0]
    diag = (jax.lax.broadcasted_iota(jnp.int32, (n, 1, n), 0)
            == jax.lax.broadcasted_iota(jnp.int32, (n, 1, n), 2))
    return jnp.sum(jnp.where(diag, x[None, None, :], 0.0), axis=-1,
                   keepdims=True)


def _build_ragged_paged_kernel(
    *,
    quant: bool,
    fused: bool,
    C: int,
    scale: float,
    qmax: float = 0.0,
    has_rope: bool = True,
    pack: int = 1,
    group_mask: bool = False,
    merged_heads: int = 0,
    head_blocks: bool = False,
):
    """ONE builder for every Pallas variant of the ragged paged kernel
    (see the module-docstring matrix): ``quant`` folds the per-page
    dequant scales into the batched dots' OUTPUTS (scores ×=
    k_scale[kv], pv ×= v_scale[kv] — scales are constant within a
    page, so scaling the O(C·G·ps) scores and O(C·G·dk) pv is exact
    and strictly cheaper than scaling the O(ps·dk) operands);
    ``pack=2`` (int4) additionally unpacks two nibble codes per DMA'd
    uint8 byte in VMEM before the dots — the page DMA moves HALF the
    int8 bytes; ``fused`` adds the megakernel prologue (in-kernel RoPE
    + KV page write through aliased pool outputs, packing through the
    same nibble layout). The quant, pack and fused axes compose, so
    the kernel variants share one attention body instead of
    hand-maintained copies. ``group_mask`` (block-sparse attention,
    :func:`sparse_paged_attention`): the mask block is (KV*C, ps), one
    (C, ps) mask a KV group, for layers whose groups attend different
    keys. The plain kernel called with a ``q_len_ref`` (the prefetched
    per-row count of real queries) works for those alone: columns from
    ``q_len[r]`` on keep no page alive and come out zero, and a row of
    at most :func:`narrow_query_extent` real queries runs the same step
    at that query extent. ``merged_heads`` (the plain kernel): the page
    blocks are (ps, KV*dk), a line's heads side by side on the minor
    axis (a pool of head size under a lane tile, see
    :func:`_ragged_paged_attention`), and the body takes each head's dk
    lanes out after the load. ``head_blocks`` (the plain kernel): the
    grid has a leading axis over blocks of KV heads, so rows and pages
    are its axes 1 and 2 (:func:`_ragged_paged_attention`)."""
    narrow = narrow_query_extent(C)
    row_axis, page_axis = (1, 2) if head_blocks else (0, 1)

    def _heads_major(block):
        # a loaded page block as (KV, ps, dk) float32
        x = _unpack_codes(block, pack)
        if not merged_heads:
            return x.transpose(1, 0, 2)
        dk = x.shape[-1] // merged_heads
        return jnp.stack([x[:, h * dk:(h + 1) * dk]
                          for h in range(merged_heads)], axis=0)

    def _masked(mask, x, fill):
        # x (C, KV, G, ps). One mask: (C, ps), every head alike. A mask
        # a group: a list of KV (C, ps) masks, each loaded from its own
        # rows of the (KV*C, ps) block and applied to its own group, so
        # Mosaic sees only the mask layout the one-mask path has (a
        # (KV, C, ps) block, or a one-row slice of a loaded i1 vector,
        # is a "layout with implicit dimension" at C=1)
        if not group_mask:
            return jnp.where(mask[:, None, None, :], x, fill)
        return jnp.concatenate(
            [jnp.where(m[:, None, None, :], x[:, kv:kv + 1], fill)
             for kv, m in enumerate(mask)], axis=1)

    def _attend(q, k, v, ks, vs, mask, o_scr, m_scr, l_scr):
        # q (n, KV, G, dk) f32; k/v (KV, ps, dk) f32; ks/vs (KV, 1, 1)
        # f32 (quant only); one batched dot per KV head over the
        # grouped (KV, n*G, dk) query layout. n is the chunk C or the
        # narrow body's extent: the accumulators' first n rows are
        # read and written (slices of the loads and stores; Mosaic
        # refuses a sliced VIEW of a scratch whose minor extent, G, is
        # under a lane tile)
        n, KV, G = q.shape[:3]
        qkv = q.transpose(1, 0, 2, 3).reshape(KV, n * G, q.shape[-1])
        scores = jax.lax.dot_general(
            qkv, k,
            dimension_numbers=(((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )                                           # (KV, n*G, ps)
        if quant:
            scores = scores * (ks * scale)          # dequant K
        else:
            scores = scores * scale
        scores = scores.reshape(KV, n, G, -1).transpose(1, 0, 2, 3)
        scores = _masked(mask, scores, NEG_INF)
        m_new = jnp.maximum(m_scr[:n], scores.max(axis=-1))
        prob = jnp.exp(scores - m_new[..., None])
        prob = _masked(mask, prob, 0.0)
        corr = jnp.exp(m_scr[:n] - m_new)
        l_scr[:n] = l_scr[:n] * corr + prob.sum(axis=-1)
        pk = prob.transpose(1, 0, 2, 3).reshape(KV, n * G, -1)
        pv = jax.lax.dot_general(
            pk, v,
            dimension_numbers=(((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )  # (KV, n*G, dk)
        if quant:
            pv = pv * vs                            # dequant V
        pv = pv.reshape(KV, n, G, -1).transpose(1, 0, 2, 3)
        o_scr[:n] = o_scr[:n] * corr[..., None] + pv
        m_scr[:n] = m_new

    def _init(o_scr, m_scr, l_scr, n=C):
        o_scr[:n] = jnp.zeros((n,) + o_scr.shape[1:], o_scr.dtype)
        m_scr[:n] = jnp.full((n,) + m_scr.shape[1:], NEG_INF, m_scr.dtype)
        l_scr[:n] = jnp.zeros((n,) + l_scr.shape[1:], l_scr.dtype)

    def _finalize(p, out_ref, o_scr, l_scr, n=C):
        # the rows past a narrow body's n never attended and are what
        # _init and this divide would give them: zero
        @pl.when(p == pl.num_programs(page_axis) - 1)
        def _():
            l = jnp.maximum(l_scr[:n], 1e-20)
            out_ref[0, :n] = (o_scr[:n] / l[..., None]).astype(out_ref.dtype)
            if n < C:
                out_ref[0, n:] = jnp.zeros(
                    (C - n,) + out_ref.shape[2:], out_ref.dtype)

    def _quant_commit(pool_out, scale_in, lines, belongs, offs):
        """In-kernel ``kv_quant.quant_line_write`` restricted to the
        current page block: running per-page amax, rescale-on-growth,
        offset-0 scale reset — op-for-op the XLA write-side contract,
        page-locally (pages are slot-private or the never-read scratch
        page, so the global scatter degenerates to this). ``pool_out``
        already holds the copied-through page codes; on exit it holds
        the requantized codes plus the new lines (packed layouts
        unpack, requantize on code values, and repack — the same
        arithmetic as the XLA twin, so pool bytes stay bitwise).
        Returns the page's final (KV,) scale — also the dequant scale
        attention uses, exactly as the unfused path reads the
        post-write scale row."""
        vf = lines.astype(jnp.float32)                 # (C, KV, dk)
        amax = jnp.max(jnp.abs(vf), axis=-1)           # (C, KV)
        # int32 flags, compared after the reshape: Mosaic cannot reshape
        # an i1 vector
        bvec = jnp.stack([b.astype(jnp.int32) for b in belongs])
        page_amax = jnp.where(bvec[:, None] != 0, amax, 0.0).max(axis=0)
        first = belongs[0] & (offs[0] == 0)
        for c in range(1, C):
            first = first | (belongs[c] & (offs[c] == 0))
        old = jnp.where(first, 0.0, scale_in)          # (KV,)
        new = jnp.maximum(old, page_amax / qmax)
        ratio = jnp.where(new > 0.0, old / jnp.maximum(new, 1e-30), 0.0)
        codes = _unpack_codes(pool_out[0], pack)       # (ps, KV, dk)
        pool_out[0] = _pack_codes(
            jnp.round(codes * ratio[None, :, None]), pool_out.dtype, pack
        )
        q = jnp.round(vf / jnp.maximum(new, 1e-30)[None, :, None])
        q = _pack_codes(jnp.clip(q, -qmax, qmax), pool_out.dtype, pack)
        for c in range(C):
            @pl.when(belongs[c])
            def _(c=c):
                pool_out[0, offs[c]] = q[c]
        return new

    def plain_kernel(*refs, q_len_ref=None):
        # (pt, q, k, v, [ks, vs], mask) -> out; o/m/l scratch
        i = 1  # refs[0] is the scalar-prefetched page table
        q_ref = refs[i]; i += 1         # (1, C, KV, G, dk)
        k_ref = refs[i]; i += 1         # (1, ps, KV, dk) via index map
        v_ref = refs[i]; i += 1
        if quant:
            ks_ref = refs[i]; i += 1    # (1, KV, 1, 1) f32 page scales
            vs_ref = refs[i]; i += 1
        mask_ref = refs[i]; i += 1      # (1, C, ps)
        out_ref = refs[i]; i += 1       # (1, C, KV, G, dk)
        o_scr, m_scr, l_scr = refs[i:i + 3]

        p = pl.program_id(page_axis)

        def step(n, q_len=None):
            # this grid step over the row's first n queries: n is a
            # leading extent of the query, mask and output blocks and of
            # the accumulators, so taking their first n rows is free
            @pl.when(p == 0)
            def _():
                _init(o_scr, m_scr, l_scr, n)

            # one (n, ps) mask, or one a KV group from its own rows of
            # the (KV*C, ps) block — already bounded: S_virt = NP*ps
            groups = q_ref.shape[2] if group_mask else 1
            mask = [mask_ref[0, g * C:g * C + n] for g in range(groups)]
            if q_len is not None:  # a padding query keeps no page alive
                real = jax.lax.broadcasted_iota(
                    jnp.int32, mask[0].shape, 0) < q_len
                mask = [m & real for m in mask]
            some = functools.reduce(jnp.logical_or, map(jnp.any, mask))

            @pl.when(some)
            def _():
                q = q_ref[0, :n].astype(jnp.float32)
                k = _heads_major(k_ref[0])
                v = _heads_major(v_ref[0])
                ks = ks_ref[0] if quant else None
                vs = vs_ref[0] if quant else None
                _attend(q, k, v, ks, vs, mask if group_mask else mask[0],
                        o_scr, m_scr, l_scr)

            _finalize(p, out_ref, o_scr, l_scr, n)

        if q_len_ref is None:
            step(C)
        else:
            q_len = q_len_ref[pl.program_id(row_axis)]
            if not narrow:
                step(C, q_len)
            else:
                pl.when(q_len > narrow)(lambda: step(C, q_len))
                pl.when(q_len <= narrow)(lambda: step(narrow, q_len))

    def fused_kernel(*refs):
        # (pt, logical, off, q_raw, k_new, v_new, [cos, sin],
        #  k_page, v_page, [ks, vs], mask)
        #   -> (out, k_page', v_page', [ks', vs']); pool outputs alias
        #      the pools, so unvisited pages keep their bytes
        pt_ref, lg_ref, off_ref = refs[0], refs[1], refs[2]
        i = 3
        q_ref = refs[i]; i += 1         # (1, C, KV, G, dk) pre-RoPE
        kn_ref = refs[i]; i += 1        # (1, C, KV, dk) pre-RoPE
        vn_ref = refs[i]; i += 1        # (1, C, KV, dk)
        if has_rope:
            cos_ref = refs[i]; i += 1   # (1, C, rot) f32
            sin_ref = refs[i]; i += 1
        k_ref = refs[i]; i += 1         # (1, ps, KV, dk) page block
        v_ref = refs[i]; i += 1
        if quant:
            ks_ref = refs[i]; i += 1    # (1, 1, KV) f32
            vs_ref = refs[i]; i += 1
        mask_ref = refs[i]; i += 1      # (1, C, ps)
        out_ref = refs[i]; i += 1       # (1, C, KV, G, dk)
        k_out = refs[i]; i += 1         # (1, ps, KV, dk) aliased pool
        v_out = refs[i]; i += 1
        if quant:
            ks_out = refs[i]; i += 1    # (1, 1, KV) aliased scale row
            vs_out = refs[i]; i += 1
        o_scr, m_scr, l_scr = refs[i:i + 3]
        q_scr = refs[i + 3]             # (C, KV, G, dk) roped q, q dtype
        k_scr = refs[i + 4]             # (C, KV, dk) roped k, k dtype

        r = pl.program_id(0)
        p = pl.program_id(1)

        @pl.when(p == 0)
        def _():
            _init(o_scr, m_scr, l_scr)
            # RoPE once per row, reused across every page step; stored
            # at the model dtype so the double f32→dtype→f32 cast of
            # the unfused path (XLA rope, then kernel load) is mirrored
            if has_rope:
                cos = cos_ref[0]        # (C, rot) f32
                sin = sin_ref[0]
                q_scr[:] = _rope_rotate(
                    q_ref[0], cos[:, None, None, :], sin[:, None, None, :]
                )
                k_scr[:] = _rope_rotate(
                    kn_ref[0], cos[:, None, :], sin[:, None, :]
                )
            else:
                q_scr[:] = q_ref[0]
                k_scr[:] = kn_ref[0]

        # ---- prologue: commit this row's fresh K/V lines landing in
        # this grid step's page. Every visited page is written back as
        # a full block (copy-through + line writes): untouched pages
        # round-trip identical bytes, the token's page carries the new
        # lines, and aliasing keeps unvisited pages' bytes in place.
        k_out[0] = k_ref[0]
        v_out[0] = v_ref[0]
        belongs = [lg_ref[r, c] == p for c in range(C)]
        offs = [off_ref[r, c] for c in range(C)]
        if quant:
            ks_new = _quant_commit(k_out, ks_ref[0, 0], k_scr[:], belongs,
                                   offs)
            vs_new = _quant_commit(v_out, vs_ref[0, 0], vn_ref[0], belongs,
                                   offs)
            ks_out[0, 0] = ks_new
            vs_out[0, 0] = vs_new
            ks_att = _lanes_to_major(ks_new)
            vs_att = _lanes_to_major(vs_new)
        else:
            ks_att = vs_att = None
            for c in range(C):
                @pl.when(belongs[c])
                def _(c=c):
                    k_out[0, offs[c]] = k_scr[c].astype(k_out.dtype)
                    v_out[0, offs[c]] = vn_ref[0, c].astype(v_out.dtype)

        mask = mask_ref[0]  # (C, ps)

        @pl.when(jnp.any(mask))
        def _():
            q = q_scr[:].astype(jnp.float32)
            # attention reads the page through the freshly written
            # block — the fresh K/V never left VMEM
            k = _unpack_codes(k_out[0], pack).transpose(1, 0, 2)
            v = _unpack_codes(v_out[0], pack).transpose(1, 0, 2)
            _attend(q, k, v, ks_att, vs_att, mask, o_scr, m_scr, l_scr)

        _finalize(p, out_ref, o_scr, l_scr)

    return fused_kernel if fused else plain_kernel


# Scoped VMEM: what one kernel instance may keep in fast memory. The
# compiler's own default scope is 16 MiB on a v5e, and the ragged paged
# kernel at the mixed step's C=128 needs 17 MiB there (refused with
# "Scoped allocation ... exceeded scoped vmem limit" once the step
# around it is 24 layers deep), so the kernel states its need. The
# ceiling stays well inside the 128 MiB a v5e/v6e core has.
_VMEM_SCOPE_DEFAULT = 16 << 20
_VMEM_SCOPE_CEILING = 96 << 20


def _vmem_bytes(shape, dtype) -> int:
    """VMEM bytes of one buffer: the last two dims pad to the dtype's
    (sublane, 128-lane) tile; bools are held as int32."""
    item = 4 if dtype == jnp.bool_ else jnp.dtype(dtype).itemsize
    *lead, sub, lane = (1, 1) + tuple(shape)
    tile = 8 * max(1, 4 // item)
    return (math.prod(lead) * (-(-sub // tile) * tile)
            * (-(-lane // 128) * 128) * item)


def _scale_rows(scale: jnp.ndarray) -> jnp.ndarray:
    """(P+1, KV) per-page scales as (P+1, 1, KV): a one-page block of the
    2-D array is (1, KV), whose second-to-last dim the TPU lowering
    refuses (neither a multiple of 8 nor the array's own); the same
    block of the 3-D view ends in the array's own (1, KV)."""
    return scale.astype(jnp.float32)[:, None, :]


def _ragged_vmem_need(specs, arrays, scratch, C, H, width) -> int:
    """VMEM bytes a grid step of a ragged paged kernel: its blocks
    double-buffered, its scratch, and the attention body's float32
    intermediates — each one (C, H, max(dk, ps)) tile, about a dozen
    live at the widest point (q and its grouped transpose, the scores,
    their masked/exponentiated/transposed forms, pv)."""
    need = 2 * sum(
        _vmem_bytes(spec.block_shape, a.dtype)
        for spec, a in zip(specs, arrays)
    )
    need += sum(_vmem_bytes(s.shape, s.dtype) for s in scratch)
    return need + 12 * _vmem_bytes((C * H, width), jnp.float32)


def _ragged_vmem_limit(specs, arrays, scratch, C, H, width) -> int:
    """``vmem_limit_bytes`` of a ragged paged kernel
    (:func:`_ragged_vmem_need`), refused over the ceiling."""
    need = _ragged_vmem_need(specs, arrays, scratch, C, H, width)
    if need > _VMEM_SCOPE_CEILING:
        raise ValueError(
            f"ragged paged attention at C={C} rows x H={H} heads needs "
            f"{need >> 20} MiB of VMEM per grid step (ceiling "
            f"{_VMEM_SCOPE_CEILING >> 20} MiB) — lower prefill_chunk / "
            "max_tokens_per_step"
        )
    return max(need, _VMEM_SCOPE_DEFAULT)


def ragged_paged_attention(
    q: jnp.ndarray,           # (R, C, H, dk)
    k_pool: jnp.ndarray,      # (P+1, ps, KV, dk)
    v_pool: jnp.ndarray,      # (P+1, ps, KV, dk)
    page_table: jnp.ndarray,  # (R, NP) int32
    mask: jnp.ndarray,        # (R, C, NP*ps) bool
    *,
    scale: Optional[float] = None,
    k_scale: Optional[jnp.ndarray] = None,  # (P+1, KV) f32 (quantized pool)
    v_scale: Optional[jnp.ndarray] = None,
    row_offset=None,          # int32 scalar: pool row of table entry 0
    q_len: Optional[jnp.ndarray] = None,  # (R,) int32 real queries a row
) -> jnp.ndarray:
    """:func:`_ragged_paged_attention` placed on the ambient mesh. The
    compiler cannot partition a Mosaic kernel ("wrap the call in a
    shard_map"), and heads are independent, so on a mesh whose
    ``model`` degree divides the KV heads — the tensor-parallel serving
    layout, Q heads and pool KV heads sharded alike — each shard runs
    the kernel on its own heads under a shard_map over that axis."""
    from jax.sharding import PartitionSpec as P

    from ..core.mesh import MODEL_AXIS, shard_map_unchecked

    optional = []  # names of the operands after the five every call has

    def body(q, k_pool, v_pool, page_table, mask, *rest):
        return _ragged_paged_attention(
            q, k_pool, v_pool, page_table, mask,
            scale=scale, **dict(zip(optional, rest)),
        )

    heads = P(None, None, MODEL_AXIS, None)
    operands = [q, k_pool, v_pool, page_table, mask]
    in_specs = [heads, heads, heads, P(), P()]
    if k_scale is not None:
        optional += ["k_scale", "v_scale"]
        operands += [k_scale, v_scale]
        in_specs += [P(None, MODEL_AXIS), P(None, MODEL_AXIS)]
    if row_offset is not None:
        optional.append("row_offset")
        operands.append(jnp.asarray(row_offset, jnp.int32))
        in_specs.append(P())
    if q_len is not None:  # replicated, as the table is
        optional.append("q_len")
        operands.append(q_len)
        in_specs.append(P())
    mesh = jax.sharding.get_abstract_mesh()
    tp = 1 if mesh.empty else mesh.shape.get(MODEL_AXIS, 1)
    # (a pool with merged heads, rank 3, is served on one shard: its
    # family refuses ``model > 1``)
    if tp == 1 or k_pool.ndim == 3 or k_pool.shape[2] % tp:
        return body(*operands)
    # every mesh axis manual (Mosaic refuses a partial-manual context);
    # the specs name only ``model``, so the others see replicas
    return shard_map_unchecked(body, None, tuple(in_specs), heads)(*operands)


def _ragged_paged_attention(
    q: jnp.ndarray,           # (R, C, H, dk)
    k_pool: jnp.ndarray,      # (P+1, ps, KV, dk)
    v_pool: jnp.ndarray,      # (P+1, ps, KV, dk)
    page_table: jnp.ndarray,  # (R, NP) int32
    mask: jnp.ndarray,        # (R, C, NP*ps) bool
    *,
    scale: Optional[float] = None,
    k_scale: Optional[jnp.ndarray] = None,  # (P+1, KV) f32 (quantized pool)
    v_scale: Optional[jnp.ndarray] = None,
    row_offset=None,          # int32 scalar: pool row of table entry 0
    group_mask: bool = False,  # mask is (R, KV, C, NP*ps): one a KV group
    q_len: Optional[jnp.ndarray] = None,  # (R,) int32 real queries a row
) -> jnp.ndarray:
    """Fused ragged paged attention: grid (request, logical page); the
    K/V BlockSpec index maps read the scalar-prefetched page table so
    each step DMAs exactly the physical page that logical position maps
    to — gathering through the table without materialising the
    (R, S) virtual cache. One kernel covers decode (C=1), chunked
    prefill and tree verify (the explicit-mask modes). With
    ``k_scale``/``v_scale`` the pools hold quantized codes (int8, or
    packed int4 nibbles when the pool's trailing dim is dk/2) and the
    same index maps additionally DMA each page's per-KV-head scales;
    dequant — and, packed, the nibble unpack — happens in VMEM so the
    full-precision cache never exists in HBM. Returns (R, C, H, dk).

    ``row_offset`` is a second prefetched scalar the page index maps add
    to every table entry: the pools (and scales) may then be the
    (L*(P+1), ...) view of every layer's pages with layer l's at rows
    ``l*(P+1)`` on — how the serving step's layer loop reads its carried
    pool without slicing a layer out (models/transformer.py).

    MERGED pools (``k_pool`` / ``v_pool`` of rank 3, (P+1, ps, KV*dk)):
    a line's heads side by side on the minor axis. For a head size under
    a lane tile (dk = 64) the device's own layout of a (..., ps, KV, dk)
    array puts ``ps`` on the lanes, and a step would re-lay the whole
    pool into the kernel's and back out, four pool copies a step; a
    minor axis of KV*dk (a multiple of 128) is laid out as it is
    written, and the body takes each head's lanes out of the loaded
    block (models/lfm2_moe.py; full-precision pools, one mask a row).

    ``q_len`` (:func:`real_query_lengths`) is a third: row r's columns
    from ``q_len[r]`` on are padding. A page is then computed only if a
    real query of the row may see a key of it (a padding query sits at
    the scratch position, past every key, and would keep every page of
    the virtual cache alive), a row of at most
    :func:`narrow_query_extent` real queries is computed at that extent,
    and padding columns come out zero. A real query's result is the
    same, to the bit at the chunk's extent. ``None``: every column
    counts, the kernel as it was."""
    R, C, H, dk = q.shape
    merged = k_pool.ndim == 3  # (P+1, ps, KV*dk): heads on the minor axis
    if merged:
        if k_scale is not None or group_mask:
            raise NotImplementedError(
                "a pool with merged heads is neither quantized nor read "
                "under a mask a KV group")
        ps, KV, dkp = k_pool.shape[1], k_pool.shape[2] // dk, dk
    else:
        _, ps, KV, dkp = k_pool.shape  # dkp = dk / pack (int4 packs 2)
    NP = page_table.shape[1]
    G = H // KV
    pack = dk // dkp if k_scale is not None else 1
    scale = scale if scale is not None else 1.0 / math.sqrt(dk)
    qg = q.reshape(R, C, KV, G, dk)
    prefetch = [page_table.astype(jnp.int32)]
    if row_offset is not None:
        prefetch.append(jnp.asarray(row_offset, jnp.int32).reshape(1))
    if q_len is not None:  # last, so the index maps' ``base`` stays put
        prefetch.append(q_len.astype(jnp.int32))
    operands = [qg, k_pool, v_pool]
    if k_scale is not None:
        # per-page scales as (P+1, KV, 1, 1): the block hands the body a
        # (KV, 1, 1) value that broadcasts over the (KV, C*G, ps) scores
        # as it is — Mosaic has no relayout from a (1, KV) lane vector
        # to that leading dim ("unsupported shape cast")
        operands += [
            k_scale.astype(jnp.float32)[:, :, None, None],
            v_scale.astype(jnp.float32)[:, :, None, None],
        ]
    rows = KV * C if group_mask else C  # (R, KV, C, S) as (R, KV*C, S)
    operands.append(mask.reshape(R, rows, -1))
    out_shape = jax.ShapeDtypeStruct((R, C, KV, G, dk), q.dtype)

    def blocks_of(KVb):
        """(grid, in_specs, out_spec, scratch) with ``KVb`` KV heads a
        grid step: all of them on the (row, page) grid, or a block of
        them under a leading grid axis over the blocks (merged pools:
        a block's lanes of a line are a block of the minor axis)."""
        split = KVb < KV

        def at(index_map):  # (head block, row, page, *prefetch) -> block
            if split:
                return index_map
            return lambda r, p, *pre: index_map(0, r, p, *pre)

        def page(b, r, p, pt, *base):
            # the paged gather: block row = page_table[r, p] (+ row_offset)
            row = pt[r, p] + base[0][0] if row_offset is not None else pt[r, p]
            return (row, 0, b) if merged else (row,) + (0,) * (k_pool.ndim - 1)

        heads = at(lambda b, r, p, *_: (r, 0, b, 0, 0))
        lines = (1, ps, KVb * dk) if merged else (1, ps, KV, dkp)
        in_specs = [
            pl.BlockSpec((1, C, KVb, G, dk), heads),
            pl.BlockSpec(lines, at(page)),
            pl.BlockSpec(lines, at(page)),
        ]
        if k_scale is not None:
            in_specs += [pl.BlockSpec((1, KV, 1, 1), at(page))] * 2
        in_specs.append(pl.BlockSpec(
            (1, rows, ps), at(lambda b, r, p, *_: (r, 0, p))))
        out_spec = pl.BlockSpec((1, C, KVb, G, dk), heads)
        scratch = [
            pltpu.VMEM((C, KVb, G, dk), jnp.float32),
            pltpu.VMEM((C, KVb, G), jnp.float32),
            pltpu.VMEM((C, KVb, G), jnp.float32),
        ]
        grid = (KV // KVb, R, NP) if split else (R, NP)
        return grid, in_specs, out_spec, scratch

    def vmem(KVb, need=_ragged_vmem_limit):
        _, in_specs, out_spec, scratch = blocks_of(KVb)
        return need(in_specs + [out_spec], operands + [out_shape], scratch,
                    C, KVb * G, max(dk, ps))

    # every KV head a grid step; merged pools whose heads do not fit the
    # fast memory at once (30 K/V heads of 128 at C=128, a query block
    # of one head a group: models/olmo_hybrid.py) the largest block of
    # them that does, the same kernel name and result
    KVb = KV
    if merged:
        KVb = next((KV // n for n in range(1, KV + 1) if KV % n == 0
                    and vmem(KV // n, _ragged_vmem_need) <= _VMEM_SCOPE_CEILING),
                   1)
    grid, in_specs, out_spec, scratch = blocks_of(KVb)
    body = _build_ragged_paged_kernel(
        quant=k_scale is not None, fused=False, C=C, scale=scale, pack=pack,
        group_mask=group_mask, merged_heads=KVb if merged else 0,
        head_blocks=KVb < KV,
    )

    def kernel(*refs):  # the body knows the table and the query lengths
        body(refs[0], *refs[len(prefetch):],
             q_len_ref=None if q_len is None else refs[len(prefetch) - 1])

    out = pl.pallas_call(
        kernel,
        out_shape=out_shape,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=grid,
            in_specs=in_specs,
            out_specs=out_spec,
            scratch_shapes=scratch,
        ),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem(KVb),
        ),
        name=("ff_sparse_paged" if group_mask else "ff_ragged_paged")
             + f"_c{C}" + _quant_suffix(k_scale is not None, pack),
        interpret=_interpret(),
    )(*prefetch, *operands)
    return out.reshape(R, C, H, dk)


def sparse_paged_attention(
    q: jnp.ndarray,           # (R, C, H, dk)
    k_pool: jnp.ndarray,      # (P+1, ps, KV, dk)
    v_pool: jnp.ndarray,
    page_table: jnp.ndarray,  # (R, NP) int32
    mask: jnp.ndarray,        # (R, KV, C, NP*ps) bool: a mask a KV group
    *,
    row_offset=None,
    q_len: Optional[jnp.ndarray] = None,  # (R,) int32 real queries a row
) -> jnp.ndarray:
    """Block-sparse paged attention (``ff_sparse_paged_c<C>``): the
    ragged paged kernel with one mask a KV group, for layers whose
    groups each attend their own chosen blocks of the context
    (models/minicpm_sala.py). A first version: the choice is a mask
    over the dense paged read. A page no query of the chunk chose in
    either group is still fetched, and skipped by the body's
    ``any(mask)`` guard — exact, the page DMAs not saved. With
    ``q_len`` that guard reads the row's real queries only: a padding
    query chooses every block, and 127 of them beside a decoding
    row's one kept all of its pages alive. One chip: no ``shard_map``
    over a ``model`` axis."""
    return _ragged_paged_attention(
        q, k_pool, v_pool, page_table, mask, row_offset=row_offset,
        group_mask=True, q_len=q_len,
    )


# ---------------------------------------------------------------------------
# Ring ragged paged attention (context-parallel serving,
# ServingConfig.kv_shard="context"): one request's KV pages are
# sequence-sharded over the mesh ``seq`` axis — shard d owns the
# contiguous pool-row slice [d*rows_local, (d+1)*rows_local) and logical
# pages stripe over shards (serve/paging.py PageAllocator cp_shards) —
# and attention runs as a shard_map program: every shard computes
# UNNORMALIZED online-softmax partials (o, m, l) over its RESIDENT pages
# only (reads stay local — each shard touches its own HBM slice at full
# bandwidth), the partial stats rotate around the ring via ``ppermute``,
# and each shard merges them with the same m/l/o online-softmax carry
# ``parallel/sequence._online_block`` uses for training ring attention.
# The merge runs in ABSOLUTE shard order (0..n-1) on every shard, so the
# result is deterministic and identical across shards — run-to-run
# bitwise, though not bitwise vs the single-shard kernel (the per-shard
# partial sums reassociate the softmax reduction; tests bound the drift
# and assert greedy-token agreement instead).
#
# :func:`ring_ragged_paged_attention_xla` is the CPU-parity fallback
# with a stronger contract: on a single-device (or replicated) layout
# every shard's pages are locally addressable, so the full-table gather
# IS the ring result — BITWISE the CP-off ``ragged_paged_attention_xla``
# math. That is what makes CP-on vs CP-off generation bitwise on this
# box (tests/test_long_context.py) and is the reference the shard_map
# program is checked against.


def ring_ragged_paged_attention_xla(
    q: jnp.ndarray,           # (R, C, H, dk)
    k_pool: jnp.ndarray,      # (rows, ps, KV, dk/pack)
    v_pool: jnp.ndarray,
    page_table: jnp.ndarray,  # (R, NP) int32
    mask: jnp.ndarray,        # (R, C, NP*ps) bool
    *,
    scale: Optional[float] = None,
    k_scale: Optional[jnp.ndarray] = None,
    v_scale: Optional[jnp.ndarray] = None,
    cp_shards: int = 1,
) -> jnp.ndarray:
    """``jnp.take``-based fallback of the ring kernel: gather the
    virtual cache through the FULL page table and run the standard
    masked softmax — bit-for-bit :func:`ragged_paged_attention_xla`
    regardless of which shard's row slice each page lives in (the
    gather is layout-blind), which is exactly the CP-on == CP-off
    bitwise contract the engine's context-parallel mode serves under
    on CPU. ``cp_shards`` documents the layout; the math ignores it."""
    del cp_shards
    return ragged_paged_attention_xla(
        q, k_pool, v_pool, page_table, mask,
        scale=scale, k_scale=k_scale, v_scale=v_scale,
    )


def _online_merge(o_a, m_a, l_a, o_b, m_b, l_b):
    """Merge two unnormalized online-softmax partials — the carry
    combine of ``parallel/sequence._online_block``, applied across
    shards instead of across K/V blocks. Fully-masked partials carry
    m = -inf and contribute nothing (the isfinite guards mirror the
    training ring's padded-block handling)."""
    m_new = jnp.maximum(m_a, m_b)
    safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    ca = jnp.where(jnp.isfinite(m_a), jnp.exp(m_a - safe), 0.0)
    cb = jnp.where(jnp.isfinite(m_b), jnp.exp(m_b - safe), 0.0)
    l_new = l_a * ca + l_b * cb
    o_new = o_a * ca[..., None] + o_b * cb[..., None]
    return o_new, m_new, l_new


def ring_ragged_paged_attention(
    q: jnp.ndarray,           # (R, C, H, dk)
    k_pool: jnp.ndarray,      # (rows, ps, KV, dk/pack) — rows % n == 0
    v_pool: jnp.ndarray,
    page_table: jnp.ndarray,  # (R, NP) int32 GLOBAL physical pages
    mask: jnp.ndarray,        # (R, C, NP*ps) bool
    mesh,
    *,
    scale: Optional[float] = None,
    k_scale: Optional[jnp.ndarray] = None,  # (rows, KV) f32 (quant pool)
    v_scale: Optional[jnp.ndarray] = None,
    fused: Optional[dict] = None,
):
    """Context-parallel ragged paged attention over a sequence-sharded
    page pool (see the section comment above): per-shard resident-page
    partials + ``ppermute`` stat rotation + online-softmax merge in
    absolute shard order. The ``seq`` axis runs manually (partial
    shard_map — other mesh axes stay under GSPMD); pool rows (and the
    quant scale rows) shard over ``seq``, q/table/mask replicate.
    Returns (R, C, H, dk). ``mesh.shape[seq] == 1`` degenerates to the
    XLA fallback (nothing to rotate).

    ``fused`` (the PR-6 ``rope_kv_write`` prologue, lifted onto
    seq-sharded meshes): a dict ``{k_new, v_new, cos, sin, phys, off}``
    — ``q``/``k_new`` arrive PRE-RoPE and each shard rotates them
    in-body (op-for-op :func:`_rope_rotate` == the XLA ``apply_rope``)
    and commits the fresh K/V lines to its OWN resident rows
    (non-resident lines drop via an out-of-bounds scatter, exactly the
    rows a GSPMD scatter would route elsewhere) before attending — the
    separate XLA rope + replicated-index scatter leave the step
    program. Returns ``(out, k_pool, v_pool)``. ``cos``/``sin`` may be
    None (no-RoPE families: the prologue is just the commit).
    Full-precision pools only — the quantized ring commit (per-shard
    scale ownership) is still excluded at validation."""
    from jax import lax

    from ..core.mesh import SEQ_AXIS, shard_map_unchecked
    from jax.sharding import PartitionSpec as P

    n = mesh.shape[SEQ_AXIS]
    if n <= 1:
        if fused is not None:
            # degenerate single-shard layout: the unfused composition IS
            # the reference math (same ops the fused body mirrors)
            cos, sin = fused.get("cos"), fused.get("sin")
            qr, kr = q, fused["k_new"]
            if cos is not None:
                qr = _rope_rotate(q, cos[:, :, None, :], sin[:, :, None, :])
                kr = _rope_rotate(
                    fused["k_new"], cos[:, :, None, :], sin[:, :, None, :]
                )
            k_pool = k_pool.at[fused["phys"], fused["off"]].set(
                kr.astype(k_pool.dtype)
            )
            v_pool = v_pool.at[fused["phys"], fused["off"]].set(
                fused["v_new"].astype(v_pool.dtype)
            )
            out = ring_ragged_paged_attention_xla(
                qr, k_pool, v_pool, page_table, mask,
                scale=scale, k_scale=k_scale, v_scale=v_scale,
            )
            return out, k_pool, v_pool
        return ring_ragged_paged_attention_xla(
            q, k_pool, v_pool, page_table, mask,
            scale=scale, k_scale=k_scale, v_scale=v_scale,
        )
    R, C, H, dk = q.shape
    rows, ps, KV, dkp = k_pool.shape
    if rows % n:
        raise ValueError(
            f"ring ragged paged attention needs pool rows ({rows}) "
            f"divisible by the seq degree ({n}) — the engine pads the "
            "pool with unreferenced rows to align the shard slices"
        )
    if fused is not None and k_scale is not None:
        raise NotImplementedError(
            "the fused rope_kv_write prologue is not composed with "
            "quantized pools on a sequence-sharded mesh — the per-page "
            "amax scale update is not shard-local; drop the fusion or "
            "kv_quant (ServingConfig.validate_long_context names this)"
        )
    rows_local = rows // n
    G = H // KV
    quant = k_scale is not None
    scale_f = scale if scale is not None else 1.0 / math.sqrt(dk)
    has_rope = fused is not None and fused.get("cos") is not None

    def body(q_, kp, vp, pt, mk, *rest):
        i = lax.axis_index(SEQ_AXIS)
        if fused is not None:
            if has_rope:
                kn, vn, cos_, sin_, fph, fof = rest[-6:]
                q_ = _rope_rotate(
                    q_, cos_[:, :, None, :], sin_[:, :, None, :]
                )
                kn = _rope_rotate(
                    kn, cos_[:, :, None, :], sin_[:, :, None, :]
                )
            else:
                kn, vn, fph, fof = rest[-4:]
            # commit each fresh line on its OWNING shard only:
            # non-resident lines redirect out of bounds and drop — the
            # same rows a GSPMD scatter over the sharded pool routes to
            # other shards, so pool bytes stay bitwise the unfused
            # step's.
            res_line = (fph // rows_local) == i          # (R, C)
            lph = jnp.where(res_line, fph % rows_local, rows_local)
            kp = kp.at[lph, fof].set(kn.astype(kp.dtype), mode="drop")
            vp = vp.at[lph, fof].set(vn.astype(vp.dtype), mode="drop")
        # translate the GLOBAL table to this shard's rows: resident
        # pages keep their local row, everything else reads local row 0
        # and is masked out of the partial (the caller's mask already
        # excludes scratch-backed positions; the residency mask
        # additionally excludes pages another shard owns)
        resident = (pt // rows_local) == i          # (R, NP)
        lpt = jnp.where(resident, pt % rows_local, 0)
        if quant:
            ks_, vs_ = rest[0], rest[1]
            k_virt = dequant_pages(kp, ks_, lpt, q_.dtype)
            v_virt = dequant_pages(vp, vs_, lpt, q_.dtype)
        else:
            k_virt = gather_pages(kp, lpt)          # (R, S, KV, dk)
            v_virt = gather_pages(vp, lpt)
        res_cols = jnp.repeat(resident, ps, axis=1)  # (R, NP*ps)
        mk_loc = mk & res_cols[:, None, :]           # (R, C, S)
        qg = q_.reshape(R, C, KV, G, dk)
        scores = jnp.einsum(
            "rckgd,rskd->rckgs", qg, k_virt,
            preferred_element_type=jnp.float32,
        ) * scale_f                                  # (R, C, KV, G, S)
        mm = mk_loc[:, :, None, None, :]
        scores = jnp.where(mm, scores, -jnp.inf)
        m_loc = scores.max(axis=-1)                  # (R, C, KV, G)
        safe_m = jnp.where(jnp.isfinite(m_loc), m_loc, 0.0)
        p = jnp.where(mm, jnp.exp(scores - safe_m[..., None]), 0.0)
        l_loc = p.sum(axis=-1)
        o_loc = jnp.einsum(
            "rckgs,rskd->rckgd", p, v_virt.astype(jnp.float32)
        )
        # ring: rotate the (o, m, l) partials n-1 hops; parts[s] on
        # shard i originated at shard (i - s) % n
        perm = [(s, (s + 1) % n) for s in range(n)]
        cur = (o_loc, m_loc, l_loc)
        parts = [cur]
        for _ in range(n - 1):
            cur = tuple(
                lax.ppermute(x, SEQ_AXIS, perm) for x in cur
            )
            parts.append(cur)
        stk = tuple(
            jnp.stack([p_[t] for p_ in parts]) for t in range(3)
        )
        # merge in ABSOLUTE shard order 0..n-1 — every shard applies
        # the identical association, so the output replicates exactly
        def merge_j(j, carry):
            s = (i - j) % n  # which rotation slot holds shard j's part
            o_b = jnp.take(stk[0], s, axis=0)
            m_b = jnp.take(stk[1], s, axis=0)
            l_b = jnp.take(stk[2], s, axis=0)
            return _online_merge(*carry, o_b, m_b, l_b)
        o0 = jnp.zeros_like(o_loc)
        m0 = jnp.full_like(m_loc, -jnp.inf)
        l0 = jnp.zeros_like(l_loc)
        o, m, l = lax.fori_loop(0, n, merge_j, (o0, m0, l0))
        out = o / jnp.maximum(l, 1e-20)[..., None]
        out = out.astype(q_.dtype).reshape(R, C, H, dk)
        if fused is not None:
            return out, kp, vp
        return out

    rep = P(None, None, None, None)
    pool_spec = P(SEQ_AXIS, None, None, None)
    in_specs = [
        rep,                                  # q
        pool_spec,                            # k_pool rows
        pool_spec,                            # v_pool rows
        P(None, None),                        # page table (global)
        P(None, None, None),                  # mask
    ]
    operands = [q, k_pool, v_pool, page_table.astype(jnp.int32), mask]
    if quant:
        in_specs += [P(SEQ_AXIS, None), P(SEQ_AXIS, None)]
        operands += [
            k_scale.astype(jnp.float32), v_scale.astype(jnp.float32)
        ]
    out_specs: Any = rep
    if fused is not None:
        # the prologue's operands replicate (every shard sees every
        # fresh line and keeps only its resident ones); the updated
        # pools come back seq-sharded exactly as they went in
        in_specs += [P(None, None, None, None), P(None, None, None, None)]
        operands += [fused["k_new"], fused["v_new"]]
        if has_rope:
            in_specs += [P(None, None, None), P(None, None, None)]
            operands += [fused["cos"], fused["sin"]]
        in_specs += [P(None, None), P(None, None)]
        operands += [
            fused["phys"].astype(jnp.int32), fused["off"].astype(jnp.int32)
        ]
        out_specs = (rep, pool_spec, pool_spec)
    fn = shard_map_unchecked(
        body, mesh, tuple(in_specs), out_specs, manual_axes={SEQ_AXIS}
    )
    # jit the call: a no-op inside the engine's already-jitted step
    # programs, where this runs in production; standalone/test callers
    # get the same compiled path
    return jax.jit(fn)(*operands)


def fused_rope_paged_attention(
    q: jnp.ndarray,           # (R, C, H, dk) — PRE-RoPE query projection
    k_new: jnp.ndarray,       # (R, C, KV, dk) — PRE-RoPE key projection
    v_new: jnp.ndarray,       # (R, C, KV, dk) — value projection
    cos: Optional[jnp.ndarray],   # (R, C, rot) f32, or None (no-RoPE family)
    sin: Optional[jnp.ndarray],
    k_pool: jnp.ndarray,      # (P+1, ps, KV, dk) — model dtype or int8 codes
    v_pool: jnp.ndarray,
    page_table: jnp.ndarray,  # (R, NP) int32
    logical: jnp.ndarray,     # (R, C) int32 logical page of each new line
    off: jnp.ndarray,         # (R, C) int32 in-page offset of each new line
    mask: jnp.ndarray,        # (R, C, NP*ps) bool
    *,
    scale: Optional[float] = None,
    k_scale: Optional[jnp.ndarray] = None,  # (P+1, KV) f32 (quantized pool)
    v_scale: Optional[jnp.ndarray] = None,
    qmax: Optional[float] = None,
    row_offset=None,          # int32 scalar: pool row of table entry 0
):
    """Megakernel decode-step prologue fused into ragged paged
    attention: one ``pallas_call`` applies RoPE to Q/K, commits the
    fresh K/V lines into their table-resolved pages (quantizing at the
    page scales when ``qmax`` is set — the in-kernel twin of
    ``kv_quant.quant_line_write``) and runs the ragged paged attention
    pass, all in VMEM. The pools (and, quantized, their scale rows)
    are ALIASED outputs: unvisited pages keep their bytes, visited
    pages round-trip (identity copy-through), the written page carries
    the new lines. Returns ``(out, k_pool, v_pool, k_scale, v_scale)``
    — scales None on a full-precision pool.

    Bitwise contract: identical outputs and identical (non-scratch)
    pool bytes vs the unfused composition ``apply_rope → pool scatter
    (or quant_line_write) → ragged_paged_attention`` — same op order,
    same grid, same accumulation (tests/test_fused_decode.py). The
    XLA serving fallback needs no fused twin at all: the unfused step
    IS the reference math, so ``fused_decode`` with kernels="xla"
    routes through it unchanged.

    Intended for decode / small mixed chunks: the per-line commit
    unrolls over C, and every page in a row's table is written back
    (identity for untouched pages) — decode (C=1) is the case whose
    dispatch and HBM round-trips this removes.

    ``row_offset`` shifts every table entry, as in
    :func:`_ragged_paged_attention`: the pools may be the view of every
    layer's pages, written in place through the aliased outputs. Here
    the table itself is shifted — the body reads only ``logical`` and
    ``off``, the table serves the index maps alone."""
    R, C, H, dk = q.shape
    _, ps, KV, dkp = k_pool.shape  # dkp = dk / pack (int4 packs 2)
    NP = page_table.shape[1]
    if row_offset is not None:
        page_table = page_table + row_offset
    G = H // KV
    scale = scale if scale is not None else 1.0 / math.sqrt(dk)
    quant = qmax is not None
    pack = dk // dkp if quant else 1
    has_rope = cos is not None
    qg = q.reshape(R, C, KV, G, dk)
    grid = (R, NP)

    kernel = _build_ragged_paged_kernel(
        quant=quant, fused=True, C=C, scale=scale,
        qmax=float(qmax) if quant else 0.0, has_rope=has_rope, pack=pack,
    )

    in_specs = [
        pl.BlockSpec((1, C, KV, G, dk),
                     lambda r, p, pt, lg, of: (r, 0, 0, 0, 0)),
        pl.BlockSpec((1, C, KV, dk),
                     lambda r, p, pt, lg, of: (r, 0, 0, 0)),
        pl.BlockSpec((1, C, KV, dk),
                     lambda r, p, pt, lg, of: (r, 0, 0, 0)),
    ]
    operands = [qg, k_new, v_new]
    if has_rope:
        rot = cos.shape[-1]
        in_specs += [
            pl.BlockSpec((1, C, rot), lambda r, p, pt, lg, of: (r, 0, 0)),
            pl.BlockSpec((1, C, rot), lambda r, p, pt, lg, of: (r, 0, 0)),
        ]
        operands += [cos, sin]
    # operand index of k_pool in the flattened pallas_call argument
    # list (scalar-prefetch args included) — the aliasing keys
    idx0 = 6 + (2 if has_rope else 0)
    in_specs += [
        pl.BlockSpec((1, ps, KV, dkp),
                     lambda r, p, pt, lg, of: (pt[r, p], 0, 0, 0)),
        pl.BlockSpec((1, ps, KV, dkp),
                     lambda r, p, pt, lg, of: (pt[r, p], 0, 0, 0)),
    ]
    operands += [k_pool, v_pool]
    aliases = {idx0: 1, idx0 + 1: 2}
    out_shapes = [
        jax.ShapeDtypeStruct((R, C, KV, G, dk), q.dtype),
        jax.ShapeDtypeStruct(k_pool.shape, k_pool.dtype),
        jax.ShapeDtypeStruct(v_pool.shape, v_pool.dtype),
    ]
    out_specs = [
        pl.BlockSpec((1, C, KV, G, dk),
                     lambda r, p, pt, lg, of: (r, 0, 0, 0, 0)),
        pl.BlockSpec((1, ps, KV, dkp),
                     lambda r, p, pt, lg, of: (pt[r, p], 0, 0, 0)),
        pl.BlockSpec((1, ps, KV, dkp),
                     lambda r, p, pt, lg, of: (pt[r, p], 0, 0, 0)),
    ]
    if quant:
        scale_spec = pl.BlockSpec(
            (1, 1, KV), lambda r, p, pt, lg, of: (pt[r, p], 0, 0)
        )
        in_specs += [scale_spec, scale_spec]
        operands += [_scale_rows(k_scale), _scale_rows(v_scale)]
        aliases[idx0 + 2] = 3
        aliases[idx0 + 3] = 4
        out_shapes += [
            jax.ShapeDtypeStruct(operands[-2].shape, jnp.float32),
            jax.ShapeDtypeStruct(operands[-1].shape, jnp.float32),
        ]
        out_specs += [scale_spec, scale_spec]
    in_specs.append(
        pl.BlockSpec((1, C, ps), lambda r, p, pt, lg, of: (r, 0, p))
    )
    operands.append(mask)

    scratch = [
        pltpu.VMEM((C, KV, G, dk), jnp.float32),
        pltpu.VMEM((C, KV, G), jnp.float32),
        pltpu.VMEM((C, KV, G), jnp.float32),
        pltpu.VMEM((C, KV, G, dk), q.dtype),     # roped q
        pltpu.VMEM((C, KV, dk), k_new.dtype),    # roped k
    ]
    outs = pl.pallas_call(
        kernel,
        out_shape=out_shapes,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=grid,
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=scratch,
        ),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_ragged_vmem_limit(
                in_specs + out_specs, operands + out_shapes, scratch,
                C, H, max(dk, ps),
            ),
        ),
        input_output_aliases=aliases,
        name=f"ff_rope_ragged_paged_c{C}{_quant_suffix(quant, pack)}",
        interpret=_interpret(),
    )(page_table.astype(jnp.int32), logical.astype(jnp.int32),
      off.astype(jnp.int32), *operands)
    if quant:
        out, k_pool, v_pool, ks, vs = outs
        return (out.reshape(R, C, H, dk), k_pool, v_pool,
                ks.reshape(k_scale.shape), vs.reshape(v_scale.shape))
    out, k_pool, v_pool = outs
    return out.reshape(R, C, H, dk), k_pool, v_pool, None, None


# ---------------------------------------------------------------------------
# Grouped matmuls of a routed expert layer (models/transformer.py
# ``routed_experts_ffn``): rows sorted by expert, every expert's rows
# starting at a multiple of the row tile, so a tile belongs to ONE expert
# and its weights are named by a prefetched scalar in the block index map.


def grouped_tile(pairs: int, experts: int) -> int:
    """Rows a tile of the grouped expert matmuls at ``pairs`` (token,
    expert) pairs over ``experts`` experts held, both static: 128 (the
    MXU's rows) where the pairs are a tile's worth an expert, 16 (a bf16
    sublane tile) while they are a few an expert (the decode step: the
    kernel is then a read of the experts' weights and alignment costs
    rows, not time). 1024 pairs are 128 rows each of Mixtral's 8
    experts, whose matmuls in tiles of 16 would use an eighth of the
    MXU, and 16 rows each of LFM2's 64."""
    return 128 if pairs >= 128 * experts else 16


def grouped_block(width: int, depth: int, weights: int, itemsize: int) -> int:
    """Columns of a weight block of the grouped expert matmuls, from the
    static widths: ``weights`` stacks of (depth, width) an expert, read
    as (depth, block) blocks. The rows' block is read again for every
    column block, so 512 columns are doubled while there would be more
    than 16 column blocks, the wider block divides ``width`` and the
    weight blocks, double-buffered, stay within 32 MB of the 48 MB the
    calls state (:func:`_grouped_call`; the rows' and the result's
    blocks take the rest). LFM2's (2048, 1536) stays at 512; Mixtral's
    up-projections (4096 -> 14336) take 1024, 14 column blocks."""
    block = min(512, width)
    while (width // block > 16 and width % (2 * block) == 0
           and 2 * weights * depth * 2 * block * itemsize <= 32 << 20):
        block *= 2
    return block


def _grouped_call(kernel, name, tile_group, n_active, operands, in_specs,
                  out_shape, out_spec, grid):
    return pl.pallas_call(
        kernel,
        out_shape=out_shape,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=grid, in_specs=in_specs,
            out_specs=out_spec),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=48 << 20),
        name=name,
        interpret=_interpret(),
    )(tile_group.astype(jnp.int32), n_active.astype(jnp.int32).reshape(1),
      *operands)


def grouped_glu(rows, w_gate, w_up, tile_group, n_active, *, tm: int):
    """``silu(rows W_gate[g]) * (rows W_up[g])`` tile by tile, ``g`` the
    expert of the tile. rows (P, D), P a multiple of ``tm``;
    ``w_gate`` / ``w_up`` (G, D, F); ``tile_group`` (P / tm,) the index
    into G of each tile's expert, ``n_active`` the tiles that hold a
    row: the others are skipped (their output rows are left as they
    are; ``tile_group`` repeats the last active tile's expert there, so
    they fetch no weights). The grid runs the F blocks outermost and the
    tiles innermost: an expert's (D, tf) block is fetched once a run of
    its tiles, so the weights read are those of the experts that have
    rows, once (``tf``: :func:`grouped_block`). -> (P, F) in rows'
    dtype."""
    P, D = rows.shape
    F = w_gate.shape[-1]
    tf = grouped_block(F, D, 2, w_gate.dtype.itemsize)
    assert P % tm == 0 and F % tf == 0, (P, tm, F, tf)

    def kernel(tg_ref, na_ref, x_ref, wg_ref, wu_ref, o_ref):
        @pl.when(pl.program_id(1) < na_ref[0])
        def _():
            x = x_ref[...]
            g = jnp.dot(x, wg_ref[0], preferred_element_type=jnp.float32)
            u = jnp.dot(x, wu_ref[0], preferred_element_type=jnp.float32)
            o_ref[...] = (jax.nn.silu(g) * u).astype(o_ref.dtype)

    weights = pl.BlockSpec((1, D, tf), lambda j, t, tg, na: (tg[t], 0, j))
    return _grouped_call(
        kernel, f"ff_moe_grouped_glu_t{tm}", tile_group, n_active,
        (rows, w_gate, w_up),
        [pl.BlockSpec((tm, D), lambda j, t, tg, na: (t, 0)), weights, weights],
        jax.ShapeDtypeStruct((P, F), rows.dtype),
        pl.BlockSpec((tm, tf), lambda j, t, tg, na: (t, j)),
        (F // tf, P // tm))


def grouped_down(act, w_down, tile_group, n_active, *, tm: int):
    """``act W_down[g]`` tile by tile (see :func:`grouped_glu`): act
    (P, F), ``w_down`` (G, F, D) -> (P, D) float32."""
    P, F = act.shape
    D = w_down.shape[-1]
    td = grouped_block(D, F, 1, w_down.dtype.itemsize)
    assert P % tm == 0 and D % td == 0, (P, tm, D, td)

    def kernel(tg_ref, na_ref, a_ref, w_ref, o_ref):
        @pl.when(pl.program_id(1) < na_ref[0])
        def _():
            o_ref[...] = jnp.dot(a_ref[...], w_ref[0],
                                 preferred_element_type=jnp.float32)

    return _grouped_call(
        kernel, f"ff_moe_grouped_down_t{tm}", tile_group, n_active,
        (act, w_down),
        [pl.BlockSpec((tm, F), lambda j, t, tg, na: (t, 0)),
         pl.BlockSpec((1, F, td), lambda j, t, tg, na: (tg[t], 0, j))],
        jax.ShapeDtypeStruct((P, D), jnp.float32),
        pl.BlockSpec((tm, td), lambda j, t, tg, na: (t, j)),
        (D // td, P // tm))


# ---------------------------------------------------------------------------
# Latent paged attention (multi-head latent attention, absorbed form)
#
# The pool holds ONE line a token and layer (models/deepseek_v3.py): the
# normed compressed key/value ``c`` and the roped key ``kr`` every head
# shares. With the key and value up-projections absorbed into the query
# and the output, attention over the pool has H query heads on one key
# ``[c | kr]`` and one value ``c`` a token. The mask is causal by
# position and nothing else, so the kernels take each row's first
# position and its count of real queries (consecutive positions, leading
# columns: what every dispatch builds) and no mask array.
#
# The line lies in TWO arrays, both with a minor axis of whole lane
# tiles: ``c`` (P+1, ps, c) and ``kr`` PAIRED, (P+1, ps/2, 2*r): row j
# of a page holds the rope keys of its tokens j and j + ps/2 side by
# side. One array of c + r = 576 values, or a ``kr`` of 64, has a minor
# axis that is no multiple of 128, and the device then lays the array
# out with the page on its lanes for the step's line write and back for
# the kernel: two copies of the pool a layer (tests/test_chip_compile).
#
# A grid step of the kernel is a tile of query columns against a BLOCK
# of KB logical pages, KB x ps lines side by side: the running maximum
# and sum and the (rows, c) float32 accumulator are rescaled once a
# block (their cost does not grow with the keys), and the state lies
# replicated along a lane tile. The tile and KB come from the static
# shapes (mla_block): the widest tile, then the most pages whose
# float32 scores Mosaic still keeps out of its spill slots.

#: query columns of one grid step of :func:`mla_paged_attention`: with
#: H = 128 heads 32 columns are 4096 rows of the matmuls, an 8 MiB
#: float32 accumulator (a 16-column tile reads 10-13% slower on the
#: kernel alone at any block of pages: PERF.md section 6, PR 43)
MLA_QUERY_TILE = 32

#: bytes of the float32 scores of one grid step of that kernel, rows x
#: the block's lines: at 8 MiB (16 columns x 8 pages of 128 lines, or 32
#: x 4) Mosaic spills 120 MB of registers and the kernel runs three
#: times slower than at 4 MiB, which 16 x 4 and 32 x 2 both are
MLA_SCORES_BYTES = 4 << 20

#: lanes of that kernel's softmax state: the running maximum and sum of
#: a row are kept replicated along one whole lane tile, so that reading
#: them against the scores and the accumulator moves no data (held as
#: (rows, 1) arrays they cost as much as the rest of the step)
MLA_STATE_LANES = 128


def mla_block(C: int, NP: int, H: int, ps: int) -> tuple[int, int]:
    """(query columns, pages) of one grid step of
    :func:`mla_paged_attention`, from the static shapes: the tile is
    :data:`MLA_QUERY_TILE` columns (the chunk's own under it); the block
    is the largest of 8, 4, 2, 1 pages that the table holds at least
    once and whose scores stay within :data:`MLA_SCORES_BYTES`. The
    DeepSeek cell's mixed step (128 heads, pages of 128 lines) takes 32
    columns x 2 pages, its decode step 1 x 8."""
    TC = min(C, MLA_QUERY_TILE)
    for KB in (8, 4, 2):
        if KB <= NP and TC * H * KB * ps * 4 <= MLA_SCORES_BYTES:
            return TC, KB
    return TC, 1


def _along_lanes(x: jnp.ndarray, width: int) -> jnp.ndarray:
    """A state replicated along its lanes, (M, MLA_STATE_LANES), at
    ``width`` lanes: whole lane tiles side by side, which moves no
    data, or a broadcast (the tests' few lines a page)."""
    lanes = x.shape[1]
    if width % lanes == 0:
        return jnp.tile(x, (1, width // lanes))
    return jnp.broadcast_to(x[:, :1], (x.shape[0], width))


def pair_rope_place(off: jnp.ndarray, page_size: int, width: int):
    """Where the rope key of the token at offset ``off`` of its page
    lies in the paired ``kr`` page (ps/2, 2*width): (row (...,), lanes
    (..., width))."""
    half = page_size // 2
    lanes = (off // half)[..., None] * width + jnp.arange(width, dtype=off.dtype)
    return off % half, lanes


def unpair_rope_lines(kr: jnp.ndarray) -> jnp.ndarray:
    """Paired pages (..., ps/2, 2*r) as lines in token order
    (..., ps, r)."""
    r = kr.shape[-1] // 2
    return jnp.concatenate([kr[..., :r], kr[..., r:]], axis=-2)


def mla_paged_attention_xla(
    q_abs: jnp.ndarray,       # (R, C, H, c) absorbed queries q'
    q_rope: jnp.ndarray,      # (R, C, H, r) roped queries
    c_pool: jnp.ndarray,      # (P+1, ps, c) compressed lines
    kr_pool: jnp.ndarray,     # (P+1, ps/2, 2r) rope keys, paired
    page_table: jnp.ndarray,  # (R, NP) int32
    q_start: jnp.ndarray,     # (R,) int32 position of each row's column 0
    q_len: jnp.ndarray,       # (R,) int32 real queries a row
    *,
    scale: float,
) -> jnp.ndarray:
    """The XLA twin of :func:`mla_paged_attention` (the CPU path and the
    kernel's correctness reference): gather each row's lines through
    the page table, one masked softmax over them. -> (R, C, H, c) in
    q's dtype; padding columns come out zero."""
    R, C = q_abs.shape[:2]
    c = gather_pages(c_pool, page_table)                       # (R, S, c)
    kr = unpair_rope_lines(jnp.take(kr_pool, page_table, axis=0))
    kr = kr.reshape(R, -1, kr.shape[-1])                       # (R, S, r)
    cols = jnp.arange(C, dtype=jnp.int32)
    real = cols[None] < q_len[:, None]                         # (R, C)
    q_pos = q_start[:, None] + cols[None]
    seen = (jnp.arange(c.shape[1], dtype=jnp.int32)[None, None]
            <= q_pos[..., None]) & real[..., None]             # (R, C, S)
    scores = (jnp.einsum("rchw,rsw->rhcs", q_abs, c,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("rchw,rsw->rhcs", q_rope, kr,
                           preferred_element_type=jnp.float32)) * scale
    scores = jnp.where(seen[:, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q_abs.dtype)
    out = jnp.einsum("rhcs,rsv->rchv", probs, c,
                     preferred_element_type=jnp.float32)
    return jnp.where(real[..., None, None], out, 0.0).astype(q_abs.dtype)


def mla_paged_attention(
    q_abs: jnp.ndarray,       # (R, C, H, c) absorbed queries q'
    q_rope: jnp.ndarray,      # (R, C, H, r) roped queries
    c_pool: jnp.ndarray,      # (P+1, ps, c) compressed lines
    kr_pool: jnp.ndarray,     # (P+1, ps/2, 2r) rope keys, paired
    page_table: jnp.ndarray,  # (R, NP) int32
    q_start: jnp.ndarray,     # (R,) int32 position of each row's column 0
    q_len: jnp.ndarray,       # (R,) int32 real queries a row
    *,
    scale: float,
    row_offset=None,          # int32 scalar: pool row of table entry 0
) -> jnp.ndarray:
    """Latent paged attention, ``ff_mla_paged_c<C>``: grid (row, query
    tile, block of logical pages). A step is handed the block's KB
    pages of lines through the page table (each pool is passed KB
    times, one page block an operand; ``row_offset``: the pools are
    every layer's pages, see :func:`_ragged_paged_attention`) and
    attends a tile's queries, all H heads of the tile's columns as the
    rows of its matmuls in the pools' dtype, against the KB x ps lines
    side by side: scores ``q' c^T + q_rope kr^T``, values over ``c``,
    and between them ONE online-softmax update in float32 a block: one
    row maximum, one exponent pass, one rescale of the (rows, c)
    accumulator. The running maximum and sum lie replicated along a
    lane tile (:data:`MLA_STATE_LANES`). The tile and the block come
    from the shapes (:func:`mla_block`).

    Work follows the real queries: a page past a tile's last real
    query is neither fetched (its index map repeats the page of that
    query, and a block whose index repeats is not fetched again; so
    does a page past the table's end, where ``NP`` is no multiple of
    KB) nor, by whole blocks, computed; a tile with no real query
    fetches nothing and writes zeros; a tile whose one real query is
    its first column (a decode row of a mixed step) runs at one
    column's rows; the causal mask is computed from the positions and
    only on the blocks it cuts, where it also hides the block's pages
    past the last query.
    -> (R, C, H, c) in q's dtype, padding columns zero."""
    R, C, H, V = q_abs.shape
    dr = q_rope.shape[-1]
    ps = c_pool.shape[1]
    NP = page_table.shape[1]
    TC, KB = mla_block(C, NP, H, ps)
    if C % TC:
        raise ValueError(f"chunk {C} is no multiple of the query tile {TC}")
    NB = pl.cdiv(NP, KB)        # blocks of a row's table
    W = KB * ps                 # keys of a block
    prefetch = [page_table.astype(jnp.int32), q_start.astype(jnp.int32),
                q_len.astype(jnp.int32)]
    if row_offset is not None:
        prefetch.append(jnp.asarray(row_offset, jnp.int32).reshape(1))

    def live_tile(r, t, count):
        # the last tile of row r that holds a real query, or 0
        return jnp.minimum(t, jnp.maximum(count[r] - 1, 0) // TC)

    def q_block(r, t, b, pt, start, count, *base):
        return (r, live_tile(r, t, count), 0, 0)

    def page_block(j):
        # page j of block b, or the page of the tile's last real query
        # once the block has passed it (a page past the table's end
        # lies past every query)
        def index(r, t, b, pt, start, count, *base):
            t = live_tile(r, t, count)
            last = start[r] + jnp.minimum(count[r], (t + 1) * TC) - 1
            row = pt[r, jnp.minimum(b * KB + j,
                                    jnp.clip(last // ps, 0, NP - 1))]
            if base:
                row = row + base[0][0]
            return (row, 0, 0)
        return index

    def kernel(pt_ref, start_ref, count_ref, *refs):
        qa_ref, qr_ref, *pages, out_ref, acc, m_scr, l_scr = \
            refs[-(2 * KB + 6):]
        c_refs, kr_refs = pages[:KB], pages[KB:]
        r, t, b = (pl.program_id(i) for i in range(3))
        start, count = start_ref[r], count_ref[r]
        cols = jnp.clip(count - t * TC, 0, TC)     # real queries of the tile
        first = start + t * TC                      # position of its column 0
        last = first + cols - 1
        nt = (((1,), (1,)), ((), ()))               # x y^T

        def step(n):
            # the tile's first n columns: n * H leading rows of the
            # accumulators
            M = n * H

            @pl.when(b == 0)
            def _():
                acc[:M] = jnp.zeros((M, V), jnp.float32)
                m_scr[:M] = jnp.full((M, MLA_STATE_LANES), NEG_INF, jnp.float32)
                l_scr[:M] = jnp.zeros((M, MLA_STATE_LANES), jnp.float32)

            def attend(cut):
                # the block's lines side by side, one softmax update
                c = jnp.concatenate([ref[0] for ref in c_refs])        # (W, V)
                kr = jnp.concatenate(
                    [unpair_rope_lines(ref[0]) for ref in kr_refs])    # (W, dr)
                s = (jax.lax.dot_general(
                        qa_ref[0, :n].reshape(M, V), c, nt,
                        preferred_element_type=jnp.float32)
                     + jax.lax.dot_general(
                        qr_ref[0, :n].reshape(M, dr), kr, nt,
                        preferred_element_type=jnp.float32)) * scale
                if cut:
                    # at the scores' own shape: Mosaic broadcasts no
                    # boolean along sublanes. A padding column sees
                    # nothing in any block and is zeroed at the end
                    key = b * W + jax.lax.broadcasted_iota(
                        jnp.int32, (n, H, W), 2)
                    col = jax.lax.broadcasted_iota(jnp.int32, (n, H, W), 0)
                    s = jnp.where(key <= first + col, s.reshape(n, H, W),
                                  NEG_INF).reshape(M, W)
                m_old = m_scr[:M]
                m_new = jnp.maximum(m_old, s.max(axis=1, keepdims=True))
                prob = jnp.exp(s - _along_lanes(m_new, W))
                corr = jnp.exp(m_old - m_new)
                l_scr[:M] = l_scr[:M] * corr + prob.sum(axis=1, keepdims=True)
                acc[:M] = acc[:M] * _along_lanes(corr, V) + jnp.dot(
                    prob.astype(c.dtype), c, preferred_element_type=jnp.float32)
                m_scr[:M] = m_new

            # every query of the n sees every key of the block, or the
            # mask cuts it
            whole = (b * W + W - 1 <= first) & (cols >= n)
            pl.when((b * W <= last) & whole)(lambda: attend(False))
            pl.when((b * W <= last) & ~whole)(lambda: attend(True))

            @pl.when(b == NB - 1)
            def _():
                o = acc[:M] / _along_lanes(jnp.maximum(l_scr[:M], 1e-20), V)
                o = o.reshape(n, H, V)
                if n > 1:
                    col = jax.lax.broadcasted_iota(jnp.int32, (n, H, V), 0)
                    o = jnp.where(col < cols, o, 0.0)
                out_ref[0, :n] = o.astype(out_ref.dtype)
                if n < TC:
                    out_ref[0, n:] = jnp.zeros((TC - n, H, V), out_ref.dtype)

        if TC > 1:
            pl.when(cols > 1)(lambda: step(TC))
        pl.when(cols == 1)(lambda: step(1))

        @pl.when((cols == 0) & (b == NB - 1))
        def _():
            out_ref[0] = jnp.zeros((TC, H, V), out_ref.dtype)

    in_specs = ([pl.BlockSpec((1, TC, H, V), q_block),
                 pl.BlockSpec((1, TC, H, dr), q_block)]
                + [pl.BlockSpec((1, ps, V), page_block(j)) for j in range(KB)]
                + [pl.BlockSpec((1, ps // 2, 2 * dr), page_block(j))
                   for j in range(KB)])
    out_spec = pl.BlockSpec((1, TC, H, V), lambda r, t, b, *_: (r, t, 0, 0))
    out_shape = jax.ShapeDtypeStruct((R, C, H, V), q_abs.dtype)
    M = TC * H
    scratch = [pltpu.VMEM((M, V), jnp.float32),
               pltpu.VMEM((M, MLA_STATE_LANES), jnp.float32),
               pltpu.VMEM((M, MLA_STATE_LANES), jnp.float32)]
    # blocks double-buffered, the scratch, and the body's intermediates:
    # the block's lines side by side, the float32 scores (M, W) with
    # their masked and exponentiated forms (about six live at the widest
    # point), the values' product (M, V) and the rescaled accumulator
    operands = (q_abs, q_rope, *[c_pool] * KB, *[kr_pool] * KB)
    need = 2 * sum(_vmem_bytes(spec.block_shape, a.dtype) for spec, a in
                   zip(in_specs + [out_spec], operands + (out_shape,)))
    need += sum(_vmem_bytes(s.shape, s.dtype) for s in scratch)
    need += _vmem_bytes((W, V + dr), c_pool.dtype)
    need += 6 * _vmem_bytes((M, W), jnp.float32)
    need += 2 * _vmem_bytes((M, V), jnp.float32)
    if need > _VMEM_SCOPE_CEILING:
        raise ValueError(
            f"latent paged attention at {TC} columns x {H} heads needs "
            f"{need >> 20} MiB of VMEM a grid step (ceiling "
            f"{_VMEM_SCOPE_CEILING >> 20} MiB)")
    return pl.pallas_call(
        kernel,
        out_shape=out_shape,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(R, C // TC, NB),
            in_specs=in_specs,
            out_specs=out_spec,
            scratch_shapes=scratch,
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=max(need, _VMEM_SCOPE_DEFAULT),
        ),
        name=f"ff_mla_paged_c{C}",
        interpret=_interpret(),
    )(*prefetch, *operands)
