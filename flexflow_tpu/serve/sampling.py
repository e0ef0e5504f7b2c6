"""Decode heads: greedy argmax, temperature, top-k, top-p sampling.

TPU-native equivalents of the reference decode operators ``argmax``,
``sampling`` (top-p via sorted cumsum, reference ``src/ops/sampling.cc``),
``arg_topk``/``beam_topk`` (reference ``src/ops/arg_topk.cc``,
``beam_topk.cc``). One jitted function handles a whole batch with
per-request parameters as arrays, so mixed greedy/sampling batches run in
a single program (the reference dispatches per-model decode-head ops).

Mode-specialized heads (how every step samples: the engine's step
programs, pipelined and sync, and the scheduler's host-side head): the
general path pays one full ``(R, V)`` descending sort — shared by the
top-k and top-p filters — whatever the rows ask for, and every row of
the common decode batch is greedy. ``mode`` specializes the compiled
head to what the batch actually needs, chosen host-side on every
dispatch by :func:`choose_sample_mode` from the step's decode-head
arrays:

``"greedy"``
    every row argmaxes — no scaling, no filters, no sort, no RNG.
``"sample"``
    temperature-only sampling (top-k/top-p both disabled) — no sort.
``"topk"``
    per-row top-k (no top-p): the k-th-value threshold comes from one
    ``lax.top_k`` over a static ``topk_cap`` bucket (power-of-two ≥
    the batch max k, so steady workloads reuse one compile) — O(V·log
    cap) instead of the full sort.
``"full"``
    the reference path: ONE shared sort feeds both filters (the
    top-k-filtered sorted tensor is derived analytically from the
    unfiltered sort, so top-p never re-sorts).

Every mode is bitwise-identical to the ``"full"`` reference head on
the rows it serves: same threshold values (a top-k prefix of a
descending sort IS the sort's prefix), same filtered logits, same
categorical draw from the same key.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = -1e30

#: the modes a compiled head can specialize to: what tags every step
#: key of the engine that samples on the device (with its top-k cap)
SAMPLE_MODES = ("full", "greedy", "sample", "topk")

#: largest per-row top-k the bucketed "topk" mode serves; bigger ks
#: fall back to the full-sort head (one compile per power-of-two
#: bucket keeps the step-key set small and steady)
TOPK_CAP_LIMIT = 128


def choose_sample_mode(
    greedy: np.ndarray,   # (R,) bool
    topp: np.ndarray,     # (R,) float; >= 1 disables
    topk: np.ndarray,     # (R,) int; <= 0 disables
    vocab_size: int,
) -> Tuple[str, int]:
    """Pick the cheapest head mode serving this batch's decode-head
    arrays (host-side — the scheduler knows every row's
    GenerationConfig). Returns ``(mode, topk_cap)``; ``topk_cap`` is 0
    except for the bucketed "topk" mode."""
    greedy = np.asarray(greedy, bool)
    if bool(greedy.all()):
        return "greedy", 0
    sampling = ~greedy
    if bool((np.asarray(topp, np.float32)[sampling] < 1.0).any()):
        return "full", 0
    mk = int(np.asarray(topk, np.int64)[sampling].max(initial=0))
    if mk <= 0:
        return "sample", 0
    if mk >= min(TOPK_CAP_LIMIT, vocab_size):
        return "full", 0
    cap = 1 << (mk - 1).bit_length()  # smallest power of two >= mk
    return "topk", min(cap, vocab_size)


def _apply_topk(logits: jnp.ndarray, k: int) -> jnp.ndarray:
    """Static-k top-k filter: keep the k largest logits per row."""
    if k <= 0:
        return logits
    kth = jax.lax.top_k(logits, k)[0][..., -1:]
    return jnp.where(logits < kth, NEG_INF, logits)


def _sorted_desc(logits: jnp.ndarray) -> jnp.ndarray:
    """One full descending sort — the shared tensor both filters cut."""
    return jnp.sort(logits, axis=-1)[..., ::-1]


def _topk_filter(
    logits: jnp.ndarray,
    topk: jnp.ndarray,
    sorted_desc: Optional[jnp.ndarray] = None,
):
    """Per-row top-k filter (``topk`` (R,) int32; <=0 disables for that
    row) — the dynamic-k counterpart of :func:`_apply_topk` so mixed
    batches honor each request's ``GenerationConfig.topk`` in ONE
    program (the reference dispatches a per-model arg_topk op,
    ``src/ops/arg_topk.cc``). Uses a sorted threshold instead of
    ``lax.top_k`` because k is a traced per-row value.

    Returns ``(filtered, filtered_sorted)``: the filter drops a SUFFIX
    of the descending sort, so the filtered tensor's sort is the shared
    sort with that suffix set to NEG_INF — derived, never re-sorted
    (the top-p filter consumes it)."""
    V = logits.shape[-1]
    if sorted_desc is None:
        sorted_desc = _sorted_desc(logits)
    kk = jnp.clip(topk, 1, V)
    kth = jnp.take_along_axis(sorted_desc, (kk - 1)[..., None], axis=-1)
    keep_all = (topk <= 0)[..., None]
    filtered = jnp.where(keep_all | (logits >= kth), logits, NEG_INF)
    filtered_sorted = jnp.where(
        keep_all | (sorted_desc >= kth), sorted_desc, NEG_INF
    )
    return filtered, filtered_sorted


def _topp_filter(
    logits: jnp.ndarray,
    topp: jnp.ndarray,
    sorted_desc: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Top-p (nucleus) filter — sorted cumulative-probability cut exactly
    like the reference's sorted-cumsum kernel (sampling.cc). ``topp`` is
    per-row (R,); topp >= 1 keeps everything. ``sorted_desc`` is the
    descending sort of ``logits`` when the caller already has it."""
    if sorted_desc is None:
        sorted_desc = _sorted_desc(logits)
    sorted_probs = jax.nn.softmax(sorted_desc, axis=-1)
    cum = jnp.cumsum(sorted_probs, axis=-1)
    # Keep tokens while the cumulative mass *before* them is < topp.
    keep_sorted = (cum - sorted_probs) < topp[..., None]
    # Threshold logit: smallest kept logit per row.
    thresh = jnp.min(
        jnp.where(keep_sorted, sorted_desc, jnp.inf), axis=-1, keepdims=True
    )
    return jnp.where(logits < thresh, NEG_INF, logits)


@functools.partial(jax.jit, static_argnames=("topk", "mode", "topk_cap"))
def sample_tokens(
    logits: jnp.ndarray,      # (R, V) float
    key: jax.Array,
    *,
    greedy: jnp.ndarray,      # (R,) bool — argmax instead of sampling
    temperature: jnp.ndarray, # (R,) float
    topp: jnp.ndarray,        # (R,) float; >=1 disables
    topk: int = 0,            # static; 0 disables
    topk_arr: Optional[jnp.ndarray] = None,  # (R,) int32; <=0 disables per row
    mode: str = "full",       # static head specialization (module doc)
    topk_cap: int = 0,        # static k bucket for mode="topk"
) -> jnp.ndarray:
    """Sample one token per request slot. Returns (R,) int32.

    ``mode``/``topk_cap`` come from :func:`choose_sample_mode`; passing
    a mode the batch's decode-head arrays don't satisfy (e.g.
    ``"greedy"`` with a sampling row) silently serves the wrong head —
    the host chooser is the contract."""
    logits = logits.astype(jnp.float32)
    greedy_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    if mode == "greedy":
        return greedy_tok
    t = jnp.maximum(temperature, 1e-6)[..., None]
    scaled = logits / t
    scaled = _apply_topk(scaled, topk)
    if mode == "sample":
        pass  # temperature only: both filters are identity
    elif mode == "topk":
        # k-th value from a static top-k bucket: bitwise the same
        # threshold as the sort path (a descending sort's prefix)
        V = scaled.shape[-1]
        top = jax.lax.top_k(scaled, topk_cap)[0]        # (R, cap)
        kk = jnp.clip(topk_arr, 1, V)
        kth = jnp.take_along_axis(top, (kk - 1)[..., None], axis=-1)
        keep_all = (topk_arr <= 0)[..., None]
        scaled = jnp.where(keep_all | (scaled >= kth), scaled, NEG_INF)
    else:  # "full" — one shared sort feeds both filters
        sorted_desc = _sorted_desc(scaled)
        if topk_arr is not None:
            scaled, sorted_desc = _topk_filter(scaled, topk_arr, sorted_desc)
        scaled = _topp_filter(scaled, topp, sorted_desc)
    sampled = jax.random.categorical(key, scaled, axis=-1).astype(jnp.int32)
    return jnp.where(greedy, greedy_tok, sampled)


@functools.partial(jax.jit, static_argnames=("k",))
def beam_topk(logprobs: jnp.ndarray, k: int):
    """Top-k over the vocab per row — the SSM beam expansion head
    (reference ``beam_topk.cc``). Returns (values, indices) each (..., k)."""
    return jax.lax.top_k(logprobs, k)


@jax.jit
def log_softmax(logits: jnp.ndarray) -> jnp.ndarray:
    return jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
