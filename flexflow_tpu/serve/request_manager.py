"""RequestManager — request queue + continuous batching + decoding loops.

TPU-native counterpart of the reference ``RequestManager`` (reference
``src/runtime/request_manager.cc:1-2435``): tokenize + queue incoming
requests, admit them into free batch slots, build per-step BatchConfigs
(``prepare_next_batch``, :350), run the incremental-decoding loop
(``generate_incr_decoding``, :2292), track per-request profiling, and
free slots on completion.

Scheduling is **iteration-level continuous batching**: prompt processing
is *chunked prefill* (a prompt enters the batch in fixed-size chunks so
prefill and decode share one program shape), and — with
``ServingConfig.continuous_batching`` (the default) — prefill chunks
ride in the SAME dispatch-ahead pipelined step as decode rows. One
jitted *mixed step* carries every decode row's single token plus up to
``max_tokens_per_step`` new prompt tokens, samples on device for decode
rows AND prefill-final rows, and feeds the sampled tokens to the next
dispatch without a host round-trip. Admissions, chunk progression and
completions therefore never drain the pipeline; host-side token append
is deferred to drain (flush) time, ``dispatch_ahead`` steps behind the
device. ``continuous_batching=False`` restores the flush-on-admit
scheduler (any PREFILLING request forces the blocking sync path) — the
bench baseline.
"""
from __future__ import annotations

import dataclasses
import enum
import time
from typing import Any, Dict, Iterator, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..logging_utils import get_logger
from ..metrics import SchedulerStats
from ..obs.tracer import NULL_TRACER
from .batch_config import (
    BatchConfig,
    GenerationConfig,
    GenerationResult,
    ProfileInfo,
    StreamEvent,
)
from .engine import InferenceEngine
from .sampling import choose_sample_mode, sample_tokens


class RequestStatus(enum.Enum):
    PENDING = "pending"
    PREFILLING = "prefilling"
    DECODING = "decoding"
    COMPLETED = "completed"
    # Terminal failure: the request can never be served under the
    # configured limits (e.g. its prompt alone exceeds the KV budget).
    # Surfaced via GenerationResult.error instead of live-locking the
    # scheduler or crashing unrelated requests.
    ERROR = "error"


TERMINAL_STATUSES = (RequestStatus.COMPLETED, RequestStatus.ERROR)


def trim_to_rung(ladder: Sequence[int], slots: int, decoding: int,
                 chunks: Sequence[int], final: Sequence[bool]) -> List[int]:
    """The prompt tokens a mixed step hands out, held under a rung of
    the engine's own ladder. ``ladder`` is the engine's packed rungs,
    ascending (:meth:`InferenceEngine.pack_ladder`), ``decoding`` the
    step's rows of one token, ``chunks`` the tokens each prefilling row
    would take, OLDEST admission first, and ``final`` whether that
    chunk is its prompt's last. Where the step's real tokens pass the
    widest rung under them by no more than ``slots`` (the most that
    decoding rows can ever add to a step: prompts in whole chunks sit
    ON a rung, and the rows' own tokens push them over it), the step
    gives those few tokens up and runs at that rung and not the next,
    at least twice as wide: they are taken from the newest prompt
    first, so the oldest keep their pace, and from a row whose chunk is
    not its prompt's last before one whose chunk is (a trimmed last
    chunk costs that request a step of its first token). A row left
    with nothing is not in the step. Returns the chunks to hand out:
    they and ``decoding`` sum to the rung where the rule fires, and are
    ``chunks`` as given where it does not (an empty ladder, a step
    further over than the slots, a rung that the decoding rows alone
    fill: a step must hand some prompt a token). A given token is in
    its row's next chunk; none is dropped."""
    out = list(chunks)
    real = decoding + sum(out)
    rung = max((w for w in ladder if w < real), default=0)
    give = real - rung
    if rung <= decoding or give > slots:
        return out
    for i in sorted(range(len(out)), key=lambda i: (final[i], -i)):
        take = min(give, out[i])
        out[i] -= take
        give -= take
    return out


@dataclasses.dataclass
class Request:
    """reference ``Request`` (request_manager.h:92-278)."""

    request_id: int
    prompt: str
    tokens: List[int]                 # prompt + generated so far
    prompt_len: int
    gen: GenerationConfig
    status: RequestStatus = RequestStatus.PENDING
    slot: int = -1
    n_cached: int = 0                 # tokens whose K/V commit was flushed
    n_sched: int = 0                  # prompt tokens dispatched (may run
    # ahead of n_cached while prefill chunks are in flight)
    inflight: int = 0                 # dispatched sampling steps not yet
    # fetched (decode rows + the prefill-final chunk)
    pipeline_refs: int = 0            # in-flight dispatches touching this
    # request's slot — the slot (and its pages) may only be released once
    # this drains to 0, or later garbage writes from already-dispatched
    # steps would scribble on a reassigned slot/page
    admit_seq: int = -1               # admission order (preemption victims
    # are chosen newest-first, vLLM-style recompute preemption)
    error: Optional[str] = None
    profile: ProfileInfo = dataclasses.field(default_factory=ProfileInfo)

    @property
    def output_tokens(self) -> List[int]:
        return self.tokens[self.prompt_len :]


class RequestManager:
    # Subclasses that keep a second engine's cache in sync (SpecInfer)
    # must not use the LLM-only fast decode pipeline.
    supports_fast_decode = True
    # Automatic prefix caching (serve/prefix_cache.py). Managers that
    # mirror slot state across engines (SpecInfer) maintain ONE radix
    # tree per page pool and keep the matched lengths aligned through
    # the _cache_attach/_cache_insert hooks — the SSM pools page
    # independently but share the token offset math.
    supports_prefix_cache = True
    # The sync step that samples on the device (engine.run_sampled)
    # bypasses the _run_batch hook; managers that override _run_batch
    # to keep a second engine in sync (SpecInfer) opt out and keep the
    # two-dispatch step + host sample.
    supports_fused_sampling = True

    def __init__(
        self,
        engine: InferenceEngine,
        tokenizer: Any = None,
        eos_token_id: Optional[int] = None,
        seed: int = 0,
        output_file: Optional[str] = None,
    ):
        self.engine = engine
        if engine.serving.inference_debugging and getattr(
            engine.model, "serve_debug_activations", None
        ) is not None:
            # the dump hook lives in engine.run(): the dispatch-ahead
            # fused decode pipeline bypasses it, so debugging forces
            # every step through the sync path (triage mode is allowed
            # to be slow — the reference's inference_debugging is too).
            # A model without the hook keeps fast decode: nothing could
            # be dumped anyway (the engine logs a loud warning instead
            # of silently paying the slowdown, ADVICE.md round 5).
            self.supports_fast_decode = False
        self.tokenizer = tokenizer
        self.eos_token_id = eos_token_id
        # Per-request telemetry sink (reference -output-file,
        # request_manager.cc:417-440: e2e latency, decoding steps and
        # token ids appended per finished request).
        self.output_file = output_file
        if eos_token_id is None and tokenizer is not None:
            self.eos_token_id = getattr(tokenizer, "eos_token_id", None)
        self.requests: Dict[int, Request] = {}
        self.pending: List[int] = []
        self.slots: List[Optional[int]] = [None] * engine.num_slots
        # Request ids whose slot + pages must SURVIVE completion: the
        # cluster's prefill→decode migration (serve/cluster/) reads the
        # finished prefill's pages out of the pool after the request
        # completes — releasing them at _finish would hand the pages to
        # the next admission before they were shipped. The holder calls
        # :meth:`release_held` once the pages have migrated.
        self.hold_finished: set = set()
        self._next_id = 1000000  # reference starts guids at 1000000
        self._admit_counter = 0
        self._key = jax.random.PRNGKey(seed)
        self._step_counter = 0
        # Dispatch-ahead pipeline (reference's 4-deep batch-future
        # queue, request_manager.cc:2310-2325): entries are
        # (device_tokens, [(rid, slot, ntoks, samples), ...])
        # oldest-first; ``ntoks`` is the row's cache lines this dispatch
        # wrote, ``samples`` whether its sampled token is meaningful
        # (decode rows and prefill-final rows).
        self._inflight: List[tuple] = []
        # Slots whose sampled token in the NEWEST dispatch is their next
        # input (device feedback instead of a host token).
        self._prev_dispatch_slots: set = set()
        self.stats = SchedulerStats()
        # per-slot state beside the pool (SchedulerStats.note_rows)
        self._slot_state = bool(getattr(engine.model, "SLOT_STATE", ()))
        if self._slot_state:
            self.stats.slot_state_bytes = engine.slot_state_bytes()
        # layers of a latent page pool (SchedulerStats.latent_lines)
        self._latent_layers = (
            engine.cache["latent"].shape[0]
            if "latent" in getattr(engine.model, "PAGE_POOLS", ()) else 0)
        # layers that step a recurrent state (SchedulerStats.recurrent_updates)
        recurrent = getattr(engine.model, "RECURRENT_STATE", None)
        self._recurrent_layers = (
            engine.cache[recurrent].shape[0] if recurrent else 0)
        self._log = get_logger("serve")
        # Observability (flexflow_tpu/obs): request-lifecycle tracing +
        # failure flight recorder. Disabled by default — every EVENT
        # site below guards on ``tracer.enabled`` (one attribute read)
        # before building any event, so a no-obs run builds no event
        # and touches no buffer (tests/test_observability.py proves
        # it). The phase spans of a step (obs.tracer.STEP_SPANS) are
        # called unguarded: with the null tracer each is a bare
        # profiler annotation, recorded only while a profiler session
        # is open. obs.attach_observability wires a live tracer in; the
        # engine shares it so dispatch events land on the same lane.
        self.tracer = NULL_TRACER
        self.flight_recorder = None
        # rid -> cluster-wide trace id (bound at submission; local runs
        # fall back to the rid itself — see trace_of)
        self._trace_ids: Dict[int, int] = {}
        # The build log (obs/builds.py): what each engine's builds cost
        # surfaces in the scheduler stats (``note_build``), stamped
        # with this scheduler's step. The callable indirection survives
        # bench-style stat swaps (rm.stats = SchedulerStats()) the same
        # way the prefix cache's stats hook does.
        for i, eng in enumerate(self._engines()):
            log = getattr(eng, "build_log", None)
            if log is not None:
                log.attach(self._build_context,
                           prefix=f"ssm{i - 1}/" if i else "")
        # Automatic prefix caching (paged layout only — on dense,
        # prefix_caching=True is a documented passthrough: there are no
        # pages to share). The radix tree owns one reference per cached
        # page; the allocator's reclaim hook evicts idle cached pages
        # before any allocation fails.
        self.prefix_cache = None
        sc = engine.serving
        if (
            self.supports_prefix_cache
            and sc.prefix_caching
            and getattr(engine, "paged", False)
        ):
            from .prefix_cache import PrefixCache

            # Hierarchical KV cache: with a host_cache_bytes budget the
            # cache SPILLS cold pages to host RAM instead of evicting
            # (async D2H via engine.fetch_page; re-admitted with an
            # async H2D upload on a later match). The spill handles are
            # harvested to numpy at flush time — the scheduler's
            # existing sync point — so the decode loop never blocks on
            # a transfer.
            host_kw = {}
            if sc.host_cache_bytes:
                host_kw = dict(
                    fetch_page=engine.fetch_page,
                    upload_page=engine.upload_page,
                    host_cache_bytes=sc.host_cache_bytes,
                    page_bytes=engine.page_host_bytes(),
                )
            self.prefix_cache = PrefixCache(
                engine.pager,
                copy_page=engine.copy_page,
                policy=sc.cache_policy,
                stats=lambda: self.stats,
                **host_kw,
            )
            engine.pager.reclaim_cb = self.prefix_cache.reclaim

    # ------------------------------------------------------------------
    # registration (reference register_new_request, request_manager.cc:137)

    def register_request(
        self,
        prompt: Union[str, Sequence[int]],
        gen: Optional[GenerationConfig] = None,
    ) -> int:
        gen = gen or GenerationConfig()
        if isinstance(prompt, str):
            if self.tokenizer is None:
                raise ValueError("string prompt requires a tokenizer")
            tokens = list(self.tokenizer.encode(prompt))
            text = prompt
        else:
            tokens = [int(t) for t in prompt]
            text = ""
        if not tokens:
            raise ValueError("empty prompt")
        max_len = self.engine.serving.max_sequence_length
        if len(tokens) >= max_len:
            tokens = tokens[: max_len - 1]
        rid = self._next_id
        self._next_id += 1
        req = Request(
            request_id=rid,
            prompt=text,
            tokens=list(tokens),
            prompt_len=len(tokens),
            gen=gen,
        )
        req.profile.start_time = time.perf_counter()
        self.requests[rid] = req
        self.pending.append(rid)
        return rid

    def submit(
        self,
        prompt: Union[str, Sequence[int]],
        gen: Optional[GenerationConfig] = None,
        max_new_tokens: Optional[int] = None,
        trace_id: Optional[int] = None,
    ) -> int:
        """Non-blocking submission: queue one request and return its id
        immediately. Drive the scheduler with :meth:`step` (or a
        concurrent :meth:`generate_stream`/:meth:`generate` call) and
        read tokens from ``requests[rid]`` / :meth:`result` as they
        drain. ``trace_id`` binds a cluster-wide trace id so this
        request's spans stitch with its router/migration/other-replica
        spans (obs/tracer.py); local rids are their own trace ids."""
        gen = gen or GenerationConfig()
        if max_new_tokens is not None:
            gen = dataclasses.replace(gen, max_new_tokens=max_new_tokens)
        rid = self.register_request(prompt, gen)
        if trace_id is not None:
            self._trace_ids[rid] = int(trace_id)
        return rid

    def bind_trace(self, rid: int, trace_id: int) -> None:
        """Bind ``rid``'s spans to a cluster-wide trace id (submission
        and migration adoption call this — see obs/__init__.py)."""
        self._trace_ids[int(rid)] = int(trace_id)

    def trace_of(self, rid: int) -> int:
        """The trace id this request's spans carry: the bound
        cluster-wide id, else the rid itself (single-engine runs)."""
        return self._trace_ids.get(rid, rid)

    # ------------------------------------------------------------------
    # cluster hooks (serve/cluster/): hold-for-migration + adoption of
    # an externally prefilled request

    def hold_on_finish(self, rid: int) -> None:
        """Mark ``rid`` so completion does NOT release its slot/pages —
        the prefill→decode migration reads them from the pool after the
        request finishes. Pair with :meth:`release_held`."""
        self.hold_finished.add(rid)

    def release_held(self, rid: int) -> None:
        """Release the slot + pages of a finished held request (the
        migration shipped its pages, or the hold is abandoned)."""
        self.hold_finished.discard(rid)
        req = self.requests.get(rid)
        if (
            req is not None
            and req.status in TERMINAL_STATUSES
            and req.slot >= 0
            and req.pipeline_refs == 0
        ):
            self._release_slot(req)

    def adopt_prefilled(
        self,
        tokens: Sequence[int],
        prompt_len: int,
        gen: GenerationConfig,
        *,
        profile: Optional[ProfileInfo] = None,
        prompt_text: str = "",
        trace_id: Optional[int] = None,
    ) -> Optional[int]:
        """Admit an EXTERNALLY prefilled request straight into DECODING
        (cluster prefill→decode migration, serve/cluster/migration.py):
        ``tokens`` is prompt + the first sampled output token, and cache
        lines [0, prompt_len) are about to be filled by page uploads
        into the slot this method allocates. Returns the new request id,
        or None when no slot (or no pages) can be had right now — the
        caller keeps the request on its source replica and retries.
        All-or-nothing: a page-allocation failure rolls the slot back."""
        assert len(tokens) > prompt_len, "adopt needs the first output token"
        slot = next(
            (i for i, occ in enumerate(self.slots) if occ is None), None
        )
        if slot is None:
            return None
        if self._paged:
            for eng in self._engines():
                if not eng.pager.ensure(slot, prompt_len):
                    self._release_pages(slot)
                    return None
        rid = self._next_id
        self._next_id += 1
        req = Request(
            request_id=rid,
            prompt=prompt_text,
            tokens=[int(t) for t in tokens],
            prompt_len=int(prompt_len),
            gen=gen,
        )
        req.slot = slot
        req.status = RequestStatus.DECODING
        req.n_cached = int(prompt_len)
        req.n_sched = int(prompt_len)
        req.admit_seq = self._admit_counter
        self._admit_counter += 1
        if profile is not None:
            req.profile = profile
        if not req.profile.admit_time:
            req.profile.admit_time = time.perf_counter()
        req.profile.context_shards = getattr(self.engine, "cp_shards", 1)
        self.requests[rid] = req
        self.slots[slot] = rid
        self.stats.admitted += 1
        if trace_id is not None:
            self._trace_ids[rid] = int(trace_id)
        tr = self.tracer
        if tr.enabled:
            tr.event(
                "adopt", trace_id=self.trace_of(rid), rid=rid, slot=slot,
                prompt_len=int(prompt_len),
            )
        return rid

    def rollback_adopt(self, rid: int) -> None:
        """Undo :meth:`adopt_prefilled` before any step ran — the
        migration failed AFTER adoption (a page gather/upload raised),
        so the destination must release the slot + pages it granted and
        forget the request entirely: the source still holds the
        original, and a half-adopted ghost would leak its pages and
        double-count the admission."""
        req = self.requests.pop(rid)
        assert req.status is RequestStatus.DECODING, (
            f"rollback_adopt of request {rid} in state {req.status}"
        )
        assert req.pipeline_refs == 0 and req.n_cached == req.prompt_len, (
            "rollback_adopt after the adopted request already stepped"
        )
        if req.slot >= 0:
            if self._paged:
                self._release_pages(req.slot)
            self.slots[req.slot] = None
        self.stats.admitted -= 1

    # ------------------------------------------------------------------
    # paged-KV page management (serve/paging.py PageAllocator; one
    # allocator per engine — a SpecInfer LLM/SSM pair allocates
    # independently but the tables evolve in lockstep because slot
    # assignment and serving limits are shared)

    @property
    def _paged(self) -> bool:
        return getattr(self.engine, "paged", False)

    def _build_context(self):
        """For the engines' build logs, read when a program is traced:
        where the counters go, the step counter (-1 before the first
        request: nothing is scheduled) and whether a request is live —
        a build that begins then began inside a step."""
        live = bool(self.pending) or any(s is not None for s in self.slots)
        step = self._step_counter if live or self._step_counter else -1
        return self.stats, step, live

    def _engines(self):
        """Every engine whose cache this manager keeps in sync
        (SpecInferManager adds its SSMs)."""
        return [self.engine]

    def _prefix_caches(self):
        """Every prefix cache this manager maintains (SpecInferManager
        adds one radix tree per SSM pool)."""
        return [] if self.prefix_cache is None else [self.prefix_cache]

    def _cache_attach(self, slot: int, tokens: Sequence[int]) -> int:
        """Hook: admission-time prefix-cache attach. SpecInferManager
        overrides it to attach the SAME matched length on the LLM pool
        and every SSM pool (or none at all) — a prefix the engines do
        not jump past together would desync verification."""
        return self.prefix_cache.attach(slot, tokens)

    def _cache_insert(self, slot: int, tokens: Sequence[int],
                      valid: int) -> None:
        """Hook: publish a slot's blocks into every maintained radix
        tree (SpecInferManager inserts into the SSM trees too — their
        pools hold the same tokens' K/V at the same lines, paged
        independently)."""
        for cache in self._prefix_caches():
            cache.insert(slot, tokens, valid)

    def _mirror_dispatch(self, last, host_tokens, use_last, positions,
                         logits_idx, key, greedy, temperature, topp,
                         topk) -> None:
        """Hook: managers that keep secondary engines' caches in sync
        (SpecInfer SSM mirrors) dispatch the SAME pipelined mixed step
        there — identical token selection (the LLM's previous sampled
        tokens feed ``use_last`` rows), identical positions, so every
        cache advances in lockstep without a host round-trip. The base
        manager has no secondary engines: no-op."""

    def _ensure_pages(self, req: Request, num_lines: int) -> bool:
        """Cover cache lines [0, num_lines) for ``req`` on every engine.
        All-or-nothing per engine; a partial cross-engine success is
        resolved by the caller's preemption retry (``ensure`` is
        idempotent on the engines that already granted)."""
        for eng in self._engines():
            if not eng.pager.ensure(req.slot, num_lines):
                return False
        return True

    def _release_pages(self, slot: int):
        for eng in self._engines():
            eng.pager.release(slot)

    def _preempt(self, req: Request):
        """Evict an admitted request back to the front of the pending
        queue, reclaiming its pages everywhere. Its prefix is recomputed
        on re-admission (prompt + tokens generated so far re-prefill —
        vLLM-style recompute preemption), so generation continues
        exactly where it stopped. Only called with the pipeline drained
        (pipeline_refs == 0), so no in-flight dispatch can scribble on
        the reclaimed pages."""
        assert req.pipeline_refs == 0, "preempting a request with work in flight"
        self._release_pages(req.slot)
        self.slots[req.slot] = None
        req.slot = -1
        req.status = RequestStatus.PENDING
        req.n_cached = 0
        req.n_sched = 0
        req.inflight = 0
        self.pending.insert(0, req.request_id)
        self.stats.preemptions += 1
        tr = self.tracer
        if tr.enabled:
            tr.event("preempt", trace_id=self.trace_of(req.request_id),
                     rid=req.request_id)

    def _lines_needed(self, req: Request, chunk: Optional[int] = None) -> int:
        """Conservative cache-line bound the next step may touch."""
        if req.status is RequestStatus.PREFILLING:
            chunk = chunk or self.engine.serving.prefill_chunk
            return min(
                len(req.tokens),
                max(req.n_cached, req.n_sched) + chunk,
            )
        # decode: reads lines [0, len-1], writes len-1 (+ dispatch-ahead
        # steps in flight advance the write line without a host sync)
        return len(req.tokens) + req.inflight + 1

    def _reserve_active_pages(self, lines_fn=None):
        """Grow every active slot's page table to cover this step's
        reads/writes; on pool exhaustion, preempt the newest admission
        (reference eviction order) and retry. A single request that
        alone exceeds the pool can never be served — it fails with an
        ERROR status (surfaced in its GenerationResult) instead of
        crashing the scheduler and every healthy request with it."""
        if not self._paged:
            return
        lines_fn = lines_fn or self._lines_needed
        classes = self.engine.pager.classes
        with self.tracer.span("step.reserve"):
            while True:
                active = sorted(
                    (
                        self.requests[rid]
                        for rid in self.slots
                        if rid is not None
                        and self.requests[rid].status
                        in (RequestStatus.PREFILLING, RequestStatus.DECODING)
                    ),
                    key=lambda r: r.admit_seq,
                )
                if classes is not None:
                    # give back, in every class of page with a window,
                    # what this step's queries no longer see
                    # (``PageAllocator.trim``: the rule ``ensure`` frees
                    # by too), all slots first and under one span. Steps
                    # in flight keep the tables they were handed, and
                    # the device runs them before any step that writes
                    # a freed page again.
                    with self.tracer.span("step.trim"):
                        self.stats.window_pages_freed += sum(
                            self.engine.pager.trim(r.slot, lines_fn(r))
                            for r in active)
                for req in active:
                    if self._ensure_pages(req, lines_fn(req)):
                        continue
                    # free in-flight state before touching slot ownership;
                    # flushed completions may already release enough pages
                    self._flush_all()
                    if req.status not in (
                        RequestStatus.PREFILLING, RequestStatus.DECODING
                    ) or self._ensure_pages(req, lines_fn(req)):
                        break  # flush resolved it; re-derive the active set
                    victims = [
                        r for r in active
                        if r is not req
                        and r.status
                        in (RequestStatus.PREFILLING, RequestStatus.DECODING)
                    ]
                    if not victims:
                        self._fail_request(
                            req,
                            "KV page pool exhausted by this request alone — "
                            "raise ServingConfig.max_cached_tokens (or lower "
                            "max_sequence_length/page_size)",
                        )
                        break  # active set changed; re-derive
                    self._preempt(victims[-1])
                    break  # active set changed; re-derive
                else:
                    if classes is not None:
                        self.stats.note_page_classes(classes)
                    return

    def _attach_paging_metadata(self, bc: BatchConfig):
        """Record the page table + ragged lengths on the batch
        descriptor (the engine dispatches with its own authoritative
        table; this is telemetry/testing metadata)."""
        if not self._paged:
            return
        bc.page_table = self.engine.pager.table.copy()
        seq_lens = np.zeros((self.engine.num_slots,), np.int32)
        for rid in self.slots:
            if rid is None:
                continue
            req = self.requests[rid]
            if req.status is RequestStatus.PREFILLING:
                seq_lens[req.slot] = min(
                    len(req.tokens),
                    req.n_cached + self.engine.serving.prefill_chunk,
                )
            elif req.status is RequestStatus.DECODING:
                seq_lens[req.slot] = len(req.tokens)
        bc.seq_lens = seq_lens

    # ------------------------------------------------------------------
    # slot management

    def _admission_error(self, req: Request) -> Optional[str]:
        """A reason this request can NEVER be admitted under the
        configured limits, or None. Without this check such a request
        either live-locks ``generate()`` (``step()`` keeps returning
        True with the request parked in ``pending``) or eventually
        preempts every healthy request before dying."""
        sc = self.engine.serving
        need = len(req.tokens) + 1  # prompt lines + the first output's line
        if need > sc.cache_len + 1:
            return (
                f"prompt ({len(req.tokens)} tokens) exceeds the cache "
                f"capacity ({sc.cache_len} lines)"
            )
        if self._paged:
            # with kv_quant the max_cached_tokens budget is an HBM
            # budget that buys ~2x the pages, and under kv_shard=
            # "context" it is a PER-SHARD budget the striped layout
            # multiplies — in both cases the allocator's actual
            # capacity (checked below) is the authoritative bound, and
            # the raw token figure would wrongly reject servable prompts
            if (
                sc.max_cached_tokens is not None
                and sc.kv_quant is None
                and sc.kv_shard != "context"
                and need > sc.max_cached_tokens
            ):
                return (
                    f"prompt ({len(req.tokens)} tokens) can never fit the "
                    f"configured KV budget (max_cached_tokens="
                    f"{sc.max_cached_tokens})"
                )
            for eng in self._engines():
                cap = eng.pager.lines_capacity
                if need > cap:
                    return (
                        f"prompt ({len(req.tokens)} tokens) exceeds the "
                        f"KV page pool ({cap} tokens)"
                    )
                cp = getattr(eng, "cp_shards", 1)
                if cp > 1:
                    # context parallelism: admission goes PER SHARD —
                    # logical page j lives on shard j % n, so every
                    # shard must cover its striped share of the prompt
                    # out of its own budget (max_cached_tokens prices
                    # ONE shard; the allocator itself is clamped to the
                    # worst case so the budget is enforced here, the
                    # same split as the single-pool raw-token check)
                    budget = getattr(eng, "cp_budget_pages_per_shard",
                                     None)
                    need_per_shard = -(-eng.pager.pages_for(need) // cp)
                    if budget is not None and need_per_shard > budget:
                        return (
                            f"prompt ({len(req.tokens)} tokens) can "
                            f"never fit the per-shard KV budget: its "
                            f"striped share is {need_per_shard} pages/"
                            f"shard vs a budget of {budget} "
                            f"(max_cached_tokens="
                            f"{sc.max_cached_tokens} per shard × "
                            f"{cp} context shards) — raise the budget "
                            "or context_shards"
                        )
                    if not eng.pager.can_ever_fit(need):
                        per = eng.pager.pages_per_shard
                        return (
                            f"prompt ({len(req.tokens)} tokens) "
                            f"exceeds the per-shard page pool ({per} "
                            f"pages/shard × {cp} context shards)"
                        )
        return None

    def _admit_pending(self):
        for i, occupant in enumerate(self.slots):
            if occupant is not None:
                continue
            # fail-fast unservable heads instead of parking them forever
            while self.pending:
                head = self.requests[self.pending[0]]
                err = self._admission_error(head)
                if err is None:
                    break
                self.pending.pop(0)
                self._fail_request(head, err)
            if not self.pending:
                return
            rid = self.pending[0]
            req = self.requests[rid]
            req.slot = i
            # Prefix-cache hit path: splice cached prompt pages into the
            # (empty) slot table and jump prefill past them — the mixed/
            # sync steps then only chunk the uncached suffix. A rolled-
            # back admission releases the spliced references with the
            # slot, so retrying is clean.
            matched = 0
            host_before = self.stats.host_hit_tokens
            if self.prefix_cache is not None:
                matched = self._cache_attach(i, req.tokens)
            if self._paged and not self._ensure_pages(
                req,
                min(
                    len(req.tokens),
                    matched + self.engine.serving.prefill_chunk,
                ),
            ):
                # pool cannot take the first chunk: stop admitting (a
                # flush will free pages; the request stays queued) and
                # roll back any partial cross-engine grant
                self._release_pages(i)
                req.slot = -1
                return
            self.pending.pop(0)
            req.status = RequestStatus.PREFILLING
            req.n_cached = matched
            req.n_sched = matched
            req.inflight = 0
            req.pipeline_refs = 0
            req.admit_seq = self._admit_counter
            self._admit_counter += 1
            if not req.profile.admit_time:
                # the FIRST grant: a preempted request's re-admission
                # is recompute time, not queue wait
                req.profile.admit_time = time.perf_counter()
            req.profile.cached_prefix_len = matched
            req.profile.context_shards = getattr(self.engine, "cp_shards", 1)
            # tokens of this prefix that came back from the HOST tier
            # (the stats counter moved inside attach's re-admissions)
            req.profile.host_hit_tokens = (
                self.stats.host_hit_tokens - host_before
            )
            if self.prefix_cache is not None:
                if matched:
                    self.stats.prefix_hits += 1
                    self.stats.prefix_hit_tokens += matched
                else:
                    self.stats.prefix_misses += 1
            self.slots[i] = rid
            self.stats.admitted += 1
            tr = self.tracer
            if tr.enabled:
                tid = self.trace_of(rid)
                if self.prefix_cache is not None:
                    tr.event("prefix_lookup", trace_id=tid, rid=rid,
                             matched=matched)
                tr.event(
                    "admit", trace_id=tid, rid=rid, slot=i,
                    prompt_len=req.prompt_len, cached_prefix=matched,
                )

    def _active(self, status: RequestStatus) -> List[Request]:
        out = []
        for rid in self.slots:
            if rid is None:
                continue
            r = self.requests[rid]
            if r.status is status:
                out.append(r)
        return out

    def _release_slot(self, req: Request):
        """Return the request's slot (and pages) to the free pool.
        Callers must guarantee no in-flight dispatch still references
        the slot (pipeline_refs == 0)."""
        if req.slot < 0:
            return
        if self._paged:
            self._release_pages(req.slot)
        self.slots[req.slot] = None
        req.slot = -1

    def _finish(self, req: Request, error: Optional[str] = None):
        req.status = RequestStatus.ERROR if error else RequestStatus.COMPLETED
        req.error = error
        req.profile.finish_time = time.perf_counter()
        tr = self.tracer
        if tr.enabled:
            tr.event(
                "terminal", trace_id=self.trace_of(req.request_id),
                rid=req.request_id, status=req.status.value,
                error=(error or "")[:200],
            )
        if error and self.flight_recorder is not None:
            # terminal request errors are a flight-recorder trigger
            # (obs/flight_recorder.py): dump this lane's recent ring
            self.flight_recorder.dump(
                self.tracer.lane, "request_error",
                step=self._step_counter,
                extra={"rid": req.request_id, "error": error[:500]},
            )
        if (
            self.prefix_cache is not None
            and error is None
            and req.slot >= 0
            and self.prefix_cache.policy == "complete"
        ):
            # Publish the finished sequence's blocks (prompt + generated
            # — the next conversation turn extends this transcript).
            # Only lines written on device are valid: the final sampled
            # token's K/V never was (it would have been the next step's
            # input), so the insertable prefix ends one short.
            self._cache_insert(req.slot, req.tokens, len(req.tokens) - 1)
        # With dispatches still in flight for this slot, defer the
        # release to the flush that drains the last of them: those
        # dispatches keep writing (garbage) K/V through the page table
        # they were launched with, so reallocating the pages or the slot
        # now would corrupt whoever received them. Held requests
        # (cluster migration sources) keep slot + pages until
        # :meth:`release_held`.
        if (
            req.slot >= 0
            and req.pipeline_refs == 0
            and req.request_id not in self.hold_finished
        ):
            self._release_slot(req)
        if self.output_file and error is None:
            self._write_output_record(req)

    def _fail_request(self, req: Request, reason: str):
        self.stats.failed += 1
        self._log.warning("request %d failed: %s", req.request_id, reason)
        if req.request_id in self.pending:
            self.pending.remove(req.request_id)
        self._finish(req, error=reason)

    def _write_output_record(self, req: Request):
        """Append one finished request's telemetry — the format mirrors
        the reference's output-file writer (request_manager.cc:417-440:
        ``[Profile] guid(%d) llm_decoding_steps(%d) start(%.1lf)
        finish(%.1lf) latency(%.1lf)`` then the token ids)."""
        p = req.profile
        latency_us = (p.finish_time - p.start_time) * 1e6
        text = (
            self.tokenizer.decode(req.output_tokens)
            if self.tokenizer is not None
            else ""
        )
        with open(self.output_file, "a") as f:
            f.write(
                f"[Profile] guid({req.request_id}) "
                f"llm_decoding_steps({p.llm_decoding_steps}) "
                f"latency({latency_us:.1f})\n"
            )
            f.write(
                f"guid({req.request_id}) tokens("
                + " ".join(str(t) for t in req.tokens)
                + f") output({text})\n"
            )

    # ------------------------------------------------------------------
    # batch building (reference prepare_next_batch, request_manager.cc:350)

    def _fill_prefill_row(self, bc: BatchConfig, req: Request, chunk: int):
        off = req.n_cached
        toks = req.tokens[off : off + chunk]
        n = len(toks)
        bc.tokens[req.slot, :n] = toks
        bc.positions[req.slot, :n] = np.arange(off, off + n)
        bc.active[req.slot] = True
        bc.logits_idx[req.slot] = n - 1
        if bc.qlens is not None:
            bc.qlens[req.slot] = n
        if bc.prefill_offsets is not None:
            bc.prefill_offsets[req.slot] = off

    def _prepare_batch(self) -> Optional[BatchConfig]:
        """Build one blocking mixed prefill+decode batch (the sync
        path). Decoding slots always contribute their one pending token,
        so decode never stalls behind a long prompt's prefill (no
        head-of-line blocking); the chunk is 1 when nobody is
        prefilling."""
        prefilling = self._active(RequestStatus.PREFILLING)
        decoding = self._active(RequestStatus.DECODING)
        if not prefilling and not decoding:
            return None
        sc = self.engine.serving
        chunk = sc.prefill_chunk if prefilling else 1
        bc = BatchConfig.empty(self.engine.num_slots, chunk, self.engine.scratch_pos)
        bc.qlens = np.zeros((self.engine.num_slots,), np.int32)
        bc.prefill_offsets = np.zeros((self.engine.num_slots,), np.int32)
        for req in prefilling:
            self._fill_prefill_row(bc, req, chunk)
        for req in decoding:
            bc.tokens[req.slot, 0] = req.tokens[-1]
            bc.positions[req.slot, 0] = len(req.tokens) - 1
            bc.active[req.slot] = True
            bc.logits_idx[req.slot] = 0
            bc.qlens[req.slot] = 1
        self._attach_paging_metadata(bc)
        return bc

    # ------------------------------------------------------------------
    # sampling glue

    def _decode_head_params(self, reqs: Sequence[Request]):
        """Per-slot decode-head arrays for ``reqs`` (greedy/temperature/
        top-k/top-p; top-p >= 1 and top-k <= 0 disable the filters)."""
        R = self.engine.num_slots
        greedy = np.ones((R,), bool)
        temp = np.ones((R,), np.float32)
        topp = np.full((R,), 2.0, np.float32)  # disabled
        topk = np.zeros((R,), np.int32)        # disabled
        for req in reqs:
            greedy[req.slot] = not req.gen.do_sample
            temp[req.slot] = req.gen.temperature
            topp[req.slot] = req.gen.topp if req.gen.do_sample else 2.0
            topk[req.slot] = req.gen.topk if req.gen.do_sample else 0
        return greedy, temp, topp, topk

    def _sample(self, logits) -> np.ndarray:
        """Sample one token per slot from (R, V) logits using each slot's
        GenerationConfig (mixed greedy/sampling in one program). The
        head is mode-specialized host-side (serve/sampling.py): a
        greedy-only batch — the common decode case — skips the (R, V)
        sorts entirely, bitwise-identically."""
        greedy, temp, topp, topk = self._decode_head_params(
            [self.requests[r] for r in self.slots if r is not None]
        )
        mode, cap = choose_sample_mode(
            greedy, topp, topk, self.engine.cfg.vocab_size
        )
        self._key, sub = jax.random.split(self._key)
        toks = sample_tokens(
            logits,
            sub,
            greedy=jnp.asarray(greedy, dtype=jnp.bool_),
            temperature=jnp.asarray(temp, dtype=jnp.float32),
            topp=jnp.asarray(topp, dtype=jnp.float32),
            topk_arr=jnp.asarray(topk, dtype=jnp.int32),
            mode=mode,
            topk_cap=cap,
        )
        # the host-side decode head is its own dispatched program — the
        # figure the one-program step that samples on the device beats
        self.engine.count_dispatch("host_sample")
        with self.tracer.span("step.flush_wait"):
            # ffcheck: disable=FF107 -- blocking sync-scheduler decode head: this path trades latency for simplicity by design (the pipelined path samples on device)
            return np.asarray(jax.device_get(toks))

    def _append_token(self, req: Request, token: int):
        if len(req.tokens) == req.prompt_len and not req.profile.first_token_time:
            # the request's first generated token, as the host observes
            # it (TTFT the way a streaming client would measure it)
            req.profile.first_token_time = time.perf_counter()
            tr = self.tracer
            if tr.enabled:
                tr.event("first_token",
                         trace_id=self.trace_of(req.request_id),
                         rid=req.request_id)
        req.tokens.append(int(token))
        gen_len = len(req.tokens) - req.prompt_len
        eos = self.eos_token_id
        max_total = self.engine.serving.max_sequence_length
        stops = set(req.gen.stop_token_ids)
        if eos is not None:
            stops.add(eos)
        if (
            (int(token) in stops)
            or gen_len >= req.gen.max_new_tokens
            or len(req.tokens) >= max_total
        ):
            self._finish(req)

    # ------------------------------------------------------------------
    # incremental decoding loop (reference generate_incr_decoding, :2292)

    def _run_batch(self, bc: BatchConfig):
        """Hook: run one prepared batch through the engine(s).
        SpecInferManager overrides this to keep the SSM cache in sync."""
        return self.engine.run(bc)

    # ------------------------------------------------------------------
    # dispatch-ahead pipeline (reference request_manager.cc:2310)

    def _sched_exhausted(self, req: Request) -> bool:
        """Everything this request will ever need is already dispatched
        — scheduling more rows would only compute garbage (its
        completion lands at a pending flush)."""
        gen_dispatched = len(req.tokens) - req.prompt_len + req.inflight
        return (
            gen_dispatched >= req.gen.max_new_tokens
            or len(req.tokens) + req.inflight
            >= self.engine.serving.max_sequence_length
        )

    @staticmethod
    def _stamp_prefill_dispatched(finals: List[Request]) -> None:
        """``ProfileInfo.prefill_dispatched_time`` for the rows whose
        final prompt chunk the step just dispatched carried (and that
        have no first token yet: a preempted request's recompute moves
        the stamp, so it is always the dispatch whose sample became the
        first token)."""
        if finals:
            now = time.perf_counter()
            for req in finals:
                req.profile.prefill_dispatched_time = now

    def _note_attn_steps(self, first, count, chunk: int) -> None:
        """Count a pipelined step's attention grid (a paged engine's:
        ``SchedulerStats.note_attn_steps``) from what the step is
        handed: each row's first position and its real queries."""
        eng = self.engine
        if eng.paged:
            from .kernels import narrow_query_extent  # Pallas: not at import

            sc = eng.serving
            narrow = narrow_query_extent(chunk)
            classes = eng.pager.classes
            if classes is not None:  # one call of each class's layers
                for a in classes.values():
                    self.stats.note_attn_steps(
                        first, count, sc.page_size, a.pages_per_slot,
                        narrow, a.window or 0)
                return
            self.stats.note_attn_steps(
                first, count, sc.page_size, sc.pages_per_slot, narrow,
                getattr(eng.cfg, "sliding_window", None) or 0,
            )

    def _dispatch_decode(self, decoding: List[Request]):
        """Dispatch one fused decode step WITHOUT waiting for the
        previous one: decode rows that sampled in the previous dispatch
        take their input token from the on-device sampled tokens; rows
        entering the pipeline take it from host state. Positions advance
        deterministically, so no host sync is needed."""
        with self.tracer.span("step.build"):
            R = self.engine.num_slots
            scratch = self.engine.scratch_pos
            host_tokens = np.zeros((R, 1), np.int32)
            use_last = np.zeros((R,), bool)
            positions = np.full((R, 1), scratch, np.int32)
            greedy, temp, topp, topk = self._decode_head_params(decoding)
            snapshot = []
            last = self._inflight[-1][0] if self._inflight else None
            for req in decoding:
                s = req.slot
                positions[s, 0] = len(req.tokens) - 1 + req.inflight
                if s in self._prev_dispatch_slots and last is not None:
                    use_last[s] = True
                else:
                    host_tokens[s, 0] = req.tokens[-1]
                req.inflight += 1
                req.pipeline_refs += 1
                snapshot.append((req.request_id, s, 1, True))
            if last is None:
                last = jnp.zeros((R,), jnp.int32)
            self._key, sub = jax.random.split(self._key)
        with self.tracer.span("step.dispatch"):
            toks = self.engine.run_decode(
                last, host_tokens, use_last, positions, sub, greedy, temp,
                topp, topk,
            )
            self._mirror_dispatch(
                last, host_tokens, use_last, positions,
                np.zeros((R,), np.int32), sub, greedy, temp, topp, topk,
            )
        self._inflight.append((toks, snapshot, self.engine.step_fetch,
                               self.engine.step_tile))
        self._prev_dispatch_slots = {s for _, s, _, _ in snapshot}
        self._step_counter += 1
        self.stats.record_step(
            "decode", active_slots=len(decoding), num_slots=R,
            decode_tokens=len(decoding),
            decode_context=sum(int(positions[r.slot, 0]) + 1 for r in decoding),
        )
        self.stats.note_head(self.engine.step_head[0])
        real = positions[:, 0] != scratch
        if self._slot_state:
            self.stats.note_rows(positions[:, 0], real,
                                 getattr(self.engine.cfg, "dense_len", None))
        self._note_attn_steps(positions[:, 0], real, 1)
        if self._latent_layers:
            self.stats.latent_lines += int(real.sum()) * self._latent_layers
        if self._recurrent_layers:
            self.stats.recurrent_updates += (
                int(real.sum()) * self._recurrent_layers)
        tr = self.tracer
        if tr.enabled:
            tr.event("decode_step", rows=len(decoding))
        self._maybe_log_stats()

    def _dispatch_mixed(self, prefilling: List[Request],
                        decoding: List[Request]):
        """Dispatch one pipelined MIXED step: every decode row's single
        token plus chunked prefill under the per-step token budget, in
        ONE (R, mixed_chunk) ragged dispatch through the shared step
        (paged layouts go through ``ragged_paged_attention`` via the
        per-row query lengths — padding columns sit at the scratch
        position). Prefill rows whose final chunk is in this dispatch
        transition to DECODING immediately: their sampled token is on
        device, so the next iteration schedules them as decode rows fed
        by device feedback — an admission never costs a pipeline
        drain."""
        with self.tracer.span("step.build"):
            eng = self.engine
            sc = eng.serving
            R = eng.num_slots
            C = sc.mixed_chunk
            bc = BatchConfig.empty(R, C, eng.scratch_pos)
            bc.qlens = np.zeros((R,), np.int32)
            bc.prefill_offsets = np.zeros((R,), np.int32)
            use_last = np.zeros((R,), bool)
            snapshot = []
            sampled_slots = set()
            last = self._inflight[-1][0] if self._inflight else None
            greedy, temp, topp, topk = self._decode_head_params(
                list(decoding) + list(prefilling)
            )
            for req in decoding:
                s = req.slot
                bc.positions[s, 0] = len(req.tokens) - 1 + req.inflight
                if s in self._prev_dispatch_slots and last is not None:
                    use_last[s] = True
                else:
                    bc.tokens[s, 0] = req.tokens[-1]
                bc.logits_idx[s] = 0
                bc.active[s] = True
                bc.qlens[s] = 1
                req.inflight += 1
                req.pipeline_refs += 1
                snapshot.append((req.request_id, s, 1, True))
                sampled_slots.add(s)
            spent = 0
            finals = []  # rows whose sample of this step is their first token
            tr = self.tracer
            rows = sorted(prefilling, key=lambda r: r.admit_seq)
            asked = [max(0, min(C, len(r.tokens) - r.n_sched)) for r in rows]
            # a step a few tokens over a rung of the engine's ladder
            # gives them up and runs at the rung (trim_to_rung)
            chunks = trim_to_rung(
                eng.pack_ladder(C), R, len(decoding), asked,
                [r.n_sched + n >= len(r.tokens) for r, n in zip(rows, asked)])
            trimmed = sum(asked) - sum(chunks)
            for req, n in zip(rows, chunks):
                if n <= 0:
                    continue
                s = req.slot
                off = req.n_sched
                bc.tokens[s, :n] = req.tokens[off : off + n]
                bc.positions[s, :n] = np.arange(off, off + n)
                bc.logits_idx[s] = n - 1
                bc.active[s] = True
                bc.qlens[s] = n
                bc.prefill_offsets[s] = off
                final = off + n >= len(req.tokens)
                req.n_sched += n
                req.pipeline_refs += 1
                spent += n
                if final:
                    # prompt fully dispatched: this step samples the first
                    # output token on device — decode from the next step on
                    req.status = RequestStatus.DECODING
                    req.inflight += 1
                    sampled_slots.add(s)
                    if not req.profile.first_token_time:
                        finals.append(req)
                    if (
                        self.prefix_cache is not None
                        and self.prefix_cache.policy == "prefill"
                    ):
                        # every prompt line's write is dispatched — publish
                        # the prompt now so concurrent same-prefix
                        # admissions hit before this request even finishes
                        self._cache_insert(
                            s, req.tokens[: req.prompt_len], req.prompt_len
                        )
                snapshot.append((req.request_id, s, n, final))
                if tr.enabled:
                    tr.event(
                        "prefill_chunk",
                        trace_id=self.trace_of(req.request_id),
                        rid=req.request_id, n=n, offset=off, final=final,
                    )
            if last is None:
                last = jnp.zeros((R,), jnp.int32)
            self._key, sub = jax.random.split(self._key)
        with self.tracer.span("step.dispatch"):
            toks = eng.run_mixed(
                last, bc.tokens, use_last, bc.positions, bc.logits_idx,
                sub, greedy, temp, topp, topk,
            )
            self._mirror_dispatch(
                last, bc.tokens, use_last, bc.positions, bc.logits_idx,
                sub, greedy, temp, topp, topk,
            )
        self._stamp_prefill_dispatched(finals)
        self._inflight.append((toks, snapshot, eng.step_fetch, eng.step_tile))
        self._prev_dispatch_slots = sampled_slots
        self._step_counter += 1
        self.stats.record_step(
            "mixed", active_slots=int(bc.active.sum()), num_slots=R,
            prefill_tokens=spent, decode_tokens=len(decoding),
            budget=C * max(1, len(prefilling)),
            decode_context=sum(
                int(bc.positions[r.slot, 0]) + 1 for r in decoding),
        )
        self.stats.note_head(eng.step_head[0])
        if self._slot_state:
            self.stats.note_rows(bc.positions[:, 0], bc.qlens,
                                 getattr(eng.cfg, "dense_len", None))
        self._note_attn_steps(bc.positions[:, 0], bc.qlens, C)
        real = int(bc.qlens.sum())
        self.stats.note_step_tokens(real, eng.pack_width(real, C), trimmed)
        self.stats.latent_lines += real * self._latent_layers
        self.stats.recurrent_updates += real * self._recurrent_layers
        if tr.enabled:
            tr.event(
                "mixed_step", prefill_tokens=spent,
                decode_rows=len(decoding), trimmed=trimmed,
            )
        self._maybe_log_stats()

    def _flush_one(self):
        """Fetch the oldest in-flight step's tokens and do the deferred
        host bookkeeping: advance each row's committed-line count, and
        for sampling rows append the token (EOS/length checks). A
        request finished by an earlier flush skips the bookkeeping but
        still drains its pipeline refs — its slot/pages are released at
        the flush that drains the last reference."""
        with self.tracer.span("step.flush"):
            toks, snapshot, fetch, tile = self._inflight.pop(0)
            with self.tracer.span("step.flush_wait"):
                # ffcheck: disable=FF107 -- the pipeline flush IS the designed sync point: it drains steps the device already finished, dispatch_ahead steps behind
                toks = np.asarray(jax.device_get(toks if fetch is None else fetch))
            self.stats.flushes += 1
            if fetch is not None:  # the step's counters ride behind its tokens
                toks, counts = self.engine.split_fetch(toks)
                self.stats.note_expert_counts(
                    counts["moe_counts"], tile,
                    counts.get("moe_zero_pairs", ()),
                    counts.get("moe_routed_pairs", ()))
            tr = self.tracer
            if tr.enabled:
                tr.event("flush", entries=len(snapshot))
            for rid, slot, ntoks, samples in snapshot:
                req = self.requests.get(rid)
                if req is None:
                    continue
                req.pipeline_refs = max(0, req.pipeline_refs - 1)
                if samples:
                    req.inflight = max(0, req.inflight - 1)
                alive = (
                    req.status
                    in (RequestStatus.PREFILLING, RequestStatus.DECODING)
                    and req.slot == slot
                )
                if alive:
                    req.n_cached += ntoks
                    if samples:
                        req.profile.llm_decoding_steps += 1
                        self._append_token(req, toks[slot])
                if (
                    req.status in TERMINAL_STATUSES
                    and req.slot == slot
                    and req.pipeline_refs == 0
                    and req.request_id not in self.hold_finished
                ):
                    self._release_slot(req)
            # the flush just blocked on device_get — every async spill
            # copy enqueued before it has landed; convert the handles
            # to host buffers and release their device memory
            for cache in self._prefix_caches():
                cache.harvest()

    def _flush_all(self):
        if self._inflight:
            self.stats.pipeline_drains += 1
        while self._inflight:
            self._flush_one()
        self._prev_dispatch_slots = set()

    def drain(self):
        """Flush every in-flight dispatch: appends all outstanding
        tokens and releases slots/pages held by finished requests whose
        tail dispatches were still in the pipeline."""
        self._flush_all()

    def _trim_pipeline(self):
        depth = max(1, self.engine.serving.dispatch_ahead)
        while len(self._inflight) >= depth:
            self._flush_one()

    def _slots_reclaimable(self) -> bool:
        """Some slot is held by a request that only needs flushes to
        leave: already terminal (zombie refs in flight) or with its
        whole generation budget dispatched."""
        for rid in self.slots:
            if rid is None:
                continue
            req = self.requests[rid]
            if req.status in TERMINAL_STATUSES:
                # held slots (cluster migration sources) only leave via
                # release_held — flushing cannot reclaim them
                if rid not in self.hold_finished:
                    return True
                continue
            if (
                req.status is RequestStatus.DECODING
                and self._sched_exhausted(req)
            ):
                return True
        return False

    def _reclaim_slots_for_admission(self):
        """Under saturation (pending queue non-empty, no free slot),
        flush ahead of the dispatch_ahead cadence to reclaim slots held
        by finished/fully-dispatched requests. Flushing drains steps the
        device has already computed (it runs up to ``dispatch_ahead``
        ahead), so this trades a little pipeline depth for slot
        occupancy — the right trade whenever admissions are waiting;
        without it a completion holds its slot for up to dispatch_ahead
        extra iterations and effective concurrency sags."""
        if not self.pending or any(s is None for s in self.slots):
            return
        while (
            self._inflight
            and self.pending
            and not any(s is None for s in self.slots)
            and self._slots_reclaimable()
        ):
            self._flush_one()
        self._admit_pending()

    def _maybe_log_stats(self):
        # context-parallel telemetry, refreshed per dispatched step so
        # bench-style stat swaps (rm.stats = SchedulerStats()) keep the
        # gauges: shard degree, ring hops a sequence-sharded mesh pays
        # per attention read, and the striping balance of the pool.
        cp = getattr(self.engine, "cp_shards", 1)
        if cp > 1 and self._paged:
            self.stats.cp_shards = cp
            self.stats.ring_steps += cp - 1
            self.stats.shard_balance = self.engine.pager.shard_balance()
        if self._step_counter % 200 == 0:
            self._log.debug("%s", self.stats.report())

    # ------------------------------------------------------------------

    def step(self) -> bool:
        """One scheduling step. Returns False when no work remains.

        Fast managers run everything through the dispatch-ahead
        pipeline: pure-decode iterations through the fused C==1 step,
        and — with ``continuous_batching`` — iterations with PREFILLING
        slots through the fused mixed step, so admissions and chunk
        progression never drain the pipeline. The blocking sync path
        remains for SpecInfer/triage managers, for the flush-on-admit
        baseline scheduler, and as the idle drain."""
        with self.tracer.span("step.admit"):
            self._admit_pending()
            if self.supports_fast_decode:
                self._reclaim_slots_for_admission()
        sc = self.engine.serving
        if self.supports_fast_decode:
            prefilling = self._active(RequestStatus.PREFILLING)
            decoding = self._active(RequestStatus.DECODING)
            if decoding and not prefilling:
                self._reserve_active_pages()
                return self._step_pipelined(mixed=False)
            if sc.continuous_batching and (prefilling or decoding):
                self._reserve_active_pages(
                    lambda r: self._lines_needed(r, sc.mixed_chunk)
                )
                return self._step_pipelined(mixed=True)
        # Sync path (SpecInfer/triage managers; prefill under the
        # flush-on-admit baseline; idle drain): blocking host round trip.
        return self._step_sync()

    def _step_pipelined(self, mixed: bool) -> bool:
        # page reservation may have preempted or failed requests —
        # re-derive the schedulable set
        prefilling = self._active(RequestStatus.PREFILLING) if mixed else []
        decoding = [
            r for r in self._active(RequestStatus.DECODING)
            if not self._sched_exhausted(r)
        ]
        if prefilling:
            self._dispatch_mixed(prefilling, decoding)
        elif decoding:
            self._dispatch_decode(decoding)
        elif self._inflight:
            # every row is fully dispatched: make flush progress so the
            # pending completions land
            self._flush_one()
            return True
        else:
            return bool(self.pending)
        self._trim_pipeline()
        return True

    def _step_sync(self) -> bool:
        self._flush_all()
        self._reserve_active_pages()
        with self.tracer.span("step.build"):
            bc = self._prepare_batch()
        if bc is None:
            return bool(self.pending)
        prefilling = self._active(RequestStatus.PREFILLING)
        decoding = self._active(RequestStatus.DECODING)
        # rows whose final prompt chunk rides this batch: its sample is
        # their first token
        finals = [
            r for r in prefilling
            if r.n_cached + int(bc.qlens[r.slot]) >= len(r.tokens)
            and not r.profile.first_token_time
        ]
        if self.supports_fused_sampling:
            # ONE dispatched program per sync step (step + on-device
            # decode head) instead of two — the (R, V) logits never
            # reach the host. Same single key split per step as the
            # two-dispatch path below, so generations are bitwise
            # identical.
            with self.tracer.span("step.build"):
                greedy, temp, topp, topk = self._decode_head_params(
                    [self.requests[r] for r in self.slots if r is not None]
                )
                self._key, sub = jax.random.split(self._key)
            with self.tracer.span("step.dispatch"):
                toks = self.engine.run_sampled(
                    bc, sub, greedy, temp, topp, topk
                )
            self._stamp_prefill_dispatched(finals)
            # the sync scheduler's blocking fetch: the same wait as the
            # pipelined flush's, with no flush round it
            with self.tracer.span("step.flush_wait"):
                # ffcheck: disable=FF107 -- blocking sync scheduler: one fetch per step by design
                sampled = np.asarray(jax.device_get(toks))
        else:
            with self.tracer.span("step.dispatch"):
                logits = self._run_batch(bc)
            self._stamp_prefill_dispatched(finals)
            sampled = self._sample(logits)
        for req in decoding:
            req.n_cached += 1
            req.n_sched = req.n_cached
            req.profile.llm_decoding_steps += 1
            self._append_token(req, sampled[req.slot])
        for req in prefilling:
            n = int(bc.logits_idx[req.slot]) + 1  # tokens cached this chunk
            req.n_cached += n
            req.n_sched = req.n_cached
            if req.n_cached >= len(req.tokens):
                # prompt fully cached: first output token sampled now
                req.status = RequestStatus.DECODING
                if (
                    self.prefix_cache is not None
                    and self.prefix_cache.policy == "prefill"
                ):
                    self._cache_insert(
                        req.slot, req.tokens[: req.prompt_len],
                        req.prompt_len,
                    )
                req.profile.llm_decoding_steps += 1
                self._append_token(req, sampled[req.slot])
        self._step_counter += 1
        self.stats.record_step(
            "sync",
            active_slots=len(prefilling) + len(decoding),
            num_slots=self.engine.num_slots,
            prefill_tokens=int(
                sum(bc.qlens[r.slot] for r in prefilling)
            ) if prefilling else 0,
            decode_tokens=len(decoding),
            decode_context=sum(
                int(bc.positions[r.slot, 0]) + 1 for r in decoding),
        )
        tr = self.tracer
        if tr.enabled:
            tr.event(
                "sync_step", prefill_rows=len(prefilling),
                decode_rows=len(decoding),
            )
        self._maybe_log_stats()
        return True

    # ------------------------------------------------------------------
    # blocking + streaming frontends

    def result(self, rid: int) -> GenerationResult:
        """Build the GenerationResult for a (terminal or in-flight)
        request."""
        req = self.requests[rid]
        out = req.output_tokens
        text = (
            self.tokenizer.decode(out) if self.tokenizer is not None else ""
        )
        return GenerationResult(
            request_id=rid,
            prompt=req.prompt,
            input_tokens=req.tokens[: req.prompt_len],
            output_tokens=list(out),
            output_text=text,
            profile=req.profile,
            error=req.error,
        )

    def generate(
        self,
        prompts: Union[str, Sequence[Union[str, Sequence[int]]]],
        gen: Optional[GenerationConfig] = None,
        max_new_tokens: Optional[int] = None,
    ) -> List[GenerationResult]:
        """Blocking generate over a batch of prompts (reference
        ``FFModel::generate`` → ``generate_incr_decoding``)."""
        if isinstance(prompts, str):
            prompts = [prompts]
        gen = gen or GenerationConfig()
        if max_new_tokens is not None:
            gen = dataclasses.replace(gen, max_new_tokens=max_new_tokens)
        rids = [self.register_request(p, gen) for p in prompts]
        while any(
            self.requests[r].status not in TERMINAL_STATUSES for r in rids
        ):
            if not self.step():
                break
        # the tail of the pipeline may still hold finished requests'
        # dispatches (and their slots/pages)
        self.drain()
        return [self.result(rid) for rid in rids]

    def generate_stream(
        self,
        prompts: Union[str, Sequence[Union[str, Sequence[int]]]],
        gen: Optional[GenerationConfig] = None,
        max_new_tokens: Optional[int] = None,
    ) -> Iterator[StreamEvent]:
        """Streaming generate: yields a :class:`StreamEvent` per token
        the moment the pipeline drains it to the host (tokens arrive up
        to ``dispatch_ahead`` steps behind the device), then one
        terminal event per request (``done=True``; ``error`` set if the
        request failed). Interleaves arbitrarily across requests."""
        if isinstance(prompts, str):
            prompts = [prompts]
        gen = gen or GenerationConfig()
        if max_new_tokens is not None:
            gen = dataclasses.replace(gen, max_new_tokens=max_new_tokens)
        rids = [self.register_request(p, gen) for p in prompts]
        sent = {r: 0 for r in rids}
        finished: set = set()

        def drain_events():
            for r in rids:
                if r in finished:
                    continue
                req = self.requests[r]
                out = req.output_tokens
                while sent[r] < len(out):
                    tok = out[sent[r]]
                    sent[r] += 1
                    yield StreamEvent(r, int(tok))
                if req.status in TERMINAL_STATUSES:
                    finished.add(r)
                    yield StreamEvent(r, None, done=True, error=req.error)

        while len(finished) < len(rids):
            progressed = self.step()
            yield from drain_events()
            if not progressed and len(finished) < len(rids):
                self.drain()
                yield from drain_events()
                if len(finished) < len(rids):
                    break  # nothing schedulable remains — avoid spinning
        self.drain()
        yield from drain_events()
