"""Paged KV cache — refcounted page allocator + per-slot page tables.

TPU-native port of the Ragged Paged Attention memory layout
(PAPERS.md, arxiv 2604.15464; vLLM's PagedAttention ancestry): instead
of a dense per-slot cache of ``slots × (max_len+1)`` lines, K/V live in
a pool of fixed-size token **pages** and each request slot owns a
**page table** mapping logical pages (line // page_size) to physical
pages. HBM cost is then proportional to pages actually allocated — live
tokens rounded up to the page size — not to the worst-case sequence
length, which is what lets serving run the reference's 64 request slots
on one chip (VERDICT.md round 5, missing #3).

HBM accounting: a page costs what the family's own pool arrays hold of
it (``init_paged_kv_cache``'s shapes; ``InferenceEngine.
kv_bytes_per_line`` reads them, never heads x head size). For a K/V
pool that is ``2 · page_size · KV · ceil(dk / pack) ·
itemsize(cache_dtype)`` bytes per layer (K and V; ``pack`` is the
codes-per-element factor of the storage layout — 1 for fp and int8,
2 for int4's packed nibbles); for a LATENT pool (models/deepseek_v3.py:
one compressed line a token instead of K and V a head) ``page_size ·
(kv_lora_rank + qk_rope_head_dim) · itemsize``, 1152 B a token and
layer in bf16 at the published widths where 8 K/V heads of 128 are
4096. ``ServingConfig.max_cached_tokens``
prices the pool in the pack=1 full-precision units — it is an HBM
budget expressed as full-precision tokens. With
``ServingConfig.kv_quant`` (serve/kv_quant.py) pages store quantized
codes plus two per-page f32 scale rows (``8·KV`` bytes — under 1% of
a page at real head dims), so the SAME budget buys ~2x (int8) or ~4x
(int4, two codes per byte along dk) the physical pages
(``kv_quant.quantized_pool_pages`` converts; the engine sizes this
allocator with the converted count). The allocator itself is
dtype-blind — it hands out page INDICES; every invariant below holds
identically over bf16, f32 and quantized pools of either pack
(asserted by the randomized property test in tests/test_paged_kv.py,
which runs the same sweep over int8 and packed-int4 engines' pools).

Pages are **reference counted** so the automatic prefix cache
(serve/prefix_cache.py) can keep a finished request's prompt pages
alive and splice them into later requests' tables: a physical page may
be referenced by several slot tables at once (a shared prompt prefix)
plus one reference held by the prefix-cache radix tree. A page returns
to the free list exactly when its refcount drains to zero — cached-but-
idle pages (refcount 1, held only by the tree) are reclaimed through
``reclaim_cb`` before an allocation ever fails, so the cache can never
cause an admission preemption that a cold pool would not. (With the
hierarchical host tier — ``ServingConfig.host_cache_bytes`` — that
reclaim SPILLS the page's content to host RAM instead of discarding
it; the page index still returns to the free list, and the tree's
host-resident nodes hold no allocator reference until re-admitted.)

The allocator is host-side state owned by the :class:`InferenceEngine`
(one per engine — a SpecInfer LLM/SSM pair allocates independently
because their pools differ in layer count and budget). The
RequestManager drives it on admit/evict/completion; the device only
ever sees the resulting ``(slots, pages_per_slot)`` int32 table shipped
with each step.

Physical page ``num_pages`` (one past the pool) is the shared
**scratch page**: unallocated table entries point at it, so padding
tokens' K/V writes and gathers through unallocated entries land on a
real buffer that no mask ever exposes (the paged analog of the dense
layout's per-slot scratch row, models/transformer.py init_kv_cache).

Context parallelism (``ServingConfig.kv_shard="context"``): with
``cp_shards`` > 1 the pool is partitioned into per-shard slices —
shard ``d`` owns physical pages ``[d*pages_per_shard,
(d+1)*pages_per_shard)`` (the contiguous row range that shards over
the mesh ``seq`` axis) — and LOGICAL page ``j`` of every request is
STRIPED to shard ``j % cp_shards``, so one long request's pages (and
its decode-time reads) spread evenly over the shards instead of
filling one shard's slice while the others idle. All allocation is
per-shard: ``ensure`` covers each shard's share of the growth
all-or-nothing, ``cow``/``take_free_page`` draw from the logical
page's owning shard, and ``check_no_leaks`` additionally audits the
striping invariant (every mapped logical page lives on its owning
shard) and the per-shard free-list partition. ``cp_shards=1``
(default) is byte-for-byte the single-pool allocator.

Page CLASSES (``PageClasses``): a family whose layers do not all keep
the same lines declares more than one class of page (``page_classes``
beside its ``PAGE_POOLS``; models/smallthinker.py: full-attention
layers keep a request's every line, sliding-window layers the newest
``window`` of them). The engine then keeps, a class, a pool over that
class's layers only, an allocator and a table. The allocator of a class
with a ``window`` has one operation more, :meth:`PageAllocator.trim`: it
frees a slot's pages whose every line lies behind the window of the
slot's next query, and its table ROLLS: entry 0 is the slot's first
live logical page (``first_page``), so a table of ``ceil((window +
step_lines) / page_size) + 1`` entries serves any context length. A
page is freed on the host for steps NOT YET DISPATCHED: a step in
flight was handed its own copy of the table and the device runs steps
in order, so a freed page is written again only after every step that
may read it. One class is the allocator as it was.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Optional, Sequence

import numpy as np


def window_table_pages(window: int, step_lines: int, page_size: int) -> int:
    """Entries of a slot's table in a class of page with a ``window``,
    where a step covers at most ``step_lines`` new lines: the pages
    from the one that holds the first query's oldest visible line to
    the one that holds the step's last line, at their worst alignment
    (4096 + 128 lines in pages of 128: 34)."""
    return -(-(int(window) + max(int(step_lines), 2)) // int(page_size)) + 1


class PageAllocator:
    """Refcounted free-list allocator over a physical KV page pool.

    Invariants (asserted, tested in tests/test_paged_kv.py):
      * ``refcount[p]`` equals the number of live references to physical
        page ``p``: one per slot-table entry pointing at it, plus any
        external references (the prefix cache's radix tree) the caller
        reports to :meth:`check_no_leaks`;
      * a page is on the free list **iff** its refcount is zero
        (refcount-zero-iff-free) — there is no leaked and no aliased
        state in between;
      * ``ensure`` either covers the requested lines fully or changes
        nothing (no partial allocation to roll back);
      * releasing never double-frees: a refcount decrement below zero is
        an assertion failure, and ``release`` of an already-clean slot
        is a no-op.
    """

    #: the allocators by class of an engine's pager: None, this is the
    #: only one (:class:`PageClasses` has the dict)
    classes = None

    def __init__(self, num_pages: int, pages_per_slot: int, num_slots: int,
                 page_size: int, cp_shards: int = 1,
                 window: Optional[int] = None, step_lines: int = 0):
        if window is not None:
            if cp_shards != 1:
                raise ValueError(
                    "a class of page with a window is not striped over "
                    "context shards")
            if pages_per_slot < window_table_pages(window, step_lines,
                                                   page_size):
                raise ValueError(
                    f"a window of {window} lines and steps of {step_lines} "
                    f"need a table of {window_table_pages(window, step_lines, page_size)}"
                    f" pages a slot (got {pages_per_slot})")
        if num_pages < pages_per_slot and cp_shards == 1:
            raise ValueError(
                f"page pool ({num_pages} pages) smaller than one request's "
                f"worst case ({pages_per_slot} pages) — no request could "
                "ever run to max_sequence_length"
            )
        if cp_shards < 1:
            raise ValueError(f"cp_shards must be >= 1 (got {cp_shards})")
        if num_pages % cp_shards:
            raise ValueError(
                f"context-parallel pool needs num_pages ({num_pages}) "
                f"divisible by cp_shards ({cp_shards}) — the engine sizes "
                "per-shard slices of equal page count"
            )
        self.num_pages = int(num_pages)
        self.page_size = int(page_size)
        self.pages_per_slot = int(pages_per_slot)
        self.cp_shards = int(cp_shards)
        self.pages_per_shard = self.num_pages // self.cp_shards
        # a class with a window (module docstring): lines a query sees,
        # the most new lines a step covers, each slot's first live
        # logical page (its table's entry 0), pages trimmed so far
        self.window = None if window is None else int(window)
        self.step_lines = int(step_lines)
        self.first_page = np.zeros((num_slots,), np.int64)
        self.trimmed = 0
        if cp_shards > 1 and -(-int(pages_per_slot) // cp_shards) > (
            self.pages_per_shard
        ):
            raise ValueError(
                f"context-parallel pool ({num_pages} pages over "
                f"{cp_shards} shards, {self.pages_per_shard}/shard) "
                f"smaller than one request's worst case "
                f"({pages_per_slot} striped logical pages = "
                f"{-(-pages_per_slot // cp_shards)}/shard) — no request "
                "could ever run to max_sequence_length"
            )
        self.scratch_page = int(num_pages)  # pool row num_pages is scratch
        # per-shard free lists (one list when cp_shards == 1 — the
        # single-pool allocator, unchanged); pop() takes from the end:
        # keep ascending ids there
        self._free_by_shard: List[List[int]] = [
            list(range((d + 1) * self.pages_per_shard - 1,
                       d * self.pages_per_shard - 1, -1))
            for d in range(self.cp_shards)
        ]
        self.refcount = np.zeros((num_pages,), np.int32)
        self.table = np.full(
            (num_slots, pages_per_slot), self.scratch_page, np.int32
        )
        # bumped on every table mutation — the engine caches the device
        # copy of the table against it, so steady-state decode (table
        # unchanged across steps) re-ships nothing
        self.version = 0
        # Last-resort page supplier: called with the shortfall (pages)
        # when the free list cannot cover a request; expected to free
        # reclaimable pages (the prefix cache evicts idle cached pages)
        # and return how many it freed. Under context parallelism the
        # call carries ``shard=`` so reclaim frees pages on the shard
        # that is actually short. None = allocation just fails.
        self.reclaim_cb: Optional[Callable[[int], int]] = None

    # ------------------------------------------------------------------
    # context-parallel partition (no-ops collapsing to shard 0 when
    # cp_shards == 1)

    def shard_of_logical(self, logical: int) -> int:
        """Owning shard of a LOGICAL page index — striped so consecutive
        logical pages land on consecutive shards (decode reads and long
        prompts load-balance)."""
        return int(logical) % self.cp_shards

    def shard_of_page(self, page: int) -> int:
        """Owning shard of a PHYSICAL page (contiguous row slices)."""
        return int(page) // self.pages_per_shard

    def shard_page_need(self, num_lines: int) -> List[int]:
        """Pages each shard must supply to cover lines [0, num_lines)
        under the striped ownership."""
        need = self.pages_for(num_lines)
        base, rem = divmod(need, self.cp_shards)
        return [base + (1 if d < rem else 0) for d in range(self.cp_shards)]

    def can_ever_fit(self, num_lines: int) -> bool:
        """Whether a request needing ``num_lines`` cache lines could ever
        be admitted into an EMPTY pool — the per-shard admission bound
        (each shard must cover its striped share)."""
        return all(
            n <= self.pages_per_shard
            for n in self.shard_page_need(num_lines)
        )

    def free_pages_by_shard(self) -> List[int]:
        return [len(f) for f in self._free_by_shard]

    def used_pages_by_shard(self) -> List[int]:
        return [
            self.pages_per_shard - len(f) for f in self._free_by_shard
        ]

    def shard_balance(self) -> float:
        """Occupancy balance gauge: min/max used pages across shards
        (1.0 = perfectly balanced or idle) — the striping telemetry
        SchedulerStats surfaces."""
        used = self.used_pages_by_shard()
        hi = max(used)
        return 1.0 if hi == 0 else min(used) / hi

    # ------------------------------------------------------------------

    @property
    def free_pages(self) -> int:
        return sum(len(f) for f in self._free_by_shard)

    @property
    def used_pages(self) -> int:
        return self.num_pages - self.free_pages

    @property
    def lines_capacity(self) -> int:
        """The longest context an empty pool could hold in one slot: a
        class with a window holds any length in its rolling table."""
        if self.window is not None:
            return np.iinfo(np.int32).max
        return self.num_pages * self.page_size

    def tables(self) -> np.ndarray:
        """What a step is handed: the table (:meth:`PageClasses.tables`
        hands a dict of them)."""
        return self.table

    def slot_pages(self, slot: int) -> int:
        """Physical pages currently mapped by ``slot``'s table."""
        return int((self.table[slot] != self.scratch_page).sum())

    def pages_for(self, num_lines: int) -> int:
        """Logical pages needed to cover cache lines [0, num_lines)."""
        return -(-int(num_lines) // self.page_size)

    # ------------------------------------------------------------------
    # reference counting (shared pages: prefix-cache splicing)

    def acquire(self, page: int) -> None:
        """Add one reference to ``page`` (a slot table or the prefix
        cache now also points at it). The page must not be on the free
        list — either it already has references, or it was just popped
        via :meth:`take_free_page`."""
        assert 0 <= page < self.num_pages, f"acquire of page {page}"
        self.refcount[page] += 1

    def release_ref(self, page: int) -> bool:
        """Drop one reference; when the count drains to zero the page
        returns to its owning shard's free list. Returns True iff the
        page was freed. Decrementing a zero refcount is a double-free
        (asserted)."""
        assert self.refcount[page] > 0, f"double free of physical page {page}"
        self.refcount[page] -= 1
        if self.refcount[page] == 0:
            self._free_by_shard[self.shard_of_page(page)].append(int(page))
            return True
        return False

    def _reclaim(self, shortfall: int, shard: int = 0) -> None:
        """Ask the reclaim hook (prefix-cache LRU eviction/spill) to free
        at least ``shortfall`` pages — on ``shard`` under context
        parallelism (freeing another shard's pages cannot satisfy a
        striped allocation). Best-effort: the free list after the call
        is the only truth."""
        if shortfall > 0 and self.reclaim_cb is not None:
            if self.cp_shards > 1:
                self.reclaim_cb(shortfall, shard=shard)
            else:
                self.reclaim_cb(shortfall)

    def take_free_page(self, shard: int = 0) -> Optional[int]:
        """Pop one page off ``shard``'s free list (evicting idle cached
        pages first if it is dry), with refcount still ZERO — the caller
        must follow up with :meth:`acquire`/:meth:`splice` before
        control returns to the scheduler. None when nothing can be
        freed. Callers allocating for a specific LOGICAL page pass
        ``shard_of_logical(logical)`` so the striping invariant holds."""
        free = self._free_by_shard[shard]
        if not free:
            self._reclaim(1, shard)
        if not free:
            return None
        return free.pop()

    def claim_free_page(self, shard: int = 0) -> Optional[int]:
        """:meth:`take_free_page` + the caller's own single reference
        (refcount 1) in one step — the prefix cache's page-adoption
        idiom (host-tier re-admits and standby tree imports take a
        page the TREE owns, never a slot). None when nothing can be
        freed."""
        page = self.take_free_page(shard)
        if page is not None:
            self.refcount[page] = 1
        return page

    # ------------------------------------------------------------------

    def trim(self, slot: int, num_lines: int) -> int:
        """A class with a window: release the logical pages of ``slot``
        that no query of its next step sees, the step that covers the
        lines up to ``num_lines``, and roll the table so that entry 0
        is the first page kept. A step covers at most ``step_lines`` new
        lines, so its first query is at or after ``num_lines -
        step_lines``, and a query at ``i`` sees lines ``i - window + 1
        .. i``: every page wholly before that goes. THE one rule:
        :meth:`ensure` trims by it before it covers the lines, so the
        scheduler (which trims all its slots first, under one span) and
        a caller that only ensures free the same pages. Returns the
        pages freed; never moves backwards, and a class without a
        window frees nothing."""
        if self.window is None:
            return 0
        first_query = int(num_lines) - self.step_lines
        keep_from = max(0, first_query - self.window + 1) // self.page_size
        drop = min(int(keep_from - self.first_page[slot]), self.pages_per_slot)
        if drop <= 0:
            return 0
        row = self.table[slot]
        freed = 0
        for page in row[:drop]:
            if int(page) != self.scratch_page:
                freed += int(self.release_ref(int(page)))
        row[:-drop] = row[drop:]
        row[-drop:] = self.scratch_page
        # pages behind the window that were never mapped roll by too
        self.first_page[slot] = keep_from
        self.trimmed += freed
        self.version += 1
        return freed

    def ensure(self, slot: int, num_lines: int) -> bool:
        """Grow ``slot``'s table to cover cache lines [0, num_lines).

        Contract: already-covered prefixes are kept (idempotent —
        calling again with the same or a smaller bound changes nothing);
        growth pages are freshly allocated with refcount 1 owned by this
        slot — each logical page from its OWNING shard's free list
        (striped, ``shard_of_logical``). When the free lists cannot
        cover the growth even after ``reclaim_cb`` eviction, returns
        False with NOTHING allocated — the caller preempts a victim and
        retries. Returns True once the lines are covered.

        A class with a window first trims (:meth:`trim`) behind the
        window of the step these lines are for, and covers the lines
        from its first live page on: a caller asks for ONE step's lines
        at a time."""
        need = min(self.pages_for(num_lines), self.pages_per_slot)
        if self.window is not None:
            # what the trim leaves is the table's width at most
            # (``window_table_pages``)
            self.trim(slot, num_lines)
            need = max(
                self.pages_for(num_lines) - int(self.first_page[slot]), 0)
        row = self.table[slot]
        have = int((row[:need] != self.scratch_page).sum())
        if need - have <= 0:
            return True
        # all-or-nothing across shards: reclaim each short shard first,
        # allocate only once every shard can cover its striped share
        grow_by_shard = [0] * self.cp_shards
        for j in range(have, need):
            grow_by_shard[self.shard_of_logical(j)] += 1
        for d, grow in enumerate(grow_by_shard):
            short = grow - len(self._free_by_shard[d])
            if short > 0:
                self._reclaim(short, d)
        if any(
            grow > len(self._free_by_shard[d])
            for d, grow in enumerate(grow_by_shard)
        ):
            return False
        for j in range(have, need):
            assert row[j] == self.scratch_page, (
                f"slot {slot} page table has a hole before logical page {j}"
            )
            page = self._free_by_shard[self.shard_of_logical(j)].pop()
            assert self.refcount[page] == 0, (
                f"free list held referenced page {page}"
            )
            self.refcount[page] = 1
            row[j] = page
        self.version += 1
        return True

    def splice(self, slot: int, pages: Sequence[int]) -> None:
        """Map ``slot``'s leading logical pages to ``pages`` (a cached
        prompt prefix), acquiring one reference per entry. The slot's
        table must be empty (fresh admission) — splicing is only ever
        the FIRST thing that happens to a slot's table, before
        :meth:`ensure` grows the uncached suffix behind it. Cached
        blocks are logical-page-aligned from the root, so under context
        parallelism a spliced page is on its logical index's owning
        shard by construction (asserted)."""
        row = self.table[slot]
        assert int((row != self.scratch_page).sum()) == 0, (
            f"splice into non-empty slot {slot}"
        )
        assert len(pages) <= self.pages_per_slot
        for j, page in enumerate(pages):
            if self.cp_shards > 1:
                assert self.shard_of_page(int(page)) == (
                    self.shard_of_logical(j)
                ), (
                    f"splice breaks striping: logical page {j} (shard "
                    f"{self.shard_of_logical(j)}) mapped to physical "
                    f"{int(page)} (shard {self.shard_of_page(int(page))})"
                )
            self.acquire(int(page))
            row[j] = int(page)
        if len(pages):
            self.version += 1

    def cow(self, slot: int, logical: int) -> Optional[int]:
        """Copy-on-write bookkeeping for ``slot``'s logical page
        ``logical``: allocate a private page (refcount 1, from the
        logical page's owning shard), swap it into the table, and drop
        this slot's reference on the shared page. Returns the new
        physical page (the caller copies the page CONTENT device-side,
        engine.copy_page), or None when no page could be allocated even
        after reclaim — the table is unchanged."""
        row = self.table[slot]
        old = int(row[logical])
        assert old != self.scratch_page, "COW of an unmapped logical page"
        fresh = self.take_free_page(self.shard_of_logical(logical))
        if fresh is None:
            return None
        self.refcount[fresh] = 1
        row[logical] = fresh
        self.release_ref(old)
        self.version += 1
        return fresh

    def release(self, slot: int) -> int:
        """Drop ``slot``'s reference on every page its table maps and
        reset the row to scratch. Shared pages (spliced prompt prefixes,
        cached pages) survive under their remaining references; only
        pages whose refcount drains to zero return to the free list.
        Returns the number of pages actually freed. Releasing an
        already-clean slot is a no-op (never a double-free)."""
        row = self.table[slot]
        freed = 0
        changed = False
        for j in range(self.pages_per_slot):
            page = int(row[j])
            if page == self.scratch_page:
                continue
            freed += int(self.release_ref(page))
            row[j] = self.scratch_page
            changed = True
        if self.first_page[slot]:
            self.first_page[slot] = 0
            changed = True
        if changed:
            self.version += 1
        return freed

    @property
    def untrimmed_pages(self) -> int:
        """What the slots would hold had :meth:`trim` freed nothing:
        the pages in use and, a slot, those its table has rolled past
        (pages of this class are private to a slot)."""
        return self.used_pages + int(self.first_page.sum())

    def check_no_leaks(
        self, external: Optional[Dict[int, int]] = None
    ) -> None:
        """Full refcount audit — the no-leak/no-double-free invariant
        the tests assert after (and, in the property test, DURING) a
        workload: every physical page's refcount equals its slot-table
        reference count plus ``external`` references (the prefix cache's
        ``page_refs()``), and a page is free iff that count is zero."""
        external = external or {}
        counts = np.zeros((self.num_pages,), np.int64)
        for slot, row in enumerate(self.table):
            for j, page in enumerate(row):
                if int(page) == self.scratch_page:
                    continue
                counts[int(page)] += 1
                if self.cp_shards > 1:
                    # striping invariant: every mapped logical page
                    # lives on its owning shard
                    assert self.shard_of_page(int(page)) == (
                        self.shard_of_logical(j)
                    ), (
                        f"slot {slot} logical page {j} (shard "
                        f"{self.shard_of_logical(j)}) maps to physical "
                        f"{int(page)} on shard "
                        f"{self.shard_of_page(int(page))}"
                    )
        for page, n in external.items():
            counts[int(page)] += int(n)
        all_free = [p for f in self._free_by_shard for p in f]
        free = set(all_free)
        assert len(free) == len(all_free), "free list holds duplicates"
        for d, flist in enumerate(self._free_by_shard):
            for p in flist:
                assert self.shard_of_page(p) == d, (
                    f"page {p} (shard {self.shard_of_page(p)}) on shard "
                    f"{d}'s free list"
                )
        for page in range(self.num_pages):
            rc = int(self.refcount[page])
            assert rc == int(counts[page]), (
                f"page {page}: refcount {rc} != {int(counts[page])} live "
                "references (leak or double-free)"
            )
            assert (rc == 0) == (page in free), (
                f"page {page}: refcount {rc} but "
                f"{'on' if page in free else 'off'} the free list"
            )


class PageClasses:
    """The pager of an engine whose family declares several classes of
    page (module docstring): one :class:`PageAllocator` a class, each
    over its own pool, behind the operations the scheduler and the
    benchmark's probe drive a single allocator with. A grant covers the
    lines in EVERY class or the caller preempts and retries
    (``ensure`` is idempotent on the classes that granted); a release
    gives back every class's pages. Pages are counted over all classes
    (``num_pages``, ``used_pages``): they differ in bytes by the layers
    their pool holds (``InferenceEngine.kv_bytes_per_line``)."""

    cp_shards = 1
    reclaim_cb = None  # no prefix cache over several classes yet

    def __init__(self, classes: Dict[str, PageAllocator]):
        self.classes = dict(classes)
        sizes = {a.page_size for a in self.classes.values()}
        assert len(sizes) == 1, f"classes differ in page size: {sizes}"
        (self.page_size,) = sizes

    def _all(self):
        return self.classes.values()

    @property
    def num_pages(self) -> int:
        return sum(a.num_pages for a in self._all())

    @property
    def free_pages(self) -> int:
        return sum(a.free_pages for a in self._all())

    @property
    def used_pages(self) -> int:
        return sum(a.used_pages for a in self._all())

    @property
    def version(self) -> int:
        return sum(a.version for a in self._all())

    @property
    def table(self) -> np.ndarray:
        """The first class's table (telemetry: ``BatchConfig``)."""
        return next(iter(self._all())).table

    @property
    def lines_capacity(self) -> int:
        """The longest context an empty pool could hold: a class with
        a window holds any length in one slot's table."""
        return min(a.lines_capacity for a in self._all())

    def tables(self) -> Dict[str, np.ndarray]:
        """What a step is handed: each class's table under its name
        and, for a class with a window, ``<name>_start`` (slots,): the
        position of the first line of each slot's entry 0. COPIES: a
        rolling table is shifted in place by the next trim, and a step
        in flight keeps the table it was handed (a host-to-device
        transfer may read, or on the CPU alias, the array it was given
        after the call returns)."""
        out = {}
        for name, a in self.classes.items():
            out[name] = a.table.copy()
            if a.window is not None:
                out[name + "_start"] = (
                    a.first_page * a.page_size).astype(np.int32)
        return out

    def pages_for(self, num_lines: int) -> int:
        return -(-int(num_lines) // self.page_size)

    def slot_pages(self, slot: int) -> int:
        return sum(a.slot_pages(slot) for a in self._all())

    def shard_balance(self) -> float:
        return 1.0

    def ensure(self, slot: int, num_lines: int) -> bool:
        return all(a.ensure(slot, num_lines) for a in self._all())

    def trim(self, slot: int, num_lines: int) -> int:
        return sum(a.trim(slot, num_lines) for a in self._all())

    def release(self, slot: int) -> int:
        return sum(a.release(slot) for a in self._all())

    def check_no_leaks(self, external=None) -> None:
        assert not external, "pages of several classes are never shared"
        for a in self._all():
            a.check_no_leaks()
