"""Slot pools over a family decode cache: contiguous and paged.

Both pools expose one bookkeeping surface to the engine — ``admit`` /
``evict`` / ``read`` / ``entries`` / ``has_free`` — over the uniform cache
contract in ``models/cache_ops.py``.

:class:`SlotPool` (DESIGN.md §7) is the contiguous baseline: the pool *is* a
batched decode cache — ``init_cache(capacity, max_seq)`` — so every slot
owns a full ``max_seq`` sequence stripe and capacity is bounded by the
longest admissible request, whether or not anything that long is in flight.

:class:`PagedSlotPool` (DESIGN.md §8) removes that waste: sequence storage
is a shared pool of ``n_blocks`` pages of ``block`` tokens, and each slot
holds a *block table* mapping logical page index → physical page. Admission
reserves just the prompt's pages; decode grows a slot one page at a time
(``ensure_page``), and eviction returns pages to the free list. Capacity is
bounded by **tokens actually in flight**, so a page budget far below
``capacity · max_seq`` still serves mixed-length traffic — the engine turns
:class:`PoolExhausted` at decode time into preemption + re-queue instead of
a crash.

With a :class:`~repro.serving.prefix.PrefixCache` attached (DESIGN.md §12)
pages become *shared*: the pool keeps a per-page **refcount ledger**, a
matching request's block table attaches to already-resident pages
(``admit_prefix``), and the first write into a page with refcount > 1 — or
into a page the prefix tree retains — goes through copy-on-write
(``paged_copy_page`` + table rewrite), never in place. Eviction turns into
decref: a page is zeroed and freed only at refcount 0 *and* unretained;
retained refcount-0 pages stay warm for future hits until the LRU
reclaimer (``PrefixCache.reclaim``) surrenders them under page pressure.

Invariants (asserted here, fuzzed in tests/test_paging.py):

* a slot is either free or holds exactly one live request; a page is either
  free, referenced by ≥ 1 block table / staging pin, retained warm by the
  prefix tree, or the trash block (never handed out);
* admission fails loudly (typed :class:`PoolExhausted`, carrying the
  requesting ``uid`` and a ``reason``) when no slot/pages are free or when
  ``prompt + max_new`` cannot fit ``max_seq`` — KV families write at
  absolute positions, so overflow must be impossible;
* no page is freed at refcount > 0, no write lands in an unwritable page
  without a preceding copy, and refcounts never go negative (typed
  :class:`~repro.errors.PrefixCacheInvariantError` on violation);
* eviction returns the lowest-index-first reusable slot/pages and zeroes
  their state, so pool contents stay a pure function of the live requests
  plus the retained prefix set.
"""
from __future__ import annotations

import heapq
from dataclasses import dataclass, field
from typing import Any

import jax.numpy as jnp
import numpy as np

from repro.errors import ConfigError, PrefixCacheInvariantError
from repro.models import cache_ops
from repro.models.cache_ops import slot_evict, slot_insert, slot_read

from .prefix import PrefixCache, PrefixMatch
from .queue import Request

__all__ = ["SlotPool", "PagedSlotPool", "SlotEntry", "PoolExhausted"]


class PoolExhausted(RuntimeError):
    """A capacity refusal: no free slot, no free page, or a request that can
    never fit the pool. Typed so the engine can distinguish backpressure
    (preempt / re-queue / wait) from genuine errors.

    Refusals carry attribution as data, so backpressure under refcounted
    eviction is actionable without parsing the message: ``uid`` is the
    request the refusal blocks (``None`` when no request is attributable),
    ``reason`` is ``"admission"`` (prompt pages at admit time) or
    ``"decode"`` (page growth for a live slot), and page-pressure refusals
    also carry the shortfall — ``pages_needed`` vs ``pages_free`` at
    refusal time (both ``None`` for refusals that involve no page
    accounting, e.g. ``max_seq`` overflow or a full slot list). The engine
    surfaces the events in ``run()`` stats under ``"backpressure"``."""

    def __init__(self, message: str, *, pages_needed: int | None = None,
                 pages_free: int | None = None, uid: str | None = None,
                 reason: str = "admission"):
        super().__init__(message)
        self.pages_needed = pages_needed
        self.pages_free = pages_free
        self.uid = uid
        self.reason = reason


@dataclass
class SlotEntry:
    """Host-side bookkeeping for one live request in a slot."""
    request: Request
    admitted_at: float
    admit_step: int
    admit_index: int = 0    # monotone admission counter (preemption order)
    generated: list = field(default_factory=list)   # sampled ids, host ints
    key: Any = None                                 # per-request PRNG chain
    #: Prompt tokens already committed to the chunked-prefill staging cache
    #: (DESIGN.md §10). Created at prefill *start* — before pool admission —
    #: so the step scheduler can resume a partial prefill across engine
    #: steps and preemption can requeue the request knowing exactly what to
    #: discard. Equals ``prompt_len`` from admission onward; one-shot
    #: prefill sets it in a single jump.
    prefill_offset: int = 0

    @property
    def n_generated(self) -> int:
        return len(self.generated)

    @property
    def next_write_pos(self) -> int:
        """Cache position the *next* decode step writes for this slot: the
        prefill filled ``[0, prompt_len)`` and each decode step since has
        appended one token (the first sampled token comes from the prefill
        logits, so it is written by the first decode step)."""
        return self.request.prompt_len + self.n_generated - 1


class SlotPool:
    """Contiguous slot bookkeeping + the pooled device cache.

    ``pool.cache`` is the live device pytree; the engine reassigns it after
    every (donating) decode step, and admission/eviction rebind it through
    the pure ``cache_ops`` scatters.
    """

    def __init__(self, model, capacity: int, max_seq: int, *,
                 cache: Any = None):
        if capacity < 1:
            raise ConfigError("slot pool needs capacity ≥ 1")
        self.capacity = capacity
        self.max_seq = max_seq
        self._model = model
        self.cache = model.init_cache(capacity, max_seq) if cache is None \
            else cache
        self._free: list[int] = list(range(capacity))
        heapq.heapify(self._free)
        self.entries: dict[int, SlotEntry] = {}

    # ------------------------------------------------------------- queries

    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def has_free(self) -> bool:
        return bool(self._free)

    @property
    def active_slots(self) -> list[int]:
        return sorted(self.entries)

    def __len__(self) -> int:
        return len(self.entries)

    # ------------------------------------------------------- admit / evict

    def check_fits(self, req: Request) -> None:
        """Raise :class:`PoolExhausted` if ``req`` can *never* fit this
        pool (as opposed to transiently not fitting right now). The single
        source of the fit rule: admission calls it as the backstop and the
        engine calls it up front at ``run()`` entry."""
        need = req.prompt_len + req.max_new_tokens
        if need > self.max_seq:
            raise PoolExhausted(
                f"request {req.uid!r} needs {need} cache positions "
                f"(prompt {req.prompt_len} + max_new {req.max_new_tokens}) "
                f"but the pool holds max_seq={self.max_seq}",
                uid=req.uid)

    def admit(self, entry: SlotEntry, single_cache: Any) -> int:
        """Insert a prefilled B=1 cache into the lowest free slot."""
        req = entry.request
        if not self._free:
            raise PoolExhausted("slot pool is full", uid=req.uid)
        self.check_fits(req)
        slot = heapq.heappop(self._free)
        assert slot not in self.entries, "free-list/entries desync"
        self.cache = slot_insert(self.cache, single_cache, slot)
        self.entries[slot] = entry
        return slot

    def evict(self, slot: int) -> SlotEntry:
        """Free ``slot``, zeroing its device state; returns its entry."""
        entry = self.entries.pop(slot)
        self.cache = slot_evict(self.cache, slot)
        heapq.heappush(self._free, slot)
        return entry

    def read(self, slot: int) -> Any:
        """The slot's state as a B=1 cache (pool sequence extents)."""
        if slot not in self.entries:
            raise KeyError(f"slot {slot} is not live")
        return slot_read(self.cache, slot)

    # ------------------------------------------------------------- tokens

    def positions(self) -> np.ndarray:
        """Per-slot device positions, pulled to host (testing/debug)."""
        return np.asarray(self.cache.pos)


class PagedSlotPool:
    """Paged slot bookkeeping: shared block pool + per-slot block tables.

    ``pool.cache`` is the paged device pytree (``cache_ops.paged_init``
    layout); ``pool.tables`` is the host-side ``(capacity, max_blocks)``
    int32 block-table array handed to the paged decode step each call
    (-1 = unallocated). Page allocation is host-driven — the free lists are
    plain heaps, so admit/evict/grow decisions never synchronize with the
    device — while the actual cache edits go through the pure
    ``cache_ops.paged_*`` scatters.
    """

    @staticmethod
    def plan(capacity: int, max_seq: int, block: int,
             n_blocks: int | None = None) -> tuple[int, int, int]:
        """The (block, max_blocks, n_blocks) this pool will derive from the
        requested geometry — the one place the derivation lives. The engine
        shapes its compiled paged decode step from the same call, so the
        device layout and the host bookkeeping can never disagree.

        A page longer than max_seq just pads every gather view (the dense
        sequence extent is ``max_blocks * block`` ≥ max_seq): clamp, don't
        pay. ``n_blocks`` defaults to no oversubscription.
        """
        if capacity < 1:
            raise ConfigError("slot pool needs capacity ≥ 1")
        if block < 1:
            raise ConfigError("page size must be ≥ 1 token")
        block = min(block, max_seq)
        max_blocks = -(-max_seq // block)
        n_blocks = capacity * max_blocks if n_blocks is None else n_blocks
        if n_blocks < 1:
            raise ConfigError("paged pool needs a page budget ≥ 1")
        return block, max_blocks, n_blocks

    def __init__(self, model, capacity: int, max_seq: int, *,
                 block: int = 64, n_blocks: int | None = None,
                 cache: Any = None):
        self.capacity = capacity
        self.max_seq = max_seq
        self.block, self.max_blocks, self.n_blocks = self.plan(
            capacity, max_seq, block, n_blocks)
        block = self.block
        self._model = model
        self.cache = cache if cache is not None else cache_ops.paged_init(
            model.init_cache, capacity, self.n_blocks, block)
        self.tables = np.full((capacity, self.max_blocks), -1, np.int32)
        self._free: list[int] = list(range(capacity))
        heapq.heapify(self._free)
        self._free_pages: list[int] = list(range(self.n_blocks))
        heapq.heapify(self._free_pages)
        self.entries: dict[int, SlotEntry] = {}
        self.peak_pages = 0
        #: Per-page reference ledger: block-table references + staging pins.
        #: Without a prefix cache attached every page is simply rc 1 while
        #: owned and rc 0 when free — the PR 4 behaviour, unchanged.
        self.refcount = np.zeros(self.n_blocks, np.int64)
        #: Pages the prefix tree keeps warm (never zeroed/freed while here).
        self.retained: set[int] = set()
        #: The attached PrefixCache (engine wires it); owns identity + LRU.
        self.prefix: PrefixCache | None = None
        self.n_cow = 0
        self.n_reclaimed = 0

    # ------------------------------------------------------------- queries

    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def has_free(self) -> bool:
        return bool(self._free)

    @property
    def active_slots(self) -> list[int]:
        return sorted(self.entries)

    @property
    def free_pages(self) -> int:
        return len(self._free_pages)

    @property
    def pages_in_use(self) -> int:
        """Pages not on the free list — includes retained warm pages (they
        hold real memory) as well as live references."""
        return self.n_blocks - len(self._free_pages)

    @property
    def pages_live(self) -> int:
        """Pages referenced by at least one block table or staging pin.
        Drains to 0; ``pages_in_use - pages_live`` is the warm prefix set."""
        return int((self.refcount > 0).sum())

    def _reclaimable(self) -> int:
        """Retained warm pages the LRU reclaimer could surrender now."""
        return sum(1 for p in self.retained if self.refcount[p] == 0)

    @property
    def available_pages(self) -> int:
        """Free pages plus reclaimable warm pages — the admission/growth
        capacity check counts both, so a full warm cache never refuses
        work it could serve by shrinking itself."""
        return len(self._free_pages) + self._reclaimable()

    def pages_for(self, n_tokens: int) -> int:
        """Pages needed to hold ``n_tokens`` sequence positions."""
        return -(-max(n_tokens, 0) // self.block)

    def _growth_pending(self) -> int:
        """Live slots that will still request at least one more page before
        they can finish (full-length need exceeds their allocation)."""
        n = 0
        for slot, entry in self.entries.items():
            req = entry.request
            allocated = int((self.tables[slot] >= 0).sum())
            if self.pages_for(req.prompt_len + req.max_new_tokens) > allocated:
                n += 1
        return n

    def can_admit(self, req: Request, *,
                  match: PrefixMatch | None = None) -> bool:
        """Slot free and enough pages for the prompt *plus the first decode
        write* (admitting with exactly the prompt's pages would preempt
        itself on the next step whenever ``prompt_len % block == 0``),
        *plus one headroom page per still-growing live slot* — without
        headroom a tight budget admits the queue head, grows an older slot,
        preempts the head again, and burns a full B=1 prefill per ping-pong
        cycle; fully-allocated slots claim none, so a budget with no growth
        in flight fills every slot. Reclaimable warm pages count as
        capacity.

        ``match`` is the staging prefill's prefix-cache plan: its shared
        pages are already resident and claim nothing new, and a pinned CoW
        source whose sole reference is the staging pin is *credited back*
        — admission copies it and releases the pin, so it turns
        reclaimable before the first decode write needs a page. Admission
        itself (:meth:`admit_prefix`) still takes its fresh pages with the
        pin held, so that draw is checked against uncredited capacity."""
        if not self._free:
            return False
        shared = cow_credit = 0
        if match is not None:
            shared = len(match.shared)
            if (match.cow_src is not None
                    and self.refcount[match.cow_src] == 1):
                cow_credit = 1
        avail = self.available_pages
        return (self.pages_for(req.prompt_len) - shared <= avail
                and self.pages_for(req.prompt_len + 1) - shared - cow_credit
                + self._growth_pending() <= avail)

    def __len__(self) -> int:
        return len(self.entries)

    # ------------------------------------------------------- admit / evict

    def _take_pages(self, n: int, *, uid: str | None = None,
                    reason: str = "admission") -> list[int]:
        """Pop ``n`` fresh pages (refcount 1), reclaiming LRU warm prefix
        pages on shortfall; typed refusal with attribution otherwise."""
        if n > self.available_pages:
            raise PoolExhausted(
                f"need {n} pages but only {self.available_pages} of "
                f"{self.n_blocks} are free or reclaimable",
                pages_needed=n, pages_free=self.available_pages,
                uid=uid, reason=reason)
        if n > len(self._free_pages) and self.prefix is not None:
            ids = self.prefix.reclaim(n - len(self._free_pages),
                                      self.refcount)
            if ids:
                self._zero_pages(ids)
                self.retained.difference_update(ids)
                self.n_reclaimed += len(ids)
                for p in ids:
                    heapq.heappush(self._free_pages, p)
        if n > len(self._free_pages):
            raise PoolExhausted(
                f"need {n} pages but only {len(self._free_pages)} of "
                f"{self.n_blocks} are free after reclaim",
                pages_needed=n, pages_free=len(self._free_pages),
                uid=uid, reason=reason)
        pages = [heapq.heappop(self._free_pages) for _ in range(n)]
        self.refcount[pages] = 1
        self.peak_pages = max(self.peak_pages, self.pages_in_use)
        return pages

    def _table_width(self, pages) -> np.ndarray:
        """``pages`` padded with the trash block (page ``n_blocks``) to the
        table's width: the eviction and the zeroing then compile once per
        pool, whatever the count of pages."""
        out = np.full((max(self.max_blocks, len(pages)),), self.n_blocks,
                      np.int32)
        out[:len(pages)] = pages
        return out

    def _zero_pages(self, pages) -> None:
        self.cache = cache_ops.paged_zero_pages(self.cache,
                                                self._table_width(pages))

    def warm_prefix_edits(self) -> None:
        """Run the page edits only a prefix cache reaches (a reclaim's
        zeroing, a copy-on-write) once, on the trash block alone, which no
        read sees: they compile in set-up, not when a serving window first
        meets page pressure."""
        self._zero_pages([self.n_blocks])
        self.cache = cache_ops.paged_copy_page(self.cache, self.n_blocks,
                                               self.n_blocks)

    def _release_page(self, page: int) -> None:
        """Drop one reference; zero + free at refcount 0 unless the prefix
        tree retains the page warm."""
        self.refcount[page] -= 1
        if self.refcount[page] < 0:
            raise PrefixCacheInvariantError(
                f"page {page} refcount went negative")
        if self.refcount[page] == 0 and page not in self.retained:
            self._zero_pages([page])
            heapq.heappush(self._free_pages, int(page))

    # --------------------------------------------------- prefix refcounting

    def pin_pages(self, pages) -> None:
        """Take a staging reference on matched pages (engine, at prefill
        start) so the LRU reclaimer cannot surrender them before the
        request admits; released by admission (the block-table reference
        replaces the pin) or by staging preemption."""
        for p in pages:
            self.refcount[p] += 1

    def unpin_pages(self, pages) -> None:
        for p in pages:
            self._release_page(int(p))

    def retain_pages(self, pages) -> None:
        """Mark pages the prefix tree just registered as retained warm."""
        for p in pages:
            if self.refcount[p] <= 0:
                raise PrefixCacheInvariantError(
                    f"page {p} retained while unreferenced")
            self.retained.add(int(p))

    def writable(self, page: int) -> bool:
        """May a slot write into ``page`` in place? Only when this slot is
        the sole reference *and* the prefix tree does not retain it — a
        retained page backs future hits even at refcount 1."""
        return self.refcount[page] <= 1 and page not in self.retained

    def check_fits(self, req: Request) -> None:
        """Raise :class:`PoolExhausted` if ``req`` can *never* fit: over
        ``max_seq`` (the block-table width) or over the page budget. Shared
        by admission and the engine's ``run()`` pre-check."""
        need = req.prompt_len + req.max_new_tokens
        if need > self.max_seq:
            raise PoolExhausted(
                f"request {req.uid!r} needs {need} cache positions "
                f"(prompt {req.prompt_len} + max_new {req.max_new_tokens}) "
                f"but the pool holds max_seq={self.max_seq}",
                uid=req.uid)
        if self.pages_for(need) > self.n_blocks:
            raise PoolExhausted(
                f"request {req.uid!r} needs {self.pages_for(need)} pages "
                f"of {self.block} tokens but the page budget is "
                f"n_blocks={self.n_blocks}",
                pages_needed=self.pages_for(need),
                pages_free=len(self._free_pages), uid=req.uid)

    def admit(self, entry: SlotEntry, single_cache: Any) -> int:
        """Reserve the prompt's pages and insert a prefilled B=1 cache into
        the lowest free slot. Lazy reservation: only ``ceil(prompt / block)``
        pages are taken now; decode growth allocates the rest on demand
        (:meth:`ensure_page`). ``single_cache`` may be a bucket-padded
        staging cache: rows past the prompt are written as zeros."""
        req = entry.request
        if not self._free:
            raise PoolExhausted("slot pool is full", uid=req.uid)
        self.check_fits(req)
        pages = self._take_pages(self.pages_for(req.prompt_len),
                                 uid=req.uid)
        slot = heapq.heappop(self._free)
        assert slot not in self.entries, "free-list/entries desync"
        self.tables[slot, :len(pages)] = pages
        self.cache = cache_ops.paged_insert(self.cache, single_cache, slot,
                                            pages, block=self.block,
                                            length=req.prompt_len)
        self.entries[slot] = entry
        return slot

    def admit_prefix(self, entry: SlotEntry, single_cache: Any,
                     match: PrefixMatch) -> int:
        """Prefix-hit admission (DESIGN.md §12): attach ``match.shared``
        pages by reference (their staging pins become this slot's
        block-table references — no refcount change), copy
        ``match.cow_src`` into a private page when the resume point falls
        inside it, and insert the suffix prefill from token
        ``match.resume`` with the overlay keeping copied rows below it.
        The engine still holds the pin on ``cow_src``; it releases it
        after this returns."""
        req = entry.request
        if not self._free:
            raise PoolExhausted("slot pool is full", uid=req.uid)
        self.check_fits(req)
        shared = [int(p) for p in match.shared]
        n_total = self.pages_for(req.prompt_len)
        fresh = self._take_pages(n_total - len(shared), uid=req.uid)
        slot = heapq.heappop(self._free)
        assert slot not in self.entries, "free-list/entries desync"
        self.tables[slot, :n_total] = shared + fresh
        if match.cow_src is not None:
            if not fresh:
                raise PrefixCacheInvariantError(
                    f"request {req.uid!r}: CoW admission took no private "
                    f"page for the resume point")
            self.cache = cache_ops.paged_copy_page(self.cache,
                                                   match.cow_src, fresh[0])
            self.n_cow += 1
        self.cache = cache_ops.paged_insert(self.cache, single_cache, slot,
                                            fresh, block=self.block,
                                            start=match.resume,
                                            length=req.prompt_len)
        self.entries[slot] = entry
        return slot

    def ensure_page(self, slot: int, write_pos: int) -> None:
        """Guarantee the page covering ``write_pos`` is allocated for
        ``slot`` — and *writable* — before a decode step writes there.
        An allocated but shared/retained page is copied first (CoW): the
        decode scatter never lands in a page another request or the warm
        prefix set can see. Raises :class:`PoolExhausted` when the free
        list is empty — the engine's cue to preempt a slot and re-queue
        its request."""
        entry = self.entries.get(slot)
        uid = entry.request.uid if entry is not None else None
        index = write_pos // self.block
        if index >= self.max_blocks:
            raise PoolExhausted(
                f"slot {slot} write position {write_pos} exceeds "
                f"max_seq={self.max_seq}", uid=uid, reason="decode")
        page = int(self.tables[slot, index])
        if page >= 0:
            if self.writable(page):
                return
            private = self._take_pages(1, uid=uid, reason="decode")[0]
            self.cache = cache_ops.paged_copy_page(self.cache, page,
                                                   private)
            self.tables[slot, index] = private
            self._release_page(page)
            self.n_cow += 1
            return
        self.tables[slot, index] = self._take_pages(1, uid=uid,
                                                    reason="decode")[0]

    def evict(self, slot: int) -> SlotEntry:
        """Release ``slot``'s references; zero and free what nothing else
        holds. Under prefix sharing eviction is a decref, not a free: a
        page still referenced by another slot survives untouched, and a
        refcount-0 page the prefix tree retains stays *warm* (contents
        intact, off the free list) until the LRU reclaimer surrenders it.
        Slot leaves and ``pos`` are always zeroed; returns the entry."""
        entry = self.entries.pop(slot)
        pages = self.tables[slot][self.tables[slot] >= 0]
        self.refcount[pages] -= 1
        if (self.refcount[pages] < 0).any():
            raise PrefixCacheInvariantError(
                f"slot {slot} eviction drove a page refcount negative")
        freed = [int(p) for p in pages.tolist()
                 if self.refcount[p] == 0 and p not in self.retained]
        self.cache = cache_ops.paged_evict(self.cache, slot,
                                           self._table_width(freed))
        self.tables[slot, :] = -1
        for p in freed:
            heapq.heappush(self._free_pages, p)
        heapq.heappush(self._free, slot)
        return entry

    def read(self, slot: int) -> Any:
        """The slot's state as a B=1 dense cache (``max_blocks * block``
        sequence extent)."""
        if slot not in self.entries:
            raise KeyError(f"slot {slot} is not live")
        return cache_ops.paged_read(self.cache, jnp.asarray(self.tables),
                                    slot, block=self.block)

    # ------------------------------------------------------------- tokens

    def positions(self) -> np.ndarray:
        """Per-slot device positions, pulled to host (testing/debug)."""
        return np.asarray(self.cache.pos)
