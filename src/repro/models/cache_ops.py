"""Uniform slot insert/read/evict contract over the family decode caches.

Every family's cache (``transformer.KVCache``, ``ssm_lm.SSMCacheState``,
``zamba2.HybridCache``) satisfies one structural contract (DESIGN.md §7):

* array leaves carry the **batch/slot dimension at axis 1** — axis 0 stacks
  layers / scan groups / attention sites, so ``leaf[:, i]`` is everything the
  model holds for sequence ``i``;
* the ``pos`` field is a per-sequence ``(B,)`` int32 vector of absolute
  positions (how far each sequence has decoded).

That single contract is what lets one serving engine drive all three model
families: a fixed-capacity *slot pool* cache is just ``init_cache(capacity,
max_seq)``, and admission/eviction are the pure functions below. All three
are shape-preserving pytree maps, safe under ``jax.jit`` with a traced
``slot`` index.

A single-sequence cache (from a B=1 prefill) may have a *shorter* sequence
axis than the pool — ``slot_insert`` writes it as a prefix and
``decode_attention`` masks the unfilled tail, so per-request prefill caches
drop into a long-lived pool without reshaping.

**Paged layout** (DESIGN.md §8): the ``paged_*`` functions below replace the
per-slot contiguous sequence stripe with a shared block pool. Leaves are
split into two classes:

* *sequence leaves* — anything under a ``k``/``v`` field (attention KV, the
  only leaves with a per-token sequence axis). In the paged pool they are
  stored as ``(lead, n_blocks + 1, block, *tail)``: axis 1 indexes physical
  pages of ``block`` tokens; the last page is a write-off **trash block**
  that absorbs scatters from free slots and is never handed out.
* *slot leaves* — everything else (SSM state, conv window, ``pos``): O(1)
  per sequence, so they keep the contiguous slot layout ``(lead, capacity,
  *tail)``.

A per-slot **block table** ``(capacity, max_blocks) int32`` maps logical
page index → physical page id, with ``-1`` marking an unallocated page
(reads redirect to the trash block, whose contents are always masked by the
per-row position mask). ``paged_gather`` materializes the dense per-slot
view the family decode steps already consume, and ``paged_commit`` scatters
the one token each decode step appends back into its page — so the decode
numerics are untouched and streams stay bit-identical to the contiguous
layout (the invariant tests/test_paging.py fuzzes).

The paged pool's edits (admission, eviction, page zeroing, copy-on-write,
prefix seeding) are jitted, with page lists padded by the trash block and
slots, lengths and offsets as runtime values, so a serving engine compiles
a few of each rather than one for every prompt length and count of pages;
each donates the pool it is given and updates it in place (the caller keeps
only what it returns). A ``counts`` field
(MoE load counters, ``transformer.KVCache``) is pool-wide: every edit
passes it through, and an admission adds the admitted cache's into it.
"""
from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.errors import CacheLayoutError, ConfigError

__all__ = ["slot_insert", "slot_read", "slot_evict", "slot_positions",
           "truncate_seq", "paged_init", "paged_gather", "paged_commit",
           "paged_commit_window", "paged_rollback",
           "paged_insert", "paged_evict", "paged_read", "paged_token_entry",
           "paged_copy_page", "paged_zero_pages", "prefix_seed",
           "SLOT_AXIS", "SEQ_FIELDS"]

#: The slot (batch) dimension of every non-``pos`` cache leaf.
SLOT_AXIS = 1

#: Name of the per-sequence position field in every family's cache.
_POS_FIELD = "pos"

#: Name of the pool-wide accumulator field (``transformer.KVCache.counts``):
#: not per slot, so the slot and page edits below pass it through, and an
#: admission adds the admitted single-sequence cache's counts into it.
_POOL_FIELD = "counts"

#: Field names whose leaves carry a per-token sequence axis (axis 2) and are
#: therefore paged; every other leaf is O(1) per sequence and stays
#: slot-indexed. All three family caches route attention KV through fields
#: with exactly these names (``cache_pspecs`` relies on the same contract).
SEQ_FIELDS = ("k", "v")


def _entry_name(entry) -> str:
    return str(getattr(entry, "name", getattr(entry, "key", None)))


def _is_pos(path: tuple) -> bool:
    return _entry_name(path[-1]) == _POS_FIELD


def _is_seq(path: tuple) -> bool:
    return any(_entry_name(p) in SEQ_FIELDS for p in path)


def _is_pool(path: tuple) -> bool:
    return _entry_name(path[-1]) == _POOL_FIELD


def _by_kind(*trees, pos, seq, slot, pool=None):
    """``tree_map`` over ``trees`` (structured like the first) calling, for
    each leaf, the function of its kind: ``pos``, a sequence leaf
    (``seq``), a pool-wide counter (``pool``; default: the first tree's
    leaf) or any other slot leaf (``slot``)."""
    def one(path, *leaves):
        if _is_pos(path):
            return pos(*leaves)
        if _is_pool(path):
            return leaves[0] if pool is None else pool(*leaves)
        if _is_seq(path):
            return seq(*leaves)
        return slot(*leaves)
    return jax.tree_util.tree_map_with_path(one, *trees)


def _pad_pages(pages, fill: int) -> jax.Array:
    """``pages`` as int32, padded with ``fill`` (the trash block) to the next
    power of two: a jitted page edit then compiles for a few lengths, not
    for every count of pages."""
    pages = np.asarray(pages, np.int32).reshape(-1)
    n = 1 << max(pages.size - 1, 0).bit_length()
    out = np.full((n,), fill, np.int32)
    out[:pages.size] = pages
    return jnp.asarray(out)


def _trash_of(data: Any) -> int:
    """The trash block's index in a paged pool: the last page."""
    for path, leaf in jax.tree_util.tree_flatten_with_path(data)[0]:
        if _is_seq(path) and not _is_pos(path):
            return _trash(leaf)
    return 0


def _check_rank(leaf) -> None:
    if leaf.ndim < SLOT_AXIS + 1:
        raise CacheLayoutError(
            f"cache leaf of rank {leaf.ndim} cannot carry the slot axis at "
            f"{SLOT_AXIS}; the family cache violates the slot contract")


def slot_insert(pool: Any, single: Any, slot) -> Any:
    """Write a single-sequence cache (B=1) into slot ``slot`` of ``pool``.

    ``single``'s non-slot extents must be ≤ the pool's (a shorter prefill
    cache lands as a prefix of the pool's sequence axis). Returns the new
    pool; ``slot`` may be a Python int or a traced int32 scalar.
    """
    slot = jnp.asarray(slot, jnp.int32)

    def one(path, pl, sl):
        if _is_pos(path):
            return pl.at[slot].set(jnp.reshape(sl, (-1,))[0])
        if _is_pool(path):
            return pl + sl
        _check_rank(pl)
        start = (jnp.zeros((), jnp.int32), slot) + \
            (jnp.zeros((), jnp.int32),) * (pl.ndim - 2)
        return jax.lax.dynamic_update_slice(pl, sl.astype(pl.dtype), start)

    return jax.tree_util.tree_map_with_path(one, pool, single)


def slot_read(pool: Any, slot) -> Any:
    """Extract slot ``slot`` as a single-sequence (B=1) cache with the pool's
    sequence extents (the inverse of :func:`slot_insert` up to tail zeros)."""
    slot = jnp.asarray(slot, jnp.int32)

    def one(path, pl):
        if _is_pos(path):
            return jax.lax.dynamic_slice_in_dim(pl, slot, 1)
        if _is_pool(path):
            return pl
        _check_rank(pl)
        return jax.lax.dynamic_slice_in_dim(pl, slot, 1, axis=SLOT_AXIS)

    return jax.tree_util.tree_map_with_path(one, pool)


def slot_evict(pool: Any, slot) -> Any:
    """Zero slot ``slot``'s state and reset its position.

    Zeroing (not just pos reset) keeps the batched decode numerics of the
    *other* slots reproducible: a freed slot's stale K/V or SSM state never
    feeds any computation (positions mask it), but zero state is what a
    fresh ``init_cache`` slot holds, so pool contents stay a pure function
    of the admitted requests.
    """
    slot = jnp.asarray(slot, jnp.int32)

    def one(path, pl):
        if _is_pos(path):
            return pl.at[slot].set(0)
        if _is_pool(path):
            return pl
        _check_rank(pl)
        return pl.at[:, slot].set(jnp.zeros_like(pl[:, slot]))

    return jax.tree_util.tree_map_with_path(one, pool)


def slot_positions(pool: Any) -> jax.Array:
    """The pool's per-slot ``(B,)`` position vector."""
    return pool.pos


def truncate_seq(single: Any, length: int) -> Any:
    """Slice a single-sequence cache's sequence leaves down to ``length``
    positions (token axis 2); slot leaves and ``pos`` pass through.

    The bridge from a bucket-padded chunked-prefill staging cache (sequence
    extent = the prompt's padded bucket, tail rows garbage or zero) to the
    exact-extent prefill cache :func:`slot_insert` / :func:`paged_insert`
    expect, so pool page accounting sees ``pages_for(prompt_len)`` — not the
    bucket — and pool contents stay a pure function of the live requests.
    ``length`` must be a host int (the slice is static).
    """
    def one(path, leaf):
        if _is_seq(path) and not _is_pos(path):
            return leaf[:, :, :length]
        return leaf

    return jax.tree_util.tree_map_with_path(one, single)


# --------------------------------------------------------------------------
# Paged block-pool layout (DESIGN.md §8)
# --------------------------------------------------------------------------

def _trash(leaf) -> int:
    """Physical index of the leaf's trash block (always the last page)."""
    return leaf.shape[SLOT_AXIS] - 1


def _safe_tables(tables: jax.Array, leaf) -> jax.Array:
    """Block tables with unallocated (-1) entries redirected to the trash
    block, so gathers stay in-bounds and scatters from free slots never land
    in a live page."""
    return jnp.where(tables < 0, _trash(leaf), tables)


def paged_init(init_cache: Callable[[int, int], Any], capacity: int,
               n_blocks: int, block: int) -> Any:
    """A paged pool cache for a family whose ``init_cache(batch, max_seq)``
    builds the contiguous layout.

    Sequence leaves come out as ``(lead, n_blocks + 1, block, *tail)`` (the
    ``+ 1`` is the trash block); slot leaves as ``(lead, capacity, *tail)``.
    The result is *not* a valid dense family cache — ``paged_gather`` makes
    one on demand.
    """
    if n_blocks < 1 or block < 1 or capacity < 1:
        raise ConfigError(
            f"paged pool needs capacity/n_blocks/block ≥ 1, got "
            f"{capacity}/{n_blocks}/{block}")
    by_block = init_cache(n_blocks + 1, block)
    by_slot = init_cache(capacity, block)
    return jax.tree_util.tree_map_with_path(
        lambda path, blk, slot: blk if _is_seq(path) else slot,
        by_block, by_slot)


def paged_gather(data: Any, tables: jax.Array, *, block: int) -> Any:
    """Materialize the dense per-slot family cache the decode steps consume.

    Each slot's pages are gathered in logical order and flattened into a
    contiguous sequence axis of ``max_blocks * block`` positions. Positions
    past a slot's ``pos`` (unallocated pages → trash block) carry garbage,
    exactly like the zero tail of the contiguous layout — the per-row
    position mask in decode attention excludes them *exactly* (softmax of a
    ``-1e30`` logit underflows to 0.0 in fp32), which is what keeps paged
    streams bit-identical. Safe under ``jit`` with ``tables`` traced.
    """
    capacity, max_blocks = tables.shape

    def one(path, leaf):
        if _is_pos(path) or not _is_seq(path):
            return leaf
        safe = _safe_tables(tables, leaf)                 # (C, MB)
        gathered = leaf[:, safe]                  # (lead, C, MB, blk, *tail)
        return gathered.reshape(leaf.shape[0], capacity, max_blocks * block,
                                *leaf.shape[2 + 1:])

    return jax.tree_util.tree_map_with_path(one, data)


def paged_token_entry(tables: jax.Array, pos, *, block: int
                      ) -> tuple[jax.Array, jax.Array]:
    """Per-slot ``(table entry, in-page offset)`` of the page cell holding
    each row's token at ``pos``.

    The one derivation of where a decode-step token lands in the page pool,
    shared by :func:`paged_commit` and the fused in-layer scatter
    (``models.layers.PagedKV`` decode paths) so the two write paths can
    never disagree. The entry is the *raw* table value — callers redirect
    negatives (free slots, whose drifted positions must land in the trash
    block) with their leaf's trash index. A position outside the table's
    logical extent — negative, or at/past ``max_blocks * block`` (a
    speculative draft overshooting a slot's last page) — resolves to ``-1``
    so the same trash redirect absorbs it instead of wrapping onto a live
    page.
    """
    capacity, max_blocks = tables.shape
    pos = jnp.asarray(pos, jnp.int32)
    raw_ix = pos // block
    page_ix = jnp.clip(raw_ix, 0, max_blocks - 1)
    entry = jnp.take_along_axis(tables, page_ix[:, None], axis=1)[:, 0]
    entry = jnp.where((raw_ix < 0) | (raw_ix >= max_blocks), -1, entry)
    return entry, pos % block


def paged_commit(data: Any, dense: Any, tables: jax.Array, *,
                 block: int) -> Any:
    """Fold one decode step's updates from the dense view back into pages.

    A decode step appends exactly one token per slot: for sequence leaves
    only the column at each slot's pre-step position changed, so that single
    token is scattered to ``(tables[slot, pos // block], pos % block)``.
    Slot leaves (SSM state, conv, ``pos``) are adopted wholesale from
    ``dense`` — their layout is identical in both views. Free slots (table
    entry -1) scatter into the trash block; duplicate trash writes race but
    trash contents are never read unmasked.
    """
    capacity, _ = tables.shape
    wpos = jnp.asarray(data.pos, jnp.int32)               # pre-step positions
    entry, off = paged_token_entry(tables, wpos, block=block)
    rows = jnp.arange(capacity)

    def one(path, pl, dl):
        if _is_pos(path) or not _is_seq(path):
            return dl
        bid = jnp.where(entry < 0, _trash(pl), entry)     # (C,)
        col = jnp.minimum(wpos, dl.shape[2] - 1)
        token = dl[:, rows, col]                          # (lead, C, *tail)
        return pl.at[:, bid, off].set(token.astype(pl.dtype))

    return jax.tree_util.tree_map_with_path(one, data, dense)


def paged_commit_window(data: Any, dense: Any, tables: jax.Array, *,
                        block: int, width: int) -> Any:
    """Fold a ``width``-token verify step's updates back into pages.

    The windowed generalization of :func:`paged_commit` for speculative
    verification (DESIGN.md §14): a ``decode_window_step`` writes ``width``
    fresh K/V rows per slot at positions ``[pos, pos + width)`` of the dense
    view (``pos`` = ``data.pos``, the *pre-step* positions — ``dense.pos``
    has already advanced by ``width``). Each of the ``width`` columns
    resolves its page cell through :func:`paged_token_entry`, so the write
    path stays the single shared derivation; cells whose position falls on
    an unallocated or out-of-range page land in the trash block. All slots
    commit the full window unconditionally — the engine's rollback pass
    (:func:`paged_rollback`) zeroes whatever verification rejects, and free
    slots' windows land in trash (their tables are all ``-1``).
    """
    capacity, _ = tables.shape
    base = jnp.asarray(data.pos, jnp.int32)               # pre-step positions
    cols = [paged_token_entry(tables, base + i, block=block)
            for i in range(width)]
    entry = jnp.stack([e for e, _ in cols], axis=1)       # (C, W)
    off = jnp.stack([o for _, o in cols], axis=1)         # (C, W)
    rows = jnp.arange(capacity)
    wpos = base[:, None] + jnp.arange(width)[None, :]     # (C, W)

    def one(path, pl, dl):
        if _is_pos(path) or not _is_seq(path):
            return dl
        bid = jnp.where(entry < 0, _trash(pl), entry)     # (C, W)
        col = jnp.minimum(wpos, dl.shape[2] - 1)
        token = dl[:, rows[:, None], col]                 # (lead, C, W, *tail)
        return pl.at[:, bid, off].set(token.astype(pl.dtype))

    return jax.tree_util.tree_map_with_path(one, data, dense)


def paged_rollback(data: Any, tables: jax.Array, *, block: int, width: int,
                   accept: jax.Array) -> Any:
    """Rewind a ``width``-token speculative window to its accepted prefix.

    After a verify step committed ``width`` tokens per slot (positions
    ``[base, base + width)`` with ``base = pos - width``), the engine keeps
    only ``accept[slot]`` of them (DESIGN.md §14): positions are rewound to
    ``base + accept`` and the K/V cells of the rejected suffix — ``width``
    cells from the new position, a deliberate overshoot past the dirty span
    — are zeroed. Overshoot is harmless: cells past a slot's dirty window
    are either already zero (allocated-but-unwritten pages are zeroed by
    ``paged_init`` / ``paged_evict`` / ``paged_zero_pages``) or resolve to
    the trash block, so re-zeroing them preserves the pool-contents-are-a-
    pure-function-of-live-state invariant rather than breaking it. Free
    slots pass ``accept = 0``: their window committed to trash, so the
    rewind restores their (drifted) ``pos`` and their zero-writes land in
    trash again.
    """
    accept = jnp.asarray(accept, jnp.int32)
    start = jnp.asarray(data.pos, jnp.int32) - width + accept  # (C,)
    cols = [paged_token_entry(tables, start + i, block=block)
            for i in range(width)]
    entry = jnp.stack([e for e, _ in cols], axis=1)       # (C, W)
    off = jnp.stack([o for _, o in cols], axis=1)         # (C, W)

    def one(path, pl):
        if _is_pos(path):
            return start
        if not _is_seq(path):
            return pl
        bid = jnp.where(entry < 0, _trash(pl), entry)     # (C, W)
        zeros = jnp.zeros_like(pl[:, bid, off])
        return pl.at[:, bid, off].set(zeros)

    return jax.tree_util.tree_map_with_path(one, data)


def paged_insert(data: Any, single: Any, slot: int,
                 pages: np.ndarray | list[int], *, block: int,
                 start: int = 0, length: int | None = None) -> Any:
    """Write a single-sequence (B=1) prefill cache into ``pages`` of the
    paged pool and ``slot`` of the slot leaves.

    ``length`` (default: ``single``'s sequence extent) is the prompt's
    length: rows at or past it are written as zeros, so a bucket-padded
    staging cache goes in as :func:`truncate_seq` would have cut it. With
    ``start == 0`` (the default), ``pages`` must hold ``ceil(length /
    block)`` physical page ids (host ints — page allocation is
    host-driven); the last page's tail beyond ``length`` is zero-padded.

    ``start > 0`` is the prefix-cache admission path (DESIGN.md §12):
    ``pages`` then covers only the token span from ``start``'s page onward
    — positions ``[(start // block) * block, …)`` — and page cells *below*
    ``start`` keep their existing pool contents. That overlay is what makes
    copy-on-write admission exact: the page copy supplies the shared rows
    the staging prefill never computed, and ``single`` supplies everything
    from the divergence point. Slot leaves and ``pos`` are always taken
    wholesale from ``single``; pool counters add ``single``'s.

    One jitted program per extent of ``single`` (``length``, ``start`` and
    ``slot`` are runtime values; logical pages with nothing to write go to
    the trash block), so a serving engine that admits its bucket-padded
    staging caches compiles one per prompt bucket, and one more where a
    resume point falls inside a page. ``data`` is donated.
    Returns the new pool pytree.
    """
    seq = [leaf for path, leaf in jax.tree_util.tree_flatten_with_path(
        single)[0] if _is_seq(path) and not _is_pos(path)]
    s1 = int(seq[0].shape[2]) if seq else 0
    length = s1 if length is None else int(length)
    pages = np.asarray(pages, np.int32).reshape(-1)
    first = start // block
    if (first + pages.size) * block < length:
        raise CacheLayoutError(
            f"{pages.size} pages of {block} tokens at token offset "
            f"{first * block} cannot hold a {length}-token prefill cache")
    n = -(-s1 // block)
    ids = np.full((n,), _trash_of(data), np.int32)
    real = pages[:max(n - first, 0)]
    ids[first:first + real.size] = real
    return _insert(data, single, jnp.int32(slot), jnp.asarray(ids),
                   jnp.int32(length), jnp.int32(start), block=block,
                   overlay=start > first * block)


def _insert_impl(data, single, slot, ids, length, start, *, block, overlay):
    n = ids.shape[0]
    cell = jnp.arange(n * block).reshape(n, block)

    def seq(pl, sl):
        lead = sl.shape[0]
        x = sl[:, 0]                                      # (lead, S1, *tail)
        pad = n * block - x.shape[1]
        x = jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
        x = x.reshape(lead, n, block, *x.shape[2:]).astype(pl.dtype)
        shape = (1,) + cell.shape + (1,) * (x.ndim - 3)
        x = jnp.where((cell < length).reshape(shape), x, 0)
        if overlay:
            # cells below ``start`` keep the pool's current contents (the
            # CoW copy); at and after it they take ``single``'s. Only an
            # admission whose resume point falls inside a page reads the
            # pool: on a TPU that gather copies the whole pool to another
            # layout, as the scatter below does once more.
            x = jnp.where((cell < start).reshape(shape), pl[:, ids], x)
        return pl.at[:, ids].set(x)

    def slot_leaf(pl, sl):
        _check_rank(pl)
        start_ix = (jnp.zeros((), jnp.int32), slot) \
            + (jnp.zeros((), jnp.int32),) * (pl.ndim - 2)
        return jax.lax.dynamic_update_slice(pl, sl.astype(pl.dtype), start_ix)

    return _by_kind(
        data, single, seq=seq, slot=slot_leaf,
        pos=lambda pl, sl: pl.at[slot].set(jnp.reshape(sl, (-1,))[0]),
        pool=lambda pl, sl: pl + sl)


_insert = jax.jit(_insert_impl, static_argnames=("block", "overlay"),
                  donate_argnums=(0,))


def paged_evict(data: Any, slot: int, pages: np.ndarray | list[int]) -> Any:
    """Zero ``slot``'s slot leaves and its ``pages``, reset its position.

    Zeroing freed pages keeps pool contents a pure function of the live
    requests (same argument as :func:`slot_evict`) — a reused page never
    leaks a previous tenant's KV into debugging dumps, even though the
    position mask already keeps it out of the math. One program per length
    of ``pages``: a pool pads its lists with the trash block to its table
    width, so it compiles one eviction. ``data`` is donated.
    """
    return _evict(data, jnp.int32(slot),
                  jnp.asarray(np.asarray(pages, np.int32).reshape(-1)))


def _evict_impl(data, slot, ids):
    def slot_leaf(pl):
        _check_rank(pl)
        return pl.at[:, slot].set(jnp.zeros_like(pl[:, slot]))

    return _by_kind(
        data, slot=slot_leaf, pos=lambda pl: pl.at[slot].set(0),
        seq=lambda pl: pl.at[:, ids].set(jnp.zeros_like(pl[:, ids])))


_evict = jax.jit(_evict_impl, donate_argnums=(0,))


def paged_read(data: Any, tables: jax.Array, slot: int, *,
               block: int) -> Any:
    """Extract ``slot`` as a single-sequence (B=1) dense cache (sequence
    extent ``max_blocks * block``, unallocated tail zero for freshly
    evicted pages / trash garbage otherwise). Test/debug surface — the
    decode path gathers all slots at once."""
    return slot_read(paged_gather(data, tables, block=block), slot)


# --------------------------------------------------------------------------
# Prefix-cache page sharing (DESIGN.md §12)
# --------------------------------------------------------------------------

def paged_copy_page(data: Any, src: int, dst: int) -> Any:
    """Copy one physical page's sequence cells ``src`` → ``dst``.

    The copy-on-write primitive: before the first write into a shared
    (refcount > 1 or prefix-retained) page, the pool copies it to a private
    page and rewrites the slot's block table. Only sequence leaves have
    page axes; slot leaves and ``pos`` pass through. ``src``/``dst`` are
    host ints — CoW decisions are host-driven like all page allocation —
    passed to one jitted program as runtime values. ``data`` is donated.
    """
    return _copy(data, jnp.int32(src), jnp.int32(dst))


def _copy_impl(data, src, dst):
    return _by_kind(data, pos=lambda pl: pl, slot=lambda pl: pl,
                    seq=lambda pl: pl.at[:, dst].set(pl[:, src]))


_copy = jax.jit(_copy_impl, donate_argnums=(0,))


def paged_zero_pages(data: Any, pages: np.ndarray | list[int]) -> Any:
    """Zero the sequence cells of ``pages`` (no slot is touched).

    The reclaim half of prefix-retained eviction: a page kept warm for
    reuse after its last reference dropped is zeroed only when the LRU
    reclaimer finally hands it back to the free list, preserving the
    pool-contents-are-a-pure-function-of-live-state argument of
    :func:`paged_evict`. One program per length of ``pages``, as there;
    ``data`` is donated.
    """
    pages = np.asarray(pages, np.int32).reshape(-1)
    if pages.size == 0:
        return data
    return _zero(data, jnp.asarray(pages))


def _zero_impl(data, ids):
    return _by_kind(
        data, pos=lambda pl: pl, slot=lambda pl: pl,
        seq=lambda pl: pl.at[:, ids].set(jnp.zeros_like(pl[:, ids])))


_zero = jax.jit(_zero_impl, donate_argnums=(0,))


def prefix_seed(single: Any, data: Any, pages: np.ndarray | list[int], *,
                block: int, resume: int) -> Any:
    """Seed a B=1 staging cache's sequence rows ``[0, resume)`` from pool
    ``pages`` and set its position to ``resume``.

    The prefix-cache hit path for chunked prefill: the staging cache enters
    the PR 6 carry *mid-prompt* — ``prefill_chunk_step`` reads ``pos`` as
    the absolute resume offset, so pre-seeded K/V rows below ``resume``
    stand in for the chunks that are skipped. Rows at/after ``resume``
    (garbage from the last matched page's tail, clipped to the staging
    extent) are overwritten by the suffix chunks before any query position
    reaches them, and causally masked until then. Slot leaves pass through
    zero-initialised — which is why only the families whose whole state is
    K/V + pos may take this path. One jitted program per staging extent and
    padded page count.
    """
    pages = np.asarray(pages, np.int32).reshape(-1)
    return _seed(single, data, _pad_pages(pages, _trash_of(data)),
                 jnp.int32(pages.size * block), jnp.int32(resume),
                 block=block)


@functools.partial(jax.jit, static_argnames=("block",))
def _seed(single, data, ids, n_rows, resume, *, block):
    def seq(sl, dl):
        gathered = dl[:, ids]                    # (lead, n, block, *tail)
        flat = gathered.reshape(dl.shape[0], ids.shape[0] * block,
                                *dl.shape[3:])
        rows = min(flat.shape[1], sl.shape[2])
        keep = jnp.arange(rows) < n_rows         # real pages only
        keep = keep.reshape((1, rows) + (1,) * (sl.ndim - 3))
        head = jnp.where(keep, flat[:, :rows].astype(sl.dtype),
                         sl[:, 0, :rows])
        return sl.at[:, 0, :rows].set(head)

    return _by_kind(single, data, seq=seq, slot=lambda sl, dl: sl,
                    pos=lambda sl, dl: jnp.full_like(sl, resume))
