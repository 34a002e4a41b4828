"""The page manager: which pages of a model's KV pools a slot holds, and when.

`models/paged_kv.py` is the device side: the pools, the tables, the jitted
table updates and the jitted steps. This module is the host side, one
`ModelPages` a model the engine serves (the target, and a draft where one
proposes): it owns that model's `PagedKVCache`, the free lists of its pools,
its prefix cache and every slot's page lists, and it answers the engine's loop
by slot index. WHO is served, preempted or finished, and in what order, is
the loop's (serving/engine.py); from which pool a page comes, what is evicted
for it, what goes back and when, is here.

A model has one pool that grows with the context (`page_table`) and, where it
has window layers, a second that turns over behind the window
(`window_table`). The growing pool may share pages through a `PrefixCache`,
with partial pages (copy-on-write before a write) or with full pages only.

Importing this module does not import jax: `ModelPages` takes the device
side in when it is built.
"""

from __future__ import annotations

import math
from typing import Any, NamedTuple, Optional


class PagePoolExhausted(Exception):
    """The shared page pool has no free pages (caller should preempt or
    queue — never a crash; docs/SERVING.md degradation matrix)."""


class PageAllocator:
    """Host-side free-list block allocator over the page pool, with
    per-page refcounts for shared-prefix reuse (ISSUE 12).

    Pages are interchangeable (the page table adds the indirection), so this
    is exact-fit by construction: `can_alloc(n)` ⇔ `len(free) >= n`, no
    matter how fragmented the alloc/free history was. Page 0 is reserved as
    the scratch page and never handed out.

    Refcounts make one physical page serveable to many readers: `alloc`
    hands a page out at refcount 1, `share` adds a holder, `free` drops one
    holder and only returns the page to the free list when the last holder
    lets go. A page with refcount > 1 is copy-on-write for whoever wants to
    mutate it (`shared()` is the write barrier's predicate, `ModelPages.reserve`)."""

    def __init__(self, num_pages: int, page_size: int):
        if num_pages < 2:
            raise ValueError("need >= 2 pages (page 0 is the reserved scratch page)")
        self.num_pages = num_pages
        self.page_size = page_size
        self._free: list[int] = list(range(num_pages - 1, 0, -1))  # pop() yields 1, 2, ...
        self._refs: dict[int, int] = {}  # page -> live holder count
        self.high_water = 0

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def allocated_pages(self) -> int:
        return (self.num_pages - 1) - len(self._free)

    def pages_for(self, num_tokens: int) -> int:
        return max(1, math.ceil(num_tokens / self.page_size))

    def can_alloc(self, n: int) -> bool:
        return len(self._free) >= n

    def alloc(self, n: int) -> list[int]:
        if n > len(self._free):
            raise PagePoolExhausted(
                f"need {n} pages, {len(self._free)} free (pool {self.num_pages - 1})"
            )
        pages = [self._free.pop() for _ in range(n)]
        for p in pages:
            self._refs[p] = 1
        self.high_water = max(self.high_water, self.allocated_pages)
        return pages

    def share(self, pages: list[int]) -> None:
        """Add one holder to each page (prefix-cache entries and follower
        slots each count as a holder)."""
        for p in pages:
            if self._refs.get(p, 0) <= 0:
                raise ValueError(f"share of unallocated page {p}")
            self._refs[p] += 1

    def refcount(self, page: int) -> int:
        return self._refs.get(page, 0)

    def shared(self, page: int) -> bool:
        """True when more than one holder references the page — any write
        must copy first (the CoW barrier)."""
        return self._refs.get(page, 0) > 1

    def free(self, pages: list[int]) -> None:
        """Drop one holder per page; the page returns to the free list only
        at refcount zero. Double frees (more drops than holders) still fail
        loudly — the refcount IS the detector."""
        if len(set(pages)) != len(pages):
            raise ValueError(f"double free within one batch: {pages}")
        for p in pages:
            if not 0 < p < self.num_pages:
                raise ValueError(f"page {p} out of range")
            if self._refs.get(p, 0) <= 0:
                raise ValueError(f"double free of page {p}")
        for p in pages:
            self._refs[p] -= 1
            if self._refs[p] == 0:
                del self._refs[p]
                self._free.append(p)


# -- shared-prefix KV reuse (ISSUE 12) ----------------------------------------


class PrefixCacheEntry:
    """One cached prefix: the exact token prefix and the pages holding its
    KV. The entry is a page holder (allocator refcount), so its pages stay
    live after the inserting request completes — that is the whole point:
    a fleet-wide system prompt prefilled once keeps serving followers."""

    __slots__ = ("tokens", "pages", "last_used", "hits")

    def __init__(self, tokens: tuple, pages: list[int]):
        self.tokens = tokens
        self.pages = pages
        self.last_used = 0.0
        self.hits = 0


class PrefixHit(NamedTuple):
    """What `PrefixCache.lookup` found: the shared pages (one holder's ref
    taken on each), the tokens they cover, and the entry; a miss is
    `([], 0, None)`."""

    pages: list
    covered: int
    entry: Optional[PrefixCacheEntry]


class PrefixCache:
    """Content-keyed prefix → KV-pages lookup over the shared pool.

    Keys are page-granular: an entry for prompt T is indexed under every
    full-page prefix `T[:j*page]`, so a follower whose prompt extends T (the
    system-prompt fleet case) finds the longest full-page match in
    O(pages-in-prompt) dict probes. A hit can extend token-granular into the
    entry's next, partially-matching page — that page is then refcount-shared
    and the follower's first write into it triggers copy-on-write
    (`copy_page`), never a mutation of cached bytes.

    The cache is a holder like any slot: `lookup` refs pages for the caller,
    `insert` refs them for the entry, `evict_lru`/`clear` un-ref. Pool
    pressure evicts entries before the engine resorts to preempting live
    requests (`ModelPages` below asks, serving/engine.py preempts)."""

    def __init__(self, allocator: PageAllocator, partial_pages: bool = True):
        self.allocator = allocator
        self.page_size = allocator.page_size
        # False: coverage and entries stop at the full-page boundary, so no
        # shared page is ever written and the pool needs no copy-on-write (a
        # draft's pool, ISSUE 18); it may only share pages it never touches
        self.partial_pages = partial_pages
        self._entries: dict[tuple, PrefixCacheEntry] = {}  # full-token key -> entry
        self._index: dict[tuple, PrefixCacheEntry] = {}  # page-granular prefix -> entry
        self._clock = 0.0
        self.hits = 0
        self.misses = 0

    def __len__(self) -> int:
        return len(self._entries)

    @property
    def held_pages(self) -> int:
        return sum(len(e.pages) for e in self._entries.values())

    def _tick(self) -> float:
        self._clock += 1.0
        return self._clock

    def lookup(self, tokens: list) -> PrefixHit:
        """Longest cached prefix of `tokens` covering at most len(tokens)-1
        positions (the suffix must still prefill to produce last-token
        logits), with one holder ref taken on every returned page — the
        caller owns the release. `covered` may end mid-page; that last page
        arrives refcount-shared and must be CoW'd before the caller writes
        into it.

        Deliberately side-effect-free beyond the refs: hit/miss counters and
        the entry's LRU clock move at `commit_use`/`note_miss` — a dry-pool
        admission retried every loop iteration must not inflate hit stats or
        keep the contested entry artificially hot against eviction."""
        page = self.page_size
        max_cover = len(tokens) - 1
        for j in range(max_cover // page, 0, -1):
            entry = self._index.get(tuple(tokens[: j * page]))
            if entry is None:
                continue
            covered = j * page
            pages = list(entry.pages[:j])
            # token-granular extension into the entry's next (partial) page
            if self.partial_pages and len(entry.tokens) > covered and len(entry.pages) > j:
                limit = min(page, len(entry.tokens) - covered, max_cover - covered)
                extra = 0
                while extra < limit and entry.tokens[covered + extra] == tokens[covered + extra]:
                    extra += 1
                if extra > 0:
                    pages.append(entry.pages[j])
                    covered += extra
            self.allocator.share(pages)
            return PrefixHit(pages, covered, entry)
        return PrefixHit([], 0, None)

    def commit_use(self, entry: "PrefixCacheEntry") -> None:
        """Count a real reuse (the admission actually went through) and
        refresh the entry's LRU position."""
        entry.last_used = self._tick()
        entry.hits += 1
        self.hits += 1

    def note_miss(self) -> None:
        self.misses += 1

    def insert(self, tokens: list, pages: list[int]) -> bool:
        """Cache `tokens`' prefix KV. `pages` is the holding slot's page list
        (only the prompt-covering prefix is taken); the entry refs them, so
        they outlive the slot. Needs at least one full page to be indexable.
        Returns True if a new entry was created.

        Without `partial_pages` only the full-page prompt prefix is
        published (the partial last page stays private to the slot): a shared
        page is then write-free on both sides."""
        page = self.page_size
        full = len(tokens) // page
        if full < 1:
            return False
        if not self.partial_pages:
            tokens = list(tokens[: full * page])
        key = tuple(tokens)
        if key in self._entries:
            return False
        n_pages = math.ceil(len(tokens) / page)
        if n_pages > len(pages):
            return False  # caller's pages don't cover the prompt (shouldn't happen)
        entry = PrefixCacheEntry(key, list(pages[:n_pages]))
        self.allocator.share(entry.pages)
        entry.last_used = self._tick()
        self._entries[key] = entry
        for j in range(1, full + 1):
            # first inserter wins a contested page-prefix key: stable, and
            # the loser's entry still serves its own exact-match lookups
            self._index.setdefault(tuple(tokens[: j * page]), entry)
        return True

    def _drop(self, entry: PrefixCacheEntry) -> None:
        self._entries.pop(entry.tokens, None)
        for k in [k for k, e in self._index.items() if e is entry]:
            del self._index[k]
        self.allocator.free(entry.pages)

    def evict_lru(self) -> int:
        """Evict the least-recently-used entry; returns how many of its
        pages this released (pages still shared with live slots stay
        allocated — eviction drops the cache's ref, never a reader's)."""
        if not self._entries:
            return 0
        entry = min(self._entries.values(), key=lambda e: e.last_used)
        released = sum(1 for p in entry.pages if self.allocator.refcount(p) == 1)
        self._drop(entry)
        return released

    def clear(self) -> None:
        for entry in list(self._entries.values()):
            self._drop(entry)


# -- one model's pools ---------------------------------------------------------


def prefix_hit_lacks(cfg: Any) -> str:
    """What a prefix hit cannot hand over, by mechanism, or "" where it hands
    over every layer's state. A hit is pages of the pool that grows with the
    context; a layer whose state at the prefix's end lives anywhere else
    cannot be served from it. The engine's default (no prefix cache) and
    `ModelPages`' refusal of an explicit one both read this."""
    if cfg.has_window:
        return (
            "window layers (a window layer needs its last `window` positions too, which the pool gave back "
            "behind the window)"
        )
    if cfg.has_conv:
        return (
            "convolution layers (a convolution layer needs its state at the prefix's end, which a slot holds "
            "beside its pages and no page holds)"
        )
    return ""



class ModelPages:
    """One model's KV pages on the host: the device cache, the free lists, the
    prefix cache and every slot's page lists. Only the engine's thread calls
    the methods that change anything; `stats` may be read from any thread.

    `self.cache` goes into a jitted step and the step's returned cache is
    stored back; between steps the methods below update its tables with the
    jitted helpers of `models/paged_kv.py`, each of one fixed shape: one
    padded-row `assign_pages` an admission, one `copy_page` a copy-on-write,
    one `assign_entries` for everything else a `reserve` handed out (growth
    and the window pool's turn-over together, `[tables, 3,
    _table_write_len]` host integers; none handed out, no call), one
    `release_slot` a freed slot.

    A slot's row in `cache.window_table` is indexed like its row in
    `page_table` (position p lives at index p // page_size), but only the
    indices the window can touch hold a live page: `window_pages[i]` are the
    live ones, consecutive from index `window_first[i]` on."""

    def __init__(
        self,
        cfg: Any,
        *,
        max_slots: int,
        num_pages: int,
        page_size: int,
        prefill_chunk: int,
        pages_per_slot: Optional[int] = None,
        # pages of the window layers' pool; None = every slot's window and one
        # chunk's headroom (paged_kv.default_window_num_pages)
        window_num_pages: Optional[int] = None,
        prefix_cache: bool = False,
        # False: a cached prefix ends at a page boundary on both sides, so no
        # shared page is ever written and nothing is copied (a draft's pool)
        partial_pages: bool = True,
    ):
        import jax.numpy as jnp

        from ..models import paged_kv

        self._jnp, self._kv = jnp, paged_kv
        self.cfg = cfg
        self.max_slots = max_slots
        self.page_size = page_size
        self.prefill_chunk = prefill_chunk
        self.pages_per_slot = pages_per_slot or math.ceil(cfg.max_seq_len / page_size)
        self.window = cfg.window if cfg.has_window else 0
        self.window_allocator: Optional[PageAllocator] = None
        if prefix_cache and prefix_hit_lacks(cfg):
            raise ValueError(
                f"prefix_cache=True with {prefix_hit_lacks(cfg)}: a prefix hit hands over the full-attention pages only"
            )
        if self.window:
            if window_num_pages is None:
                window_num_pages = paged_kv.default_window_num_pages(cfg, max_slots, page_size, prefill_chunk)
            if window_num_pages - 1 < math.ceil((prefill_chunk + self.window - 1) / page_size) + 1:
                raise ValueError(
                    f"window_num_pages={window_num_pages} cannot hold one prefill chunk of {prefill_chunk} "
                    f"tokens and the window of {self.window} before it"
                )
            self.window_allocator = PageAllocator(window_num_pages, page_size)
        # one fixed length for every assign_entries call (one executable): a decode step's
        # page a slot, or a prefill chunk's window pages; more go in further calls
        self._table_write_len = max(max_slots, math.ceil(prefill_chunk / page_size) + 1)
        self.allocator = PageAllocator(num_pages, page_size)
        self.cache = paged_kv.PagedKVCache.create(
            cfg, max_slots, num_pages, page_size, self.pages_per_slot, window_num_pages
        )
        self.pool_bytes, self.window_pool_bytes = paged_kv.pool_bytes_by_kind(cfg, self.cache)
        self.conv_state_bytes = self.cache.conv_state_bytes()  # every slot's, beside the pools: no page holds it
        self.prefix_cache: Optional[PrefixCache] = PrefixCache(self.allocator, partial_pages) if prefix_cache else None
        self.pages: list[list[int]] = [[] for _ in range(max_slots)]
        self.window_pages: list[list[int]] = [[] for _ in range(max_slots)]
        self.window_first = [0] * max_slots
        self.cow_copies = 0
        self.window_pages_released = 0
        self.table_writes = 0  # assign_entries calls
        self.table_entries = 0  # (slot, index, page) entries they carried, both tables

    # -- what the loop reads --------------------------------------------------

    @property
    def total_pages(self) -> int:
        return self.allocator.num_pages - 1

    @property
    def free_pages(self) -> int:
        return self.allocator.free_pages

    @property
    def allocated_pages(self) -> int:
        return self.allocator.allocated_pages

    def pages_for(self, num_tokens: int) -> int:
        return self.allocator.pages_for(num_tokens)

    # -- admission ------------------------------------------------------------

    def lookup(self, tokens: list) -> Optional[PrefixHit]:
        """The longest cached prefix of `tokens`, its pages held for the
        caller until `admit` takes them or `drop` lets them go; None where the
        model caches no prefixes. Counts nothing: `admit` does."""
        return self.prefix_cache.lookup(tokens) if self.prefix_cache is not None else None

    def drop(self, hit: Optional[PrefixHit]) -> None:
        if hit is not None and hit.pages:
            self.allocator.free(hit.pages)

    def _fresh_need(self, n_tokens: int, hit: Optional[PrefixHit]) -> int:
        # the tokens and the position the first new one is written to
        return max(0, self.allocator.pages_for(n_tokens + 1) - (len(hit.pages) if hit is not None else 0))

    def _evict_prefixes(self, shortage: int) -> None:
        """Drop LRU prefix-cache entries until `shortage` pages came free (or
        the cache is empty). Cached prefixes are strictly cheaper to lose
        than live requests: this always runs before the engine preempts."""
        released = 0
        while released < shortage and self.prefix_cache is not None and len(self.prefix_cache):
            released += self.prefix_cache.evict_lru()

    def can_admit(self, n_tokens: int, hit: Optional[PrefixHit]) -> bool:
        """Whether a slot of `n_tokens` to prefill fits, after evicting cached
        prefixes for it. The window layers' pool is asked for the first
        chunk's room only: it gives its pages chunk by chunk, to the one slot
        whose chunk runs (`reserve`), and a slot that waits its turn holds none."""
        fresh = self._fresh_need(n_tokens, hit)
        if not self.allocator.can_alloc(fresh):
            self._evict_prefixes(fresh - self.allocator.free_pages)
            if not self.allocator.can_alloc(fresh):
                return False
        if self.window_allocator is None:
            return True
        first_chunk_last = min(n_tokens, self.prefill_chunk) - 1
        return self.window_allocator.can_alloc(first_chunk_last // self.page_size + 1)

    def admit(self, idx: int, n_tokens: int, hit: Optional[PrefixHit]) -> int:
        """Slot `idx` takes the hit's pages and fresh ones for the rest, and
        its row goes to the device. This is the admission's commit: the hit
        or the miss is counted here, not at a dry-pool retry of `can_admit`.
        Returns the tokens the shared pages cover."""
        pages = (hit.pages if hit is not None else []) + self.allocator.alloc(self._fresh_need(n_tokens, hit))
        self.pages[idx] = pages
        self.window_pages[idx] = []
        self.window_first[idx] = 0
        if hit is not None:
            if hit.entry is not None and hit.covered:
                self.prefix_cache.commit_use(hit.entry)
            else:
                self.prefix_cache.note_miss()
        # pad the row to pages_per_slot: assign_pages keys an executable on
        # the page-array SHAPE, so padded admissions all share one compile
        row = pages + [0] * (self.pages_per_slot - len(pages))
        self.cache = self._kv.assign_pages(self.cache, idx, 0, self._jnp.asarray(row, self._jnp.int32))
        return hit.covered if hit is not None else 0

    # -- before a write -------------------------------------------------------

    def _shared_in(self, wants: list) -> list:
        """(slot index, row index) of every held page in the write ranges
        that has another holder: the copy-on-write barrier's work list. Pages
        past a slot's row are growth's, and fresh."""
        if self.prefix_cache is None or not self.prefix_cache.partial_pages:
            return []  # nothing is shared, or no shared page is ever written
        size, held, shared = self.page_size, self.pages, self.allocator.shared
        return [
            (i, t)
            for i, first, last in wants
            for t in range(first // size, min(last // size + 1, len(held[i])))
            if shared(held[i][t])
        ]

    def reserve(self, wants: list) -> bool:
        """wants: [(slot index, first position, last position)], the writes
        the next step makes. Every pool holds them afterwards, or (False) no
        page was handed out and the caller preempts and asks again:

        - the pool that grows gives each slot the pages its row lacks up to
          `last`, and every shared page in a write range is copied into a
          private one first (`copy_page`; the shared original, which the
          prefix cache or another slot still reads, is never written);
          cached prefixes are evicted for either before this says no;
        - the window layers' pool takes back what lies behind the window of
          the query at `first` and gives the pages up to `last`.

        What the pools gave goes to the device's tables in ONE `assign_entries`
        call, after the copies (more entries than `_table_write_len`: further
        calls of the same shape; nothing given: no call)."""
        jnp, kv = self._jnp, self._kv
        size, held, pool = self.page_size, self.pages, self.allocator
        grow = [(i, last // size + 1 - len(held[i])) for i, _first, last in wants if last // size >= len(held[i])]
        cow = self._shared_in(wants)
        short = sum(n for _i, n in grow) + len(cow) - pool.free_pages
        if short > 0:
            self._evict_prefixes(short)
            cow = self._shared_in(wants)  # an evicted entry may have been the other holder
            if sum(n for _i, n in grow) + len(cow) > pool.free_pages:
                return False
        if self.window_allocator is not None:
            window_need = []
            for i, first, last in wants:
                self.trim(i, first)
                window_need.append(max(0, last // size + 1 - self.window_first[i] - len(self.window_pages[i])))
            if not self.window_allocator.can_alloc(sum(window_need)):
                return False
        grown = []  # (slot index, row index, page) for `page_table` on the device
        for i, n in grow:
            for page in pool.alloc(n):
                grown.append((i, len(held[i]), page))
                held[i].append(page)
        for i, t in cow:  # reads the table's entry of a page the slot held before: never a grown one
            old = held[i][t]
            if not pool.shared(old):
                continue  # a copy above left this slot the only holder
            page = pool.alloc(1)[0]
            self.cache = kv.copy_page(self.cache, i, t, jnp.int32(page))
            pool.free([old])  # this slot's ref; the other holders keep theirs
            held[i][t] = page
            self.cow_copies += 1
        tables = [grown]
        if self.window_allocator is not None:
            turned = []  # the same for `window_table`
            for (i, _first, _last), n in zip(wants, window_need):
                held_window = self.window_pages[i]
                for page in self.window_allocator.alloc(n):
                    turned.append((i, self.window_first[i] + len(held_window), page))
                    held_window.append(page)
            tables.append(turned)
        length = self._table_write_len
        for at in range(0, max(len(entries) for entries in tables), length):
            packed = kv.pack_entries(self.max_slots, length, *(entries[at : at + length] for entries in tables))
            self.cache = kv.assign_entries(self.cache, packed)
            self.table_writes += 1
        self.table_entries += sum(len(entries) for entries in tables)
        return True

    # -- after a write, and at a slot's end -----------------------------------

    def trim(self, idx: int, next_pos: int) -> None:
        """The window pool's pages that lie wholly behind the window of the
        NEXT query (at `next_pos`) go back to the pool while the request
        lives; their table entries go stale and are never addressed again.
        Nothing for a pool that grows."""
        if self.window_allocator is None:
            return
        first = max(0, next_pos - (self.window - 1)) // self.page_size
        held = self.window_pages[idx]
        dead = min(len(held), first - self.window_first[idx])
        if dead > 0:
            self.window_allocator.free(held[:dead])
            del held[:dead]
            self.window_first[idx] += dead
            self.window_pages_released += dead
        if not held:
            self.window_first[idx] = max(self.window_first[idx], first)

    def release(self, idx: int, on_device: bool = True) -> None:
        """Everything slot `idx` holds goes back, every pool together, and
        its rows on the device point at scratch again (`on_device=False`
        after a failed step, when the cache may not be there to update)."""
        self.allocator.free(self.pages[idx])
        self.pages[idx] = []
        if self.window_allocator is not None:
            self.window_allocator.free(self.window_pages[idx])
            self.window_pages[idx] = []
            self.window_first[idx] = 0
        if on_device:
            self.cache = self._kv.release_slot(self.cache, idx)

    def publish(self, idx: int, prompt: list) -> None:
        """The prompt's KV is resident in slot `idx`: cache it for followers
        (the entry holds the pages, so they outlive the request; `insert`
        dedups by exact content). Full pages only where no partial page may
        be shared: the last, partial page stays the slot's own."""
        if self.prefix_cache is not None and len(prompt) >= self.page_size:
            self.prefix_cache.insert(prompt, self.pages[idx])

    def clear_prefixes(self) -> None:
        if self.prefix_cache is not None:
            self.prefix_cache.clear()

    def prefix_keys(self, limit: int) -> list:
        """The full-page prefixes the cache serves now, as token tuples."""
        if self.prefix_cache is None:
            return []
        return list(self.prefix_cache._index.keys())[:limit]  # atomic snapshot (GIL)

    # -- shipments between replicas -------------------------------------------

    def check_ships(self, what: str) -> None:
        """Refuse at submission what `export_pages` / `import_pages` would
        refuse in the loop: they address one pool."""
        self._kv._one_pool(self.cache, what)

    def shipment_fits(self, n_tokens: int, shipment: dict) -> bool:
        """Whether the bundle's arrays are `n_tokens` of this pool's pages."""
        k, v = shipment.get("k"), shipment.get("v")
        return (
            k is not None
            and v is not None
            and getattr(k, "shape", None) == getattr(v, "shape", None)
            and k.shape[:3] == (self.cfg.n_layers, -(-n_tokens // self.page_size), self.page_size)
        )

    def export_shipment(self, idx: int, n_tokens: int) -> tuple[dict, int]:
        """The pages of slot `idx` that hold its first `n_tokens`, pulled off
        the device: ({"k", "v"}, pages). The pages must still be live. Two
        pools refuse here too, by mechanism (`paged_kv.export_pages`)."""
        n = -(-n_tokens // self.page_size)
        return self._kv.export_pages(self.cache, self.pages[idx][:n]), n

    def import_shipment(self, idx: int, n_tokens: int, shipment: dict) -> int:
        """Land a bundle of `n_tokens` in the slot's first pages (fresh from
        `admit`); returns how many pages that was."""
        n = -(-n_tokens // self.page_size)
        self.cache = self._kv.import_pages(self.cache, self.pages[idx][:n], shipment)
        return n

    # -- /v1/stats -------------------------------------------------------------

    def stats(self) -> dict:
        """The `kv_pages_*` keys mean the pool that grows with the context; a
        model without window layers carries no `kv_window_*` key."""
        pool, prefixes = self.allocator, self.prefix_cache
        # what one more token of context costs over all layers, as the pools store it (a key
        # padded to a stored width counts padded, a latent row as one row)
        per_token = self.pool_bytes / (pool.num_pages * self.page_size)
        if self.window_allocator is not None:
            per_token += self.window_pool_bytes / (self.window_allocator.num_pages * self.page_size)
        out = {
            "kv_bytes_per_token": per_token,
            "kv_pages_total": pool.num_pages - 1,
            "kv_pages_allocated": pool.allocated_pages,
            "kv_pages_free": pool.free_pages,
            "kv_pages_high_water": pool.high_water,
            "kv_pool_bytes": self.pool_bytes,
            "prefix_cache_entries": len(prefixes) if prefixes is not None else 0,
            "prefix_cache_pages": prefixes.held_pages if prefixes is not None else 0,
            "prefix_cache_hits": prefixes.hits if prefixes is not None else 0,
            "prefix_cache_misses": prefixes.misses if prefixes is not None else 0,
            "kv_pages_cow_copies": self.cow_copies,
            # device calls `reserve` made to write table entries, and the entries they carried
            "kv_table_writes": self.table_writes,
            "kv_table_entries": self.table_entries,
        }
        if self.window_allocator is not None:
            out.update(
                kv_window_pages_total=self.window_allocator.num_pages - 1,
                kv_window_pages_high_water=self.window_allocator.high_water,
                kv_window_pages_released=self.window_pages_released,
                kv_window_pool_bytes=self.window_pool_bytes,
            )
        return out
