"""Paged KV-cache allocator for the serving runtime.

vLLM-style paged memory (PAPERS.md: Ragged Paged Attention, arXiv
2604.15464): the device KV cache is a fixed pool of ``num_pages`` pages
of ``page_size`` token slots each, laid out ``(kv_heads, num_pages,
page_size, head_dim)`` per layer (stored with rows that fill the 128
lanes where head_dim leaves lanes empty, ``KVCacheConfig.pool_shape``:
the form ops/pallas_kernels.py ``paged_attention`` consumes).
Sequences own PAGES, not a contiguous max-seq strip: appending a token
allocates a page only when the sequence's last page is full, finishing
a sequence returns its pages immediately — so pool capacity is bounded
by the sum of TRUE lengths, not ``batch * max_seq``.

The allocator here is pure host bookkeeping (page free list + per-
sequence page lists); the device pools live in the serving scope as
ordinary persistable vars that ``kv_cache_append`` updates in place
(the executor donates them to the step program, and the append kernel
writes its pages through an alias of the pool: ops/paged_ops.py).
All decisions are deterministic: pages are
handed out FIFO (fresh ids ascending, freed pages reused in free
order), so a seeded request trace yields a bit-identical allocation
sequence — the property the scheduler-determinism tests pin.

Exhaustion is BACKPRESSURE, not an error: ``append_tokens`` returns
``None`` (mutating nothing) when the pool cannot cover the request, and
the scheduler defers admission until pages free up.

Copy-on-write prefix caching (``FLAGS_kv_prefix_cache`` or the
``prefix_cache=`` ctor arg; off by default — the off path is
byte-identical to the plain allocator above, pinned by test):

* every page carries a **refcount**; a page is *owned* while any live
  sequence maps it, *cached* when its refcount reaches zero but its
  content is still indexed, *free* otherwise.  Frees only decrement;
  reclaim happens at refcount zero — never under a live sharer.
* pages are **immutable once full**: a full page is registered in the
  prefix index under a chained content digest (sha1 over the page's
  token ids, chained through every preceding page), and appends past
  it always open a new page.  The partial TAIL page of a prompt is
  indexed too (under ``(chain digest, tail-token tuple)``), so prefix
  hits are not quantized to page boundaries.
* ``match_prefix`` walks a new prompt through the index and
  ``acquire_prefix`` maps every already-cached page into the new
  sequence's block table at refcount+1 — the engine skips prefilling
  those tokens entirely.
* the first **write into a shared partial page forks it** (CoW): the
  writer gets a private copy-page, the fork is queued for the engine
  (``take_forks``) to replay as a device page copy before the step
  that writes runs, and every other sharer keeps the frozen original.
* refcount-0 cached pages are reclaimed only when the free list runs
  dry, in a **deterministic seeded eviction order** (free generation
  FIFO, ``crc32(seed:page)`` as the documented tiebreak), so a seeded
  trace replays bit-identically, eviction decisions included.

``stats()`` keeps every legacy key and adds a ``prefix_cache`` section
(hit tokens, forked/evicted pages, live shared pages, cached pages) —
all zeros when the feature is off.
"""
from __future__ import annotations

import hashlib
import zlib
from collections import deque
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np

__all__ = ["KVCacheConfig", "PagedKVCache"]

# the chip's vector tile: 128 lanes a row, rows in groups of 8
_LANES, _TILE_ROWS = 128, 8


@dataclass(frozen=True)
class KVCacheConfig:
    num_pages: int
    page_size: int
    num_kv_heads: int
    head_dim: int
    num_layers: int = 1
    dtype: str = "float32"
    # fixed-size per-sequence state beside the pages (a recurrent layer's
    # state): a live sequence owns one of ``state_slots`` slots, whatever
    # its length; the pools that hold them have one slot more, the
    # padding's (``pad_state_slot``).  0: the model keeps no such state
    state_slots: int = 0
    # a second GROUP of pages with a lifetime rule of its own: the layers
    # ``window_layers`` (indices among the cache's ``num_layers``) attend the
    # last ``window`` positions only, keep their rows in pools of
    # ``window_pages`` pages with their own free list and per-sequence table,
    # and give back the pages behind the window as a sequence advances.  The
    # other layers' group is the cache as it ever was.  0 / (): one group
    window: int = 0
    window_pages: int = 0
    window_layers: Tuple[int, ...] = ()

    @property
    def window_pages_per_seq(self) -> int:
        """The most pages a sequence holds in the window group: the pages
        that cover positions ``> ctx - window - page_size``."""
        return self.window // self.page_size + 2 if self.window else 0

    @property
    def window_pad_slot(self) -> int:
        """The window group's pad sentinel, past its pools' end."""
        return self.window_pages * self.page_size

    def groups(self) -> Dict[str, dict]:
        """The page groups by name: their layers, window (0: every position
        kept until the sequence ends) and pages."""
        held = tuple(i for i in range(self.num_layers)
                     if i not in self.window_layers)
        out = {"full": {"layers": held, "window": 0,
                        "pages": self.num_pages}}
        if self.window:
            out["window"] = {"layers": tuple(self.window_layers),
                             "window": self.window,
                             "pages": self.window_pages}
        return out

    @property
    def pad_state_slot(self) -> int:
        """The slot padded rows carry: the last of the state pools, owned
        by no sequence."""
        return self.state_slots

    @property
    def pad_slot(self) -> int:
        """Flat slot id past the pool end: ``kv_cache_append`` drops
        writes to it (mode='drop'), so bucket-padded positions are
        no-ops."""
        return self.num_pages * self.page_size

    @property
    def quantized(self) -> bool:
        """True when the pool dtype needs a parallel scale pool (int8:
        pages store ``round(x / scale * 127)`` per (kv_head, page))."""
        return self.dtype == "int8"

    @property
    def tokens_per_row(self) -> int:
        """Consecutive tokens of a page that share one stored row: ``128
        / head_dim`` where head_dim is under the 128 lanes, divides them,
        and a page is whole (8, 128) tiles (the tile the chip's compiler
        gives float32, bfloat16 and int8 pools alike, narrower types
        packed inside it); else 1, a row a token."""
        t = _LANES // self.head_dim if self.head_dim < _LANES \
            and _LANES % self.head_dim == 0 else 1
        whole = (self.page_size * self.head_dim) % (_TILE_ROWS * _LANES) == 0
        return t if whole else 1

    def pool_shape(self, window: bool = False):
        """The shape a pool is STORED in (scope, programs, kernels).
        Logically a pool is ``(kv_heads, num_pages, page_size,
        head_dim)`` — the contract of the allocator, its flat slots,
        the scale pools and page copies, all of which address whole
        pages or tokens by number.  Stored, its rows fill the lanes:
        ``tokens_per_row`` tokens side by side, ``(kv_heads, num_pages,
        page_size * head_dim / 128, 128)``, a row-major bitcast of the
        logical pool.  The chip's compiler holds that shape row-major
        in exact tiles by its own choice, which is where both pool
        kernels work; a head_dim-64 pool in the logical shape it holds
        page-minor, and ``paged_decode`` cost a re-layout of every pool
        on every call.  With ``tokens_per_row`` 1 (head_dim 128 and
        over, the tiny test models) the two shapes are one.  ``window``: a
        pool of the window group, ``window_pages`` pages."""
        t = self.tokens_per_row
        return (self.num_kv_heads,
                self.window_pages if window else self.num_pages,
                self.page_size // t, self.head_dim * t)

    def make_pool(self, window: bool = False) -> np.ndarray:
        """One zeroed host-side pool (K or V, one layer); the engine
        stages it to the device once via scope.set + device_put."""
        return np.zeros(self.pool_shape(window), dtype=self.dtype)

    def scale_shape(self):
        """Per-(kv_head, page) absmax scale pool (int8 only)."""
        return (self.num_kv_heads, self.num_pages)

    def make_scale_pool(self) -> np.ndarray:
        """Zeroed f32 scale pool — scale 0 marks a never-written page
        (``kv_cache_append`` raises it monotonically per page)."""
        return np.zeros(self.scale_shape(), dtype="float32")

    def scale_bytes(self) -> int:
        """Scale-pool bytes for ONE side (K or V) of ONE layer; 0 for
        unquantized dtypes (no scale pool exists)."""
        if not self.quantized:
            return 0
        return int(np.prod(self.scale_shape())) * 4


@dataclass
class _Seq:
    pages: List[int] = field(default_factory=list)
    length: int = 0  # tokens written
    # prefix-cache chain state (unused when the feature is off)
    digest: bytes = b""           # chain digest after the last FULL page
    tail: List[int] = field(default_factory=list)  # tokens in the tail page
    # full token history (prefix caching only, opaque sequences
    # excepted) — what lets truncate_tokens rewind the chain/index
    # state to ANY earlier length, not just page boundaries
    tokens: List[int] = field(default_factory=list)
    opaque: bool = False          # tokens unknown -> pages never indexed
    # acquired-but-uncommitted hit accounting (folded into the cache
    # counters at the first successful prefill slice — see
    # commit_prefix_hit — so blocked-admission acquire/release retries
    # never inflate the hit numbers)
    pending_hit: int = 0
    pending_shared: int = 0
    state_slot: Optional[int] = None   # its slot in the state pools
    # the window group: the pages that cover logical pages ``win_first ..
    # win_first + len(win_pages) - 1`` of the sequence
    win_pages: List[int] = field(default_factory=list)
    win_first: int = 0
    win_slots: Optional[np.ndarray] = None   # of the last append


def _chain(digest: bytes, tokens) -> bytes:
    """Chained page-content digest: deterministic across processes
    (hashlib, never the salted builtin hash)."""
    h = hashlib.sha1(digest)
    h.update(np.asarray(tokens, np.int64).tobytes())
    return h.digest()


class PagedKVCache:
    """Page allocator + per-sequence block tables (host side)."""

    def __init__(self, config: KVCacheConfig,
                 prefix_cache: Optional[bool] = None, seed: int = 0):
        self.config = config
        if prefix_cache is None:
            from ..utils.flags import flag

            prefix_cache = bool(flag("kv_prefix_cache", False))
        self.prefix_cache = bool(prefix_cache)
        if config.state_slots and self.prefix_cache:
            raise ValueError(
                "a cache with state slots takes no prefix cache: a recurrent "
                "state is not a function of a shared page, and sharing a "
                "prefix would need a snapshot of the state at its end")
        from ..utils import telemetry as tm

        # the allocator's instruments, resolved once and not by name at
        # every mutation (a decode step mutates once a sequence); each
        # is published from its first use, so the prefix and
        # quantization gauges exist only where engaged
        self._tm = tm.Handles(
            pages_in_use=("gauge", "kv_pool_pages_in_use",
                          "KV pages currently owned by live sequences"),
            utilization=("gauge", "kv_pool_utilization",
                         "fraction of KV pool pages in use"),
            fragmentation=("gauge", "kv_pool_fragmentation",
                           "fraction of owned KV slots holding no token "
                           "(tail-of-page waste)"),
            tokens_per_row=("gauge", "kv_pool_tokens_per_row",
                            "tokens side by side in one stored pool row "
                            "(over 1: the pool is stored lane-full)"),
            prefix_cached=("gauge", "kv_prefix_cached_pages",
                           "refcount-0 pages kept as evictable "
                           "prefix-cache entries"),
            prefix_shared=("gauge", "kv_prefix_shared_pages",
                           "pages currently mapped by more than one live "
                           "sequence"),
            quant_scale_bytes=("gauge", "kv_quant_scale_bytes",
                               "per-side per-layer scale-pool bytes "
                               "backing the quantized KV pool"),
            quant_capacity=("gauge", "kv_quant_capacity_tokens",
                            "token slots the quantized pool holds at its "
                            "fixed byte budget"),
            window_in_use=("gauge", "kv_window_pool_pages_in_use",
                           "pages of the window group owned by live "
                           "sequences"),
            alloc=("counter", "kv_pool_pages_alloc_total",
                   "KV pages handed out"),
            freed=("counter", "kv_pool_pages_freed_total",
                   "KV pages returned to the pool"))
        # fixed for the pool's life: says whether the lane-full form engaged
        self._tm.current().tokens_per_row.set(config.tokens_per_row)
        self.seed = int(seed)
        self._free: deque = deque(range(config.num_pages))
        self._seqs: Dict[object, _Seq] = {}
        # state slots: handed out lowest first, reused in free order
        self._free_slots: deque = deque(range(config.state_slots))
        self._stateful = config.state_slots > 0
        self.peak_state_slots = 0
        self.state_slots_preempted = 0
        # the window group: its own pages, handed out as the full group's
        if config.window and self.prefix_cache:
            raise ValueError(
                "a cache with a window group takes no prefix cache: a page "
                "freed behind the window cannot be shared by a later prompt")
        if config.window and config.window_pages < 1:
            raise ValueError("a cache with a window group needs window_pages")
        self._windowed = config.window > 0
        self._win_free: deque = deque(range(config.window_pages))
        self.peak_window_pages = 0
        self.freed_behind_window = 0
        # the sum of every live sequence's length, kept as they change:
        # ``fragmentation`` is published on every append, and summing the
        # sequences there made a decode step of n sequences n * n
        self._live_tokens = 0
        # CoW / prefix-index state (all empty — and untouched — when
        # prefix_cache is off, so the legacy path stays byte-identical)
        self._refs: Dict[int, int] = {}            # page -> refcount
        self._used: Dict[int, int] = {}            # page -> valid slots
        self._full_key: Dict[int, bytes] = {}      # page -> full digest
        self._index: Dict[bytes, int] = {}         # full digest -> page
        self._partials: Dict[bytes, Dict[int, tuple]] = {}
        self._page_partial: Dict[int, Tuple[bytes, tuple]] = {}
        self._cached_free: Dict[int, int] = {}     # page -> free generation
        self._free_gen = 0
        self._pending_forks: List[Tuple[int, int, int]] = []
        # counters for the serving report
        self.alloc_count = 0
        self.free_count = 0
        self.peak_pages = 0
        self.hit_tokens = 0
        self.forked_pages = 0
        self.evicted_pages = 0
        self.shared_acquires = 0

    # -- capacity ----------------------------------------------------------
    @property
    def num_free_pages(self) -> int:
        """Reclaimable pages: truly free plus refcount-0 cached pages
        (evictable on demand)."""
        return len(self._free) + len(self._cached_free)

    @property
    def pages_in_use(self) -> int:
        """DISTINCT pages owned by live sequences — a page shared by N
        sequences counts once (the invariant the memory planner's
        ``kv_pool`` reconciliation relies on)."""
        return self.config.num_pages - self.num_free_pages

    def utilization(self) -> float:
        """Fraction of pool pages currently owned by live sequences."""
        return self.pages_in_use / self.config.num_pages

    def fragmentation(self) -> float:
        """Internal fragmentation: fraction of owned slots holding no
        token (tail-of-page waste).  0.0 when nothing is allocated.
        Shared pages count their slots ONCE."""
        used_pages = self.pages_in_use
        if used_pages == 0:
            return 0.0
        if self.prefix_cache:
            tokens = sum(self._used.get(p, 0) for p in self._refs)
        else:
            tokens = self._live_tokens
        return 1.0 - tokens / (used_pages * self.config.page_size)

    def pages_needed(self, seq_id, n_tokens: int) -> int:
        """Fresh pages required to append n_tokens to seq_id (which may
        be new)."""
        s = self._seqs.get(seq_id)
        have = len(s.pages) if s else 0
        length = s.length if s else 0
        need = -(-(length + n_tokens) // self.config.page_size)  # ceil
        return max(0, need - have)

    def cow_fork_need(self, seq_id, n_tokens: int) -> int:
        """Extra pages a CoW fork would consume if ``n_tokens`` were
        appended now: 1 when the append would write into a SHARED
        partial tail page (the write forks it), else 0.  Always 0 with
        prefix caching off — safe to add into any capacity check."""
        if not self.prefix_cache or n_tokens <= 0:
            return 0
        s = self._seqs.get(seq_id)
        if s is None or not s.pages or s.length % self.config.page_size == 0:
            return 0
        return 1 if self._refs.get(s.pages[-1], 0) > 1 else 0

    def can_append(self, seq_id, n_tokens: int) -> bool:
        return (self.pages_needed(seq_id, n_tokens)
                + self.cow_fork_need(seq_id, n_tokens)
                <= self.num_free_pages) and (
                    not self._stateful or self.has_slot_for(seq_id)) and (
                    not self._windowed
                    or self.window_fits([(seq_id, n_tokens)]))

    # -- the window group ----------------------------------------------------
    def _window_span(self, length: int) -> Tuple[int, int]:
        """The logical pages ``[first, end)`` a sequence of ``length`` tokens
        holds in the window group: those that cover positions ``> length -
        window - page_size``."""
        ps = self.config.page_size
        return (max(0, length - self.config.window - ps + 1) // ps,
                -(-length // ps))

    def window_pages_needed(self, seq_id, n_tokens: int) -> int:
        """Net window-group pages appending ``n_tokens`` to ``seq_id`` takes
        from the free list: the fresh ones less those it gives back behind
        the window first (may be negative)."""
        s = self._seqs.get(seq_id)
        first, end = self._window_span((s.length if s else 0) + n_tokens)
        return (end - first) - (len(s.win_pages) if s else 0)

    @property
    def num_free_window_pages(self) -> int:
        return len(self._win_free)

    @property
    def window_pages_in_use(self) -> int:
        return self.config.window_pages - len(self._win_free)

    def window_fits(self, asks) -> bool:
        """Whether the window group covers every ``(seq_id, n_tokens)`` of
        ``asks`` appended together; True of a cache with one group."""
        if not self._windowed:
            return True
        return sum(self.window_pages_needed(sid, n) for sid, n in asks) \
            <= len(self._win_free)

    def _window_append(self, s: _Seq, old_len: int, n_tokens: int):
        """Advance ``s`` (its length already ``old_len + n_tokens``) in the
        window group: give back the pages behind the new window, take the
        fresh ones, and return the appended positions' flat slots in the
        group's pools; a position behind the window (the head of a long
        prompt) carries the group's pad sentinel and is never written."""
        ps = self.config.page_size
        first, end = self._window_span(s.length)
        drop = min(max(0, first - s.win_first), len(s.win_pages))
        if drop:
            self._win_free.extend(s.win_pages[:drop])
            del s.win_pages[:drop]
            self.freed_behind_window += drop
        if not s.win_pages:
            s.win_first = first
        else:
            s.win_first += drop
        for _ in range(end - (s.win_first + len(s.win_pages))):
            s.win_pages.append(self._win_free.popleft())
        self.peak_window_pages = max(self.peak_window_pages,
                                     self.window_pages_in_use)
        pos = old_len + np.arange(n_tokens)
        page = pos // ps - s.win_first
        held = page >= 0
        pages = np.asarray(s.win_pages, np.int64)
        slots = np.where(held, pages[np.where(held, page, 0)] * ps + pos % ps,
                         self.config.window_pad_slot)
        s.win_slots = slots.astype(np.int32)

    def window_slots(self, seq_id) -> np.ndarray:
        """The window group's flat slots of the positions the sequence's
        last ``append_tokens`` appended."""
        return self._seqs[seq_id].win_slots

    def window_first(self, seq_id) -> int:
        """The position of the first slot of the sequence's window table."""
        return self._seqs[seq_id].win_first * self.config.page_size

    def window_table(self, seq_id, width: int) -> np.ndarray:
        """The sequence's pages in the window group, entry 0 the page of
        position ``window_first``, padded to ``width`` with page 0."""
        pages = self._seqs[seq_id].win_pages
        out = np.zeros(width, np.int32)
        out[: len(pages)] = pages
        return out

    def num_window_pages_of(self, seq_id) -> int:
        return len(self._seqs[seq_id].win_pages)

    # -- state slots ---------------------------------------------------------
    def has_slot_for(self, seq_id) -> bool:
        """A sequence not yet live needs a free state slot (asked only of
        a cache that has slots: an engine hands out one a sequence of its
        full batch, so this guards a caller that admits beyond it)."""
        return seq_id in self._seqs or bool(self._free_slots)

    @property
    def state_slots_in_use(self) -> int:
        return self.config.state_slots - len(self._free_slots)

    def state_slot(self, seq_id) -> int:
        """The live sequence's slot in the state pools."""
        return self._seqs[seq_id].state_slot

    def _publish_gauges(self):
        """Pool state -> telemetry registry (r13): the gauges mirror
        what ``stats()`` computes, updated at every allocator mutation
        so a mid-run snapshot is never stale."""
        handles = self._tm.current()
        handles.pages_in_use.set(self.pages_in_use)
        handles.utilization.set(self.utilization())
        handles.fragmentation.set(self.fragmentation())
        if self._windowed:
            handles.window_in_use.set(self.window_pages_in_use)
        if self.prefix_cache:
            handles.prefix_cached.set(len(self._cached_free))
            handles.prefix_shared.set(
                sum(1 for r in self._refs.values() if r > 1))
        if self.config.dtype != "float32":
            # published only when quantization is engaged, so the
            # default-f32 gauge namespace stays byte-identical
            handles.quant_scale_bytes.set(self.config.scale_bytes())
            handles.quant_capacity.set(
                self.config.num_pages * self.config.page_size)

    # -- page pool internals ----------------------------------------------
    def _evict_key(self, page: int):
        """Deterministic seeded eviction order for refcount-0 cached
        pages: oldest free generation first; ``crc32(seed:page)`` is
        the (documented, seed-dependent) tiebreak — a pure function of
        (seed, free order, page id), so replays evict identically."""
        return (self._cached_free[page],
                zlib.crc32(f"{self.seed}:{page}".encode()))

    def _take_page(self) -> int:
        """One free page, evicting the oldest cached page when the free
        list is dry.  The caller checked capacity."""
        if self._free:
            return self._free.popleft()
        page = min(self._cached_free, key=self._evict_key)
        del self._cached_free[page]
        self._drop_index(page)
        self._used.pop(page, None)
        self.evicted_pages += 1
        from ..utils import telemetry as tm

        tm.counter("kv_prefix_evicted_total",
                   "cached prefix pages evicted to satisfy fresh "
                   "allocations").inc()
        return page

    def _drop_index(self, page: int):
        d = self._full_key.pop(page, None)
        if d is not None and self._index.get(d) == page:
            del self._index[d]
        self._unregister_partial(page)

    def _unregister_partial(self, page: int):
        pp = self._page_partial.pop(page, None)
        if pp is not None:
            digest, _ = pp
            m = self._partials.get(digest)
            if m is not None:
                m.pop(page, None)
                if not m:
                    del self._partials[digest]

    def _register_chain(self, s: _Seq, tokens):
        """Advance the sequence's chain state by ``tokens`` (the tokens
        just appended) and register newly-full pages (immutable from
        now on) plus the new partial tail in the prefix index."""
        buf = s.tail + [int(t) for t in tokens]
        ps = self.config.page_size
        # page index the buffered tokens start at == count of pages the
        # chain already covers (s.length was updated by the caller)
        page_i = (s.length - len(buf)) // ps
        while len(buf) >= ps:
            chunk, buf = buf[:ps], buf[ps:]
            d = _chain(s.digest, chunk)
            page = s.pages[page_i]
            self._unregister_partial(page)
            if page not in self._full_key and d not in self._index:
                self._full_key[page] = d
                self._index[d] = page
            s.digest = d
            page_i += 1
        s.tail = buf
        if buf:
            page = s.pages[page_i]
            # the tail page is exclusively owned here (a write into a
            # shared page forked first), so its entry can be refreshed
            self._unregister_partial(page)
            tup = tuple(buf)
            self._partials.setdefault(s.digest, {})[page] = tup
            self._page_partial[page] = (s.digest, tup)

    # -- lifecycle ---------------------------------------------------------
    def append_tokens(self, seq_id, n_tokens: int,
                      tokens=None) -> Optional[np.ndarray]:
        """Reserve slots for n_tokens appended to seq_id (creating it on
        first touch) and return their flat slot ids ``(n_tokens,)``
        int32 for ``kv_cache_append``'s SlotMapping.  Returns None —
        with NO state change — when the pool can't cover it
        (admission backpressure).

        ``tokens`` (prefix caching only) are the token ids being
        appended: they feed the content index so the pages become
        shareable.  ``tokens=None`` marks the sequence OPAQUE — its
        pages are never indexed (chaos pool spikes, callers that don't
        know content)."""
        if tokens is not None:
            tokens = list(tokens)
            if len(tokens) != n_tokens:
                raise ValueError(
                    f"append_tokens: {len(tokens)} token ids for "
                    f"{n_tokens} slots")
        need = self.pages_needed(seq_id, n_tokens)
        fork = self.cow_fork_need(seq_id, n_tokens)
        if need + fork > self.num_free_pages or (
                self._stateful and not self.has_slot_for(seq_id)) or (
                self._windowed
                and not self.window_fits([(seq_id, n_tokens)])):
            return None
        s = self._seqs.get(seq_id)
        if s is None:
            s = self._seqs[seq_id] = _Seq()
            if self._stateful:
                s.state_slot = self._free_slots.popleft()
                self.peak_state_slots = max(self.peak_state_slots,
                                            self.state_slots_in_use)
        ps = self.config.page_size
        if self.prefix_cache:
            if tokens is None and n_tokens:
                if not s.opaque:
                    s.opaque = True
                    if s.pages and s.length % ps:
                        # stale partial entry: content will change
                        self._unregister_partial(s.pages[-1])
            if fork:
                src = s.pages[-1]
                dst = self._take_page()
                self._refs[src] -= 1
                self._refs[dst] = 1
                keep = s.length % ps
                self._used[dst] = keep
                s.pages[-1] = dst
                self._pending_forks.append((src, dst, keep))
                self.forked_pages += 1
                self.alloc_count += 1
                from ..utils import telemetry as tm

                tm.counter("kv_prefix_forked_total",
                           "shared partial pages forked on first write "
                           "(copy-on-write)").inc()
            elif (n_tokens and s.pages and s.length % ps
                    and not s.opaque):
                # exclusive tail about to change: retire the stale entry
                # (re-registered with the new content below)
                self._unregister_partial(s.pages[-1])
        for _ in range(need):
            page = self._take_page()
            s.pages.append(page)
            self._refs[page] = 1
            self.alloc_count += 1
        self.peak_pages = max(self.peak_pages, self.pages_in_use)
        if need:
            self._tm.current().alloc.inc(need)
        if n_tokens > 32:
            # a prompt: the same slots as the loop below, in one pass
            pos = s.length + np.arange(n_tokens)
            first = s.length // ps
            pages = np.asarray(s.pages[first:], np.int64)
            slots = (pages[pos // ps - first] * ps + pos % ps) \
                .astype(np.int32)
        else:
            slots = np.empty(n_tokens, np.int32)
            for j in range(n_tokens):
                pos = s.length + j
                slots[j] = s.pages[pos // ps] * ps + pos % ps
        s.length += n_tokens
        self._live_tokens += n_tokens
        if self._windowed:
            self._window_append(s, s.length - n_tokens, n_tokens)
        if self.prefix_cache:
            # only pages covering the appended range can change — a
            # whole-sequence rescan here would be O(len^2) host work
            # over a sequence's life on the decode hot path
            for i in range((s.length - n_tokens) // ps, len(s.pages)):
                if s.length > i * ps:
                    self._used[s.pages[i]] = \
                        max(self._used.get(s.pages[i], 0),
                            min(ps, s.length - i * ps))
            if tokens is not None and not s.opaque and n_tokens:
                s.tokens.extend(int(t) for t in tokens)
                self._register_chain(s, tokens)
        # after the length update, and on EVERY append (a within-page
        # append changes fragmentation too)
        self._publish_gauges()
        return slots

    # -- prefix cache ------------------------------------------------------
    def match_prefix(self, tokens) -> Tuple[int, List[int]]:
        """Longest already-cached prefix of ``tokens``: the number of
        covered tokens and the pages holding them (full pages via the
        chain index, then at most one partial tail page whose frozen
        content is a prefix of the remainder).  Read-only; the caller
        decides how much of the match to ``acquire_prefix``."""
        if not self.prefix_cache or not len(tokens):
            return 0, []
        ps = self.config.page_size
        toks = [int(t) for t in tokens]
        digest, i, pages = b"", 0, []
        while i + ps <= len(toks):
            d = _chain(digest, toks[i:i + ps])
            page = self._index.get(d)
            if page is None:
                break
            pages.append(page)
            digest = d
            i += ps
        best = None
        for page, tup in (self._partials.get(digest) or {}).items():
            if (0 < len(tup) <= len(toks) - i
                    and tuple(toks[i:i + len(tup)]) == tup):
                key = (len(tup), -page)   # longest, then lowest page id
                if best is None or key > best[0]:
                    best = (key, page, tup)
        if best is not None:
            pages.append(best[1])
            i += len(best[2])
        return i, pages

    def acquire_prefix(self, seq_id, tokens, pages: List[int]) -> int:
        """Map an exact ``match_prefix`` result into a NEW sequence's
        block table at refcount+1 (resurrecting refcount-0 cached pages
        from the evictable set).  ``tokens`` are the covered prompt
        tokens (``prompt[:hit]``).  Returns the hit length."""
        assert seq_id not in self._seqs, f"sequence {seq_id!r} exists"
        hit = len(tokens)
        if not hit:
            return 0
        s = _Seq()
        self._seqs[seq_id] = s
        for page in pages:
            prev = self._refs.get(page, 0)
            if prev == 0:
                self._cached_free.pop(page, None)
            else:
                s.pending_shared += 1
            self._refs[page] = prev + 1
        s.pages = list(pages)
        self._live_tokens += hit - s.length
        s.length = hit
        s.tokens = [int(t) for t in tokens]
        s.pending_hit = hit
        ps = self.config.page_size
        n_full = len(pages) if hit % ps == 0 else len(pages) - 1
        s.digest = self._full_key[pages[n_full - 1]] if n_full else b""
        s.tail = [int(t) for t in tokens[n_full * ps:]]
        self.peak_pages = max(self.peak_pages, self.pages_in_use)
        self._publish_gauges()
        return hit

    def commit_prefix_hit(self, seq_id):
        """Fold the sequence's acquired-prefix stats into the cache
        counters.  The engine calls this at the FIRST prefill slice
        that actually lands, so an acquire that gets released again
        (admission blocked, retried next step) never counts as a hit."""
        s = self._seqs.get(seq_id)
        if s is None or not s.pending_hit:
            return
        hit, s.pending_hit = s.pending_hit, 0
        shared, s.pending_shared = s.pending_shared, 0
        self.hit_tokens += hit
        self.shared_acquires += shared
        from ..utils import telemetry as tm

        tm.counter("kv_prefix_hit_tokens_total",
                   "prompt tokens served from cached prefix pages "
                   "(prefill skipped)").inc(hit)

    def truncate_tokens(self, seq_id, n_tokens: int):
        """Roll back the LAST ``n_tokens`` of ``seq_id`` — the
        spec-decode reject path: drafted tokens whose verify failed are
        un-appended so the next append re-writes their slots.  Pages
        are append-only (r19), so device-side this is free; host-side
        it pops now-empty pages (refcount decrement, exactly the
        free_sequence reclaim rules) and rewinds the prefix chain/index
        state to the kept length using the sequence's token history.

        A kept partial tail page that is EXCLUSIVELY owned gets its
        stale index entries dropped and its kept content re-registered
        (future appends will overwrite the rejected slots); a shared
        tail page stays frozen — the CoW fork rules already cover the
        next write into it."""
        if n_tokens <= 0:
            return
        if self.config.state_slots:
            raise ValueError("truncate_tokens: a cache with state slots "
                             "cannot roll a sequence back (its state holds "
                             "the tokens to be dropped)")
        if self._windowed:
            raise ValueError("truncate_tokens: a cache with a window group "
                             "cannot roll a sequence back (a page freed "
                             "behind the window does not come back)")
        s = self._seqs[seq_id]
        if n_tokens > s.length:
            raise ValueError(
                f"truncate_tokens: {n_tokens} > length {s.length} of "
                f"sequence {seq_id!r}")
        ps = self.config.page_size
        new_len = s.length - n_tokens
        keep = -(-new_len // ps)  # ceil
        dropped, s.pages = s.pages[keep:], s.pages[:keep]
        released = 0
        for page in dropped:
            self._refs[page] = self._refs.get(page, 1) - 1
            if self._refs[page] <= 0:
                self._refs.pop(page, None)
                released += 1
                if self.prefix_cache and (page in self._full_key
                                          or page in self._page_partial):
                    self._free_gen += 1
                    self._cached_free[page] = self._free_gen
                else:
                    self._free.append(page)
                    if self.prefix_cache:
                        self._used.pop(page, None)
        self._live_tokens -= s.length - new_len
        s.length = new_len
        if self.prefix_cache and not s.opaque:
            s.tokens = s.tokens[:new_len]
            n_full = new_len // ps
            digest = b""
            for i in range(n_full):
                digest = _chain(digest, s.tokens[i * ps:(i + 1) * ps])
            s.digest = digest
            s.tail = list(s.tokens[n_full * ps:])
            if s.tail and self._refs.get(s.pages[-1], 0) == 1:
                page = s.pages[-1]
                self._drop_index(page)
                tup = tuple(s.tail)
                self._partials.setdefault(s.digest, {})[page] = tup
                self._page_partial[page] = (s.digest, tup)
                self._used[page] = len(s.tail)
        elif self.prefix_cache and s.opaque:
            if s.pages and new_len % ps \
                    and self._refs.get(s.pages[-1], 0) == 1:
                self._used[s.pages[-1]] = new_len % ps
        if released:
            self.free_count += released
            self._tm.current().freed.inc(released)
        self._publish_gauges()

    def take_forks(self) -> List[Tuple[int, int, int]]:
        """Drain pending CoW forks as ``(src_page, dst_page, used)``
        triples.  The engine must replay each as a device page copy
        BEFORE running the program that writes the forked page."""
        out, self._pending_forks = self._pending_forks, []
        return out

    def free_sequence(self, seq_id, preempted: bool = False):
        """Decrement the sequence's page refcounts; a page is reclaimed
        only at refcount zero (indexed pages park in the evictable
        cached set, the rest return to the free list — free-on-finish
        order unchanged).  Its state slot returns with them (the next
        owner's prefill rewrites the whole slot); ``preempted`` counts it
        as freed by a preemption."""
        s = self._seqs.pop(seq_id, None)
        if s is None:
            return
        if s.state_slot is not None:
            self._free_slots.append(s.state_slot)
            self.state_slots_preempted += bool(preempted)
        if s.win_pages:
            self._win_free.extend(s.win_pages)
        self._live_tokens -= s.length
        released = 0
        for page in s.pages:
            self._refs[page] = self._refs.get(page, 1) - 1
            if self._refs[page] <= 0:
                self._refs.pop(page, None)
                released += 1
                if self.prefix_cache and (page in self._full_key
                                          or page in self._page_partial):
                    self._free_gen += 1
                    self._cached_free[page] = self._free_gen
                else:
                    self._free.append(page)
                    if self.prefix_cache:
                        self._used.pop(page, None)
        self.free_count += released
        if released:
            self._tm.current().freed.inc(released)
            self._publish_gauges()

    # -- views for the decode step ----------------------------------------
    def context_len(self, seq_id) -> int:
        return self._seqs[seq_id].length

    def num_pages_of(self, seq_id) -> int:
        return len(self._seqs[seq_id].pages)

    def block_table(self, seq_id, width: int) -> np.ndarray:
        """The sequence's page ids padded to ``width`` with page 0 (a
        valid page — padded entries are masked by ContextLens, never
        read meaningfully)."""
        pages = self._seqs[seq_id].pages
        if len(pages) > width:
            raise ValueError(
                f"block table width {width} < {len(pages)} pages of "
                f"sequence {seq_id!r}")
        out = np.zeros(width, np.int32)
        out[: len(pages)] = pages
        return out

    def live_sequences(self) -> List:
        return list(self._seqs)

    def refcount(self, page: int) -> int:
        """Live-sequence references to a page (0 = free or cached)."""
        return self._refs.get(page, 0)

    def stats(self) -> dict:
        slots = {"state_slots": {
            "total": self.config.state_slots,
            "in_use": self.state_slots_in_use,
            "peak": self.peak_state_slots,
            "freed_by_preemption": self.state_slots_preempted}} \
            if self.config.state_slots else {}
        if self._windowed:
            # each group's own count; the keys below stay the full group's
            slots["groups"] = {
                "full": {"layers": len(self.config.groups()["full"]["layers"]),
                         "window": 0, "pages_total": self.config.num_pages,
                         "pages_in_use": self.pages_in_use,
                         "peak_pages": self.peak_pages,
                         "freed_behind_window": 0},
                "window": {"layers": len(self.config.window_layers),
                           "window": self.config.window,
                           "pages_total": self.config.window_pages,
                           "pages_in_use": self.window_pages_in_use,
                           "peak_pages": self.peak_window_pages,
                           "freed_behind_window": self.freed_behind_window}}
        return {
            **slots,
            "dtype": self.config.dtype,
            "pool_stored_shape": list(self.config.pool_shape()),
            "pool_tokens_per_row": self.config.tokens_per_row,
            "scale_bytes": self.config.scale_bytes(),
            "effective_capacity_tokens":
                self.config.num_pages * self.config.page_size,
            "pages_total": self.config.num_pages,
            "pages_in_use": self.pages_in_use,
            "peak_pages": self.peak_pages,
            "utilization": self.utilization(),
            "fragmentation": self.fragmentation(),
            "alloc_count": self.alloc_count,
            "free_count": self.free_count,
            "prefix_cache": {
                "enabled": self.prefix_cache,
                "hit_tokens": self.hit_tokens,
                "forked_pages": self.forked_pages,
                "evicted_pages": self.evicted_pages,
                "shared_acquires": self.shared_acquires,
                "cached_pages": len(self._cached_free),
                "shared_pages": sum(1 for r in self._refs.values()
                                    if r > 1),
            },
        }
