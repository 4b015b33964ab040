"""KV-cache slot pool for generative serving (DESIGN.md §14).

One device-resident pytree holds the K/V cache for every in-flight
sequence: per layer, ``{"k", "v"}`` arrays shaped
``[num_slots + 1, max_len, width]`` (a position's heads side by side in
one ``width``-wide line: the form the device stores as written, see
models/gpt.py). Row ``s < num_slots`` is *slot s* — one sequence's
full-context cache, written in place by the prefill and decode
executables at positions ``< lengths[s]``. The extra last row is
the **scratch slot**: padded decode lanes (the slot ladder pads the
in-flight batch up to a compiled lane count) point their reads *and*
writes at it, so padding never perturbs a live sequence and never needs
a branch inside the compiled step.

The pool is the donation anchor of the decode loop: every compiled
prefill/decode call donates the previous pool buffers and returns the
next pool (``KVCachePool.swap``), so a long generation reuses one HBM
allocation with zero realloc — the compiled executables never see a new
shape and the compile cache never grows.

Host-side state (free list, per-slot lengths) is plain numpy owned by
the single scheduler thread in serving/generation.py; this class does no
locking of its own.

The model says what its cache is: the pool builds its leaves with
``model.init_cache(rows, dtype)`` and budgets with
``model.cache_bytes_per_row(dtype)`` (the cache protocol of
models/gpt.py; ``CausalLM`` answers ``{"k", "v"}`` lines of ``width``,
models/latent_moe.py one latent line a position), and never looks inside
a leaf beyond its leading rows. A family may keep more than one rows x
positions leaf a layer (models/latent_moe.py under an indexer: the latent
line and the index key), and leaves of different kinds and widths layer by
layer (its window layers keep a ring of a fixed number of positions, a
state leaf, beside the full layers' ``max_len`` rows).

Capacity is budgeted *before* allocation: ``cache_bytes`` multiplies
``model.cache_bytes_per_row`` by the row count, and on devices
that report allocator stats (``observability.hbm_stats``; None on CPU)
the constructor refuses pools that would exceed ``hbm_fraction`` of the
device limit — slot exhaustion must surface as queue backpressure
(``QueueFull``), never as an OOM mid-flight.

Paged variant (DESIGN.md §19): :class:`PagedKVCachePool` replaces the
per-slot ``max_len`` rectangle with a shared pool of fixed-size pages
plus a per-slot page table. A slot reserves only
``ceil((prompt + max_new_tokens) / page_size)`` pages at admission, so
a long-tail length mix fits in a fraction of the rectangular
reservation; page exhaustion surfaces exactly like slot exhaustion
(admission blocks, ``QueueFull`` backpressure upstream).
``kv_dtype="int8"`` stores pages as symmetric int8 codes with per-page
f32 scales (models/gpt.py, "Int8 KV pages") — same table machinery,
~4x the resident conversations per HBM byte at f32 compute, a stated
``scale/2``-per-cell error bound, and quantized blobs everywhere the
pool is treated as a pytree (host swap, prefix cache, fleet handoff).
:class:`PrefixCache` is the host-RAM side of the same machinery:
content-hashed KV prefixes (shared system prompts, parked/finished
conversations) are swapped out page-by-page and swapped back in on a
prefix match, skipping prefill for the cached span.
"""

from __future__ import annotations

import collections
from typing import Optional, Tuple

import numpy as np

from distkeras_tpu import observability, telemetry
from distkeras_tpu.models import gpt as gpt_lib


def state_leaves(model) -> Tuple[str, ...]:
    """Names of the cache leaves ``model`` declares as a state a row: no
    position axis, so no length mask hides what a step writes there
    (``cache_state_leaves``; DESIGN.md section 14). Empty for a family
    whose every leaf is rows x positions."""
    return tuple(getattr(model, "cache_state_leaves", ()))


def select_leaves(model) -> Tuple[str, ...]:
    """Names of the rows x positions cache leaves ``model`` declares as
    read by a selection (``cache_select_leaves``; DESIGN.md section 14):
    a top-k chooses before any mask, so the model itself keeps what lies
    past a lane's length from being chosen, and the engine serves it from
    the rectangular pool alone. Empty for a family that attends all it
    holds."""
    return tuple(getattr(model, "cache_select_leaves", ()))


class KVCachePool:
    """Slot pool + host-side accounting for one model's decode cache.

    Parameters
    ----------
    model: a model that keeps the cache protocol (``init_cache``,
        ``cache_bytes_per_row``, ``max_len``): ``CausalLM``,
        ``LatentMoELM``.
    num_slots: concurrent sequences the pool can hold. One extra scratch
        row is always added for padded decode lanes.
    device: optional ``jax.Device`` to place the pool on (default: JAX's
        default device).
    hbm_fraction: refuse to build a pool larger than this fraction of
        the device's reported memory limit (no-op on hosts where
        ``hbm_stats`` returns None, e.g. CPU).
    """

    def __init__(self, model, num_slots: int, *, device=None,
                 dtype=None, hbm_fraction: float = 0.8):
        if num_slots < 1:
            raise ValueError(f"num_slots must be >= 1, got {num_slots}")
        import jax

        self.num_slots = int(num_slots)
        self.max_len = int(model.max_len)
        per_row = model.cache_bytes_per_row(dtype)
        self.cache_bytes = per_row * (self.num_slots + 1)
        stats = observability.hbm_stats(device)
        if stats and stats.get("limit_bytes"):
            budget = hbm_fraction * stats["limit_bytes"]
            if self.cache_bytes > budget:
                raise ValueError(
                    f"KV cache pool needs {self.cache_bytes} bytes "
                    f"({self.num_slots}+1 rows x {per_row} B/row) but the "
                    f"budget is {int(budget)} B ({hbm_fraction:.0%} of the "
                    f"device limit {stats['limit_bytes']} B); lower "
                    f"num_slots or max_len")
        pool = model.init_cache(self.num_slots + 1, dtype)
        if device is not None:
            pool = jax.device_put(pool, device)
        #: live device pytree; replaced wholesale by swap() after every
        #: donated prefill/decode step
        self.pool = pool
        #: tokens cached per slot (prompt + fed-back generations);
        #: scheduler-thread-owned, index num_slots is the scratch row and
        #: stays 0
        self.lengths = np.zeros(self.num_slots + 1, np.int32)
        self._free = list(range(self.num_slots - 1, -1, -1))
        self._active = set()
        #: the part of ``cache_bytes`` in leaves the model declares as a
        #: state a row (``cache_state_leaves``): no position axis, never
        #: grows, whatever the context length
        state = set(state_leaves(model))
        self.state_bytes = sum(
            leaf.nbytes for path, leaf in
            jax.tree_util.tree_flatten_with_path(pool)[0]
            if path[-1].key in state)
        telemetry.gauge("serving.decode.cache_bytes").set(self.cache_bytes)
        telemetry.gauge("serving.decode.state_bytes").set(self.state_bytes)
        self._occupancy_g = telemetry.gauge("serving.decode.slot_occupancy")
        self._occupancy_g.set(0.0)

    # -- slot lifecycle ---------------------------------------------------

    @property
    def scratch_slot(self) -> int:
        """Row index padded decode lanes read/write (never a live slot)."""
        return self.num_slots

    @property
    def num_free(self) -> int:
        return len(self._free)

    @property
    def num_active(self) -> int:
        return len(self._active)

    def allocate(self) -> Optional[int]:
        """Claim a free slot (length reset to 0), or None when exhausted."""
        if not self._free:
            return None
        slot = self._free.pop()
        self._active.add(slot)
        self.lengths[slot] = 0
        self._occupancy_g.set(self.num_active / self.num_slots)
        return slot

    def free(self, slot: int) -> None:
        """Return a slot to the pool. Stale cache cells need no scrubbing:
        every read is masked by the slot's (reset) length."""
        if slot not in self._active:
            raise ValueError(f"slot {slot} is not allocated")
        self._active.remove(slot)
        self.lengths[slot] = 0
        self._free.append(slot)
        self._occupancy_g.set(self.num_active / self.num_slots)

    # -- device buffer handoff --------------------------------------------

    def swap(self, new_pool) -> None:
        """Install the pool returned by a donated prefill/decode call.
        The previous buffers were consumed by the executable; holding on
        to them would be a use-after-donate."""
        self.pool = new_pool


class PagedKVCachePool:
    """Page-granular KV pool: slot -> page-table indirection over a
    shared page pool (DESIGN.md §19).

    Device state is a per-layer ``{"k", "v"}`` pytree of
    ``[num_pages + 1, page_size, heads, head_dim]`` arrays
    (:func:`models.gpt.init_paged_cache`; the last page is scratch).
    Host state adds a ``[num_slots + 1, pages_per_slot]`` int32 page
    table whose unmapped entries point at the scratch page — padding
    lanes, ghost writes, and any write past a slot's reservation land
    there, never in a live page. The scratch slot's row is all-scratch
    and never mapped.

    A slot claims pages via :meth:`reserve` (all-or-nothing, sized to
    ``prompt + max_new_tokens``), not at :meth:`allocate` — that
    reservation, not ``num_slots * max_len``, is what HBM budgeting
    charges, which is the whole point: a long-tail length mix whose
    worst-case rectangle exceeds the budget fits comfortably in pages.

    Like :class:`KVCachePool` this does no locking; the scheduler
    thread owns it, and ``swap()`` installs each donated step's result.
    """

    def __init__(self, model, num_slots: int, *, page_size: int = 16,
                 num_pages: Optional[int] = None, device=None,
                 dtype=None, kv_dtype: Optional[str] = None,
                 hbm_fraction: float = 0.8):
        if num_slots < 1:
            raise ValueError(f"num_slots must be >= 1, got {num_slots}")
        if kv_dtype not in (None, "native", "int8"):
            raise ValueError(
                f"kv_dtype must be None, 'native', or 'int8', got "
                f"{kv_dtype!r}")
        import jax

        if not isinstance(model, gpt_lib.CausalLM):
            raise TypeError(
                f"PagedKVCachePool holds {{k, v}} pages of [page_size, "
                f"heads, head_dim] and serves CausalLM only; "
                f"{type(model).__name__} owns other cache leaves and has "
                f"no paged form yet: use the rectangular KVCachePool "
                f"(GenerationEngine without page_size)")
        #: page storage format — "native" (compute dtype) or "int8"
        #: (per-page affine codes + f32 scales, models/gpt.py
        #: quantize_kv_page); a pytree-shape property, so host swap,
        #: prefix cache, and fleet handoff ship whichever format the
        #: pool holds with no format-specific code
        self.kv_dtype = "int8" if kv_dtype == "int8" else "native"
        self.num_slots = int(num_slots)
        self.max_len = int(model.max_len)
        self.page_size = int(page_size)
        if self.page_size < 1 or self.max_len % self.page_size:
            raise ValueError(
                f"page_size must divide max_len ({self.max_len}), got "
                f"{self.page_size}")
        #: page-table width: pages a full-context slot needs
        self.pages_per_slot = self.max_len // self.page_size
        if num_pages is None:
            num_pages = self.num_slots * self.pages_per_slot
        self.num_pages = int(num_pages)
        if self.num_pages < self.pages_per_slot:
            raise ValueError(
                f"num_pages={self.num_pages} cannot back even one "
                f"full-context slot ({self.pages_per_slot} pages)")
        self.page_bytes = gpt_lib.page_bytes(model, self.page_size, dtype,
                                             kv_dtype=kv_dtype)
        self.cache_bytes = self.page_bytes * (self.num_pages + 1)
        #: bytes int8 pages save vs native-dtype pages at this pool's
        #: geometry (0 for native pools) — the capacity headline
        self.kv_quant_bytes_saved = 0
        if self.kv_dtype == "int8":
            native = gpt_lib.page_bytes(model, self.page_size, dtype)
            self.kv_quant_bytes_saved = (
                (native - self.page_bytes) * (self.num_pages + 1))
        stats = observability.hbm_stats(device)
        if stats and stats.get("limit_bytes"):
            budget = hbm_fraction * stats["limit_bytes"]
            if self.cache_bytes > budget:
                raise ValueError(
                    f"paged KV pool needs {self.cache_bytes} bytes "
                    f"({self.num_pages}+1 pages x {self.page_bytes} "
                    f"B/page) but the budget is {int(budget)} B "
                    f"({hbm_fraction:.0%} of the device limit); lower "
                    f"num_pages or page_size")
        pool = gpt_lib.init_paged_cache(model, self.num_pages,
                                        self.page_size, dtype,
                                        kv_dtype=kv_dtype)
        if device is not None:
            pool = jax.device_put(pool, device)
        #: live device pytree (the page pool); replaced wholesale by
        #: swap() after every donated step
        self.pool = pool
        self.lengths = np.zeros(self.num_slots + 1, np.int32)
        #: slot -> page-table rows; unmapped entries = scratch page
        self.page_tables = np.full(
            (self.num_slots + 1, self.pages_per_slot), self.scratch_page,
            np.int32)
        self._free = list(range(self.num_slots - 1, -1, -1))
        self._active = set()
        self._free_pages = list(range(self.num_pages - 1, -1, -1))
        self._reserved: dict = {}  # slot -> [page ids]
        telemetry.gauge("serving.decode.cache_bytes").set(self.cache_bytes)
        self._occupancy_g = telemetry.gauge("serving.decode.slot_occupancy")
        self._occupancy_g.set(0.0)
        self._pages_c = telemetry.counter(
            "serving.decode.paged.pages_allocated")
        self._page_occ_g = telemetry.gauge(
            "serving.decode.paged.page_occupancy")
        self._page_occ_g.set(0.0)
        if self.kv_dtype == "int8":
            telemetry.gauge(
                "serving.decode.paged.kv_quant_bytes_saved").set(
                    self.kv_quant_bytes_saved)

    # -- slot/page lifecycle ----------------------------------------------

    @property
    def scratch_page(self) -> int:
        """Physical page unmapped table entries and overflow writes hit."""
        return self.num_pages

    @property
    def scratch_slot(self) -> int:
        """Row index padded decode lanes read/write (never a live slot)."""
        return self.num_slots

    @property
    def num_free(self) -> int:
        return len(self._free)

    @property
    def num_active(self) -> int:
        return len(self._active)

    @property
    def free_pages(self) -> int:
        return len(self._free_pages)

    @property
    def pages_in_use(self) -> int:
        return self.num_pages - len(self._free_pages)

    def pages_for(self, tokens: int) -> int:
        """Pages a ``tokens``-long context occupies (ceil division)."""
        return -(-int(tokens) // self.page_size)

    def allocate(self) -> Optional[int]:
        """Claim a free slot (no pages yet — :meth:`reserve` follows),
        or None when exhausted."""
        if not self._free:
            return None
        slot = self._free.pop()
        self._active.add(slot)
        self.lengths[slot] = 0
        self._occupancy_g.set(self.num_active / self.num_slots)
        return slot

    def reserve(self, slot: int, tokens: int) -> bool:
        """All-or-nothing: map enough pages onto ``slot`` to hold
        ``tokens`` cells. False (nothing claimed) when the pool can't
        cover it — the scheduler leaves the request queued, which is the
        paged pool's backpressure. Writes past the reservation route to
        the scratch page (the table's unmapped tail), so a ghost or
        bucket-padding write can never corrupt another slot."""
        if slot not in self._active:
            raise ValueError(f"slot {slot} is not allocated")
        need = self.pages_for(tokens)
        if need > self.pages_per_slot:
            raise ValueError(
                f"{tokens} tokens need {need} pages, above the "
                f"{self.pages_per_slot}-page table width")
        have = len(self._reserved.get(slot, ()))
        grow = need - have
        if grow <= 0:
            return True
        if grow > len(self._free_pages):
            return False
        pages = [self._free_pages.pop() for _ in range(grow)]
        self._reserved.setdefault(slot, []).extend(pages)
        self.page_tables[slot, have:need] = pages
        self._pages_c.inc(grow)
        self._page_occ_g.set(self.pages_in_use / self.num_pages)
        return True

    def free(self, slot: int) -> None:
        """Return a slot and its pages. Stale page cells need no
        scrubbing: reads are masked by the (reset) length and cells are
        overwritten before the mask ever unhides them."""
        if slot not in self._active:
            raise ValueError(f"slot {slot} is not allocated")
        self._active.remove(slot)
        self.lengths[slot] = 0
        self._free_pages.extend(reversed(self._reserved.pop(slot, [])))
        self.page_tables[slot, :] = self.scratch_page
        self._free.append(slot)
        self._occupancy_g.set(self.num_active / self.num_slots)
        self._page_occ_g.set(self.pages_in_use / self.num_pages)

    def page_table_row(self, slot: int) -> np.ndarray:
        """Copy of ``slot``'s page-table row (what a compiled step gets)."""
        return self.page_tables[slot].copy()

    # -- device buffer handoff --------------------------------------------

    def swap(self, new_pool) -> None:
        """Install the page pool returned by a donated step call."""
        self.pool = new_pool


class _PrefixEntry:
    __slots__ = ("tokens", "length", "data", "last_logits", "nbytes")

    def __init__(self, tokens, length, data, last_logits, nbytes):
        self.tokens = tokens            # tuple of cached token ids
        self.length = length            # cached positions [0, length)
        self.data = data                # host page data (swap_out output)
        self.last_logits = last_logits  # np [V] after `tokens`, or None
        self.nbytes = nbytes


class PrefixCache:
    """Host-RAM KV prefix store: content-hashed reuse of prefill work
    (DESIGN.md §19).

    An entry is a token sequence plus the host copy of the pages that
    hold its K/V (captured by the engine's compiled ``swap_out``) and —
    when the entry covers a full request — the logits after its last
    token, so a full hit emits the first token with ZERO forward calls.
    Keys are ``hash(tokens[:L])`` per distinct cached length; lookup
    walks cached lengths longest-first and verifies actual token
    equality (a hash collision must degrade to a miss, never a wrong
    cache row). Eviction is LRU under ``budget_bytes`` of host RAM,
    charged at numpy buffer size.

    Owned by the scheduler thread like the pools; no locking.
    """

    def __init__(self, budget_bytes: int):
        if budget_bytes < 1:
            raise ValueError(
                f"budget_bytes must be >= 1, got {budget_bytes}")
        self.budget_bytes = int(budget_bytes)
        self.bytes = 0
        self._entries: "collections.OrderedDict" = collections.OrderedDict()
        self._lengths: collections.Counter = collections.Counter()
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self._hits_c = telemetry.counter("serving.decode.prefix.hits")
        self._misses_c = telemetry.counter("serving.decode.prefix.misses")
        self._evict_c = telemetry.counter("serving.decode.prefix.evictions")
        self._inserts_c = telemetry.counter("serving.decode.prefix.inserts")
        self._bytes_g = telemetry.gauge("serving.decode.prefix.bytes")
        self._bytes_g.set(0)
        self._rate_g = telemetry.gauge("serving.decode.prefix.hit_rate")

    def __len__(self) -> int:
        return len(self._entries)

    @property
    def hit_rate(self) -> float:
        total = self.hits + self.misses
        return self.hits / total if total else 0.0

    @staticmethod
    def _key(tokens) -> tuple:
        return (len(tokens), hash(tokens))

    def has(self, tokens) -> bool:
        """Exact-sequence membership (no hit/miss accounting, no LRU
        refresh) — the capture path's don't-repark check."""
        tokens = tuple(int(t) for t in tokens)
        entry = self._entries.get(self._key(tokens))
        return entry is not None and entry.tokens == tokens

    def peek(self, tokens) -> Optional[_PrefixEntry]:
        """Exact-sequence fetch with no hit/miss accounting and no LRU
        refresh — the fleet KV-handoff export path (DESIGN.md §22) reads
        an entry to ship it without perturbing the cache's own stats."""
        tokens = tuple(int(t) for t in tokens)
        entry = self._entries.get(self._key(tokens))
        if entry is not None and entry.tokens == tokens:
            return entry
        return None

    def lookup(self, prompt) -> Optional[_PrefixEntry]:
        """Longest cached prefix of ``prompt`` (LRU-refreshed), or None.
        Counted as a hit only when a prefix matches; the engine decides
        full-hit vs suffix-prefill from ``entry.length``."""
        prompt = tuple(int(t) for t in prompt)
        for ln in sorted({l for l in self._lengths if l <= len(prompt)},
                         reverse=True):
            key = self._key(prompt[:ln])
            entry = self._entries.get(key)
            if entry is not None and entry.tokens == prompt[:ln]:
                self._entries.move_to_end(key)
                self.hits += 1
                self._hits_c.inc()
                self._rate_g.set(self.hit_rate)
                return entry
        self.misses += 1
        self._misses_c.inc()
        self._rate_g.set(self.hit_rate)
        return None

    def insert(self, tokens, data, last_logits=None) -> None:
        """Store ``data`` (host page pytree from ``swap_out``) as the KV
        for ``tokens``; evicts LRU entries to stay under budget. An
        entry larger than the whole budget is refused (counted as an
        eviction of itself)."""
        tokens = tuple(int(t) for t in tokens)
        import jax

        nbytes = sum(np.asarray(leaf).nbytes
                     for leaf in jax.tree.leaves(data))
        if last_logits is not None:
            last_logits = np.asarray(last_logits)
            nbytes += last_logits.nbytes
        if nbytes > self.budget_bytes:
            self.evictions += 1
            self._evict_c.inc()
            return
        key = self._key(tokens)
        old = self._entries.pop(key, None)
        if old is not None:
            self.bytes -= old.nbytes
            self._lengths[old.length] -= 1
            if not self._lengths[old.length]:
                del self._lengths[old.length]
        while self.bytes + nbytes > self.budget_bytes and self._entries:
            self._evict_lru()
        self._entries[key] = _PrefixEntry(tokens, len(tokens), data,
                                          last_logits, nbytes)
        self._lengths[len(tokens)] += 1
        self.bytes += nbytes
        self._inserts_c.inc()
        self._bytes_g.set(self.bytes)

    def evict(self, entry: _PrefixEntry) -> None:
        """Drop one entry (the failed-swap-in path: a torn restore must
        not be offered again)."""
        key = self._key(entry.tokens)
        if self._entries.pop(key, None) is not None:
            self.bytes -= entry.nbytes
            self._lengths[entry.length] -= 1
            if not self._lengths[entry.length]:
                del self._lengths[entry.length]
            self.evictions += 1
            self._evict_c.inc()
            self._bytes_g.set(self.bytes)

    def _evict_lru(self) -> None:
        _key, entry = self._entries.popitem(last=False)
        self.bytes -= entry.nbytes
        self._lengths[entry.length] -= 1
        if not self._lengths[entry.length]:
            del self._lengths[entry.length]
        self.evictions += 1
        self._evict_c.inc()
        self._bytes_g.set(self.bytes)
