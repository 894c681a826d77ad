"""Host-side page accounting for the paged KV layout (vLLM
PagedAttention's block manager, TPU re-design).

The DEVICE side is dumb on purpose: a global page pool
`[L, n_pages, page_size, KV, hd]` plus per-slot page tables
(models/decode.py paged primitives). Everything stateful — which
physical pages a request owns, which are shared by how many readers,
when a shared page must copy-on-write — lives here, in plain Python,
where the engine already runs its admission bookkeeping. No device
traffic: the allocator hands out integers; the engine turns them into
table scatters and (rarely) page copies.

Sharing model: a page's refcount is the number of page RUNS that
reference it — a live request's table row counts one, a published
radix prefix run counts one. Prefix hits `share()` the matched run
(pure increments: the copy-free admission win), retire/cancel/crash
`free()` the request's run, radix eviction frees the published run.
A page is writable only at refcount 1; the engine calls `cow()`
before a request appends into a shared page, which hands back a
fresh page (and says whether a device copy is needed) so readers of
the original never observe the write.

Page 0 is the TRASH page: permanently allocated, never handed out,
never freed. Done/retired slots' table rows park on it so frozen
rewrites land where no live table reads.
"""

from typing import Dict, List, Tuple

import numpy as np

TRASH_PAGE = 0


class OutOfPages(RuntimeError):
    """The pool cannot satisfy an allocation — the scheduler's cue to
    evict unreferenced prefix runs or preempt-and-swap a request."""


class PageAllocator:
    """Ref-counted free-list allocator over `n_pages` physical pages
    of `page_size` cells. Deterministic: fresh pages come out in
    ascending id order, freed pages are reused LIFO — same inputs,
    same page ids, which keeps parity sweeps reproducible."""

    def __init__(self, n_pages: int, page_size: int):
        if n_pages < 2:
            raise ValueError(
                f"n_pages must be >= 2 (page 0 is the trash page), "
                f"got {n_pages}"
            )
        if page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        self.n_pages = n_pages
        self.page_size = page_size
        # ascending pop() order: the list is stored reversed
        self._free: List[int] = list(range(n_pages - 1, 0, -1))
        self._refs: Dict[int, int] = {}
        # counters (monotonic, for ServingMetrics)
        self.pages_allocated = 0
        self.pages_freed = 0
        self.pages_shared = 0
        self.cow_copies = 0
        self.pages_adopted = 0
        self.pages_promoted = 0

    # -- capacity ----------------------------------------------------

    @property
    def capacity(self) -> int:
        """Allocatable pages (trash excluded)."""
        return self.n_pages - 1

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def used_pages(self) -> int:
        return self.capacity - len(self._free)

    @property
    def shared_pages(self) -> int:
        """Pages with more than one referencing run."""
        return sum(1 for r in self._refs.values() if r > 1)

    def pages_for(self, cells: int) -> int:
        """Pages covering `cells` logical cells."""
        return max(1, -(-cells // self.page_size))

    # -- lifecycle ---------------------------------------------------

    def alloc(self, n: int) -> List[int]:
        """Hand out `n` fresh pages, each at refcount 1."""
        if n < 0:
            raise ValueError(f"alloc({n})")
        if n > len(self._free):
            raise OutOfPages(
                f"need {n} pages, {len(self._free)} free "
                f"of {self.capacity}"
            )
        pages = [self._free.pop() for _ in range(n)]
        for p in pages:
            self._refs[p] = 1
        self.pages_allocated += n
        return pages

    def adopt(self, n: int) -> List[int]:
        """THE page-run install entry point for cross-replica handoff
        (graftlint HANDOFF-001): reserve `n` fresh pages to receive a
        run shipped from a prefill replica. Accounting-wise this IS an
        alloc — each page comes out at refcount 1, owned exclusively
        by the adopting slot, so the one-CoW-site invariant holds with
        nothing to copy — but it is counted separately so the
        handoff-vs-local admission mix stays observable."""
        pages = self.alloc(n)
        self.pages_adopted += n
        return pages

    def promote(self, n: int) -> List[int]:
        """THE page-run install entry point for host-tier promotion
        (serving/kv_tier.py): reserve `n` fresh pages to receive a run
        uploaded from the host-DRAM tier. Accounting-wise this IS an
        alloc — each page comes out at refcount 1, owned by whichever
        run (radix republish or swapped-in slot) triggered the
        promotion, so the one-CoW-site invariant holds — but it is
        counted separately so PCIe-paid admissions stay observable
        next to cold prefills and cross-replica adoptions."""
        pages = self.alloc(n)
        self.pages_promoted += n
        return pages

    def share(self, pages: List[int]) -> None:
        """Add one referencing run to each page — a prefix hit. Pure
        increments: THE copy-free admission path."""
        for p in pages:
            if p == TRASH_PAGE:
                continue
            if p not in self._refs:
                raise ValueError(f"share of unallocated page {p}")
            self._refs[p] += 1
        self.pages_shared += len(pages)

    def free(self, pages: List[int]) -> None:
        """Drop one referencing run from each page; pages reaching
        refcount 0 return to the free list. Trash ids (a table row's
        dead tail) pass through unharmed."""
        for p in pages:
            if p == TRASH_PAGE:
                continue
            r = self._refs.get(p)
            if r is None:
                raise ValueError(f"double free of page {p}")
            if r == 1:
                del self._refs[p]
                self._free.append(p)
                self.pages_freed += 1
            else:
                self._refs[p] = r - 1

    def cow(self, page: int) -> Tuple[int, bool]:
        """Make `page` writable for ONE of its referencing runs.
        Exclusive already (refcount 1) → same page, no copy. Shared →
        detach this run (decref), allocate a fresh page at refcount 1
        and report that a device copy is required. Raises OutOfPages
        with the original page's refcount UNTOUCHED when the pool is
        dry — the caller evicts/preempts and retries."""
        r = self._refs.get(page)
        if r is None:
            raise ValueError(f"cow of unallocated page {page}")
        if r == 1:
            return page, False
        if not self._free:
            raise OutOfPages(
                f"cow of shared page {page}: pool dry "
                f"({self.capacity} pages)"
            )
        [fresh] = self.alloc(1)
        self._refs[page] = r - 1
        self.cow_copies += 1
        return fresh, True

    def refcount(self, page: int) -> int:
        return self._refs.get(page, 0)

    # -- invariants --------------------------------------------------

    def check(self) -> None:
        """Assert the accounting invariants (the property-fuzz hook):
        free and allocated partition the capacity, every refcount is
        positive, no id appears twice, trash is never tracked."""
        free_set = set(self._free)
        if len(free_set) != len(self._free):
            raise AssertionError("duplicate page in free list")
        if TRASH_PAGE in free_set or TRASH_PAGE in self._refs:
            raise AssertionError("trash page entered circulation")
        alloc_set = set(self._refs)
        if free_set & alloc_set:
            raise AssertionError(
                f"pages both free and allocated: {free_set & alloc_set}"
            )
        if len(free_set) + len(alloc_set) != self.capacity:
            raise AssertionError(
                f"page leak: {self.capacity - len(free_set) - len(alloc_set)} "
                "pages unaccounted for"
            )
        if any(r < 1 for r in self._refs.values()):
            raise AssertionError("non-positive refcount")

    def stats(self) -> Dict[str, float]:
        used = self.used_pages
        return {
            "n_pages": self.capacity,
            "page_size": self.page_size,
            "used_pages": used,
            "free_pages": self.free_pages,
            "occupancy": used / self.capacity if self.capacity else 0.0,
            "shared_pages": self.shared_pages,
            "shared_ratio": self.shared_pages / used if used else 0.0,
            "pages_allocated": self.pages_allocated,
            "pages_freed": self.pages_freed,
            "pages_shared": self.pages_shared,
            "cow_copies": self.cow_copies,
            "pages_adopted": self.pages_adopted,
            "pages_promoted": self.pages_promoted,
        }


class WindowRings:
    """Host-side page accounting of the WINDOW class of a model that
    mixes window and full attention layers: a window layer attends a
    slot's last `window` positions only, so a slot holds just the
    pages that still have one of them (and the pages the next
    dispatch will write). Each slot's table row is a RING of
    `ring_pages` entries: logical page p (cells [p * page_size, (p +
    1) * page_size)) lives at entry p % ring_pages, which is free
    again by the time page p + ring_pages is needed.

    `hold(slot, first_cell, last_cell)` makes a slot hold exactly the
    pages of those cells: the pages wholly behind `first_cell` go back
    to the class's allocator, the pages up to `last_cell` are
    allocated. The engine calls it for every live slot between
    dispatches (never per token); `table` (numpy, [n_slots,
    ring_pages], 0 = the class's trash page) rides into the next
    dispatch as it stands."""

    def __init__(self, allocator: PageAllocator, n_slots: int,
                 window: int, chunk: int):
        self.allocator = allocator
        self.window = window
        ps = allocator.page_size
        # the cells a dispatch of `chunk` steps reads and writes span
        # window + chunk - 1; unaligned, they touch one page more
        self.ring_pages = -(-(window + chunk - 1) // ps) + 1
        self.table = np.full(
            (n_slots, self.ring_pages), TRASH_PAGE, np.int32
        )
        # logical pages [lo, hi) each slot holds
        self.lo = [0] * n_slots
        self.hi = [0] * n_slots
        self.pages_freed_behind = 0  # monotonic: freed by a hold()

    def hold(self, slot: int, first_cell: int, last_cell: int) -> int:
        """Hold exactly the logical pages of cells [first_cell,
        last_cell]; returns how many pages went back to the
        allocator. Raises OutOfPages with the slot's holding
        consistent (what was allocated so far is held)."""
        ps = self.allocator.page_size
        lo, hi = first_cell // ps, last_cell // ps + 1
        if hi - lo > self.ring_pages:
            raise ValueError(
                f"cells [{first_cell}, {last_cell}] span {hi - lo} "
                f"pages, the ring has {self.ring_pages}"
            )
        row = self.table[slot]
        freed = 0
        for p in range(self.lo[slot], min(self.hi[slot], lo)):
            self.allocator.free([int(row[p % self.ring_pages])])
            row[p % self.ring_pages] = TRASH_PAGE
            freed += 1
        self.lo[slot] = lo
        self.hi[slot] = max(self.hi[slot], lo)
        self.pages_freed_behind += freed
        while self.hi[slot] < hi:
            [page] = self.allocator.alloc(1)
            row[self.hi[slot] % self.ring_pages] = page
            self.hi[slot] += 1
        return freed

    def release(self, slot: int) -> None:
        """Drop everything the slot holds (finish, cancel, preempt)."""
        row = self.table[slot]
        for p in range(self.lo[slot], self.hi[slot]):
            self.allocator.free([int(row[p % self.ring_pages])])
        row[:] = TRASH_PAGE
        self.lo[slot] = self.hi[slot] = 0

    def held(self, slot: int) -> int:
        return self.hi[slot] - self.lo[slot]

    @property
    def pages_held(self) -> int:
        return sum(h - l for l, h in zip(self.lo, self.hi))

    def check(self, slot: int, first_cell: int) -> None:
        """The window class's invariants for one slot: no page is held
        that lies wholly behind `first_cell` (the oldest cell a
        dispatch may still read), every held page has an entry, and
        no entry outside the held range is set."""
        ps = self.allocator.page_size
        if self.held(slot) and (self.lo[slot] + 1) * ps <= first_cell:
            raise AssertionError(
                f"slot {slot} holds page {self.lo[slot]}, wholly "
                f"behind cell {first_cell}"
            )
        held = {
            p % self.ring_pages
            for p in range(self.lo[slot], self.hi[slot])
        }
        for entry, page in enumerate(self.table[slot]):
            if (page != TRASH_PAGE) != (entry in held):
                raise AssertionError(
                    f"slot {slot} ring entry {entry} = {page}, held "
                    f"entries {sorted(held)}"
                )
            if page != TRASH_PAGE and self.allocator.refcount(
                int(page)
            ) != 1:
                raise AssertionError(
                    f"slot {slot} ring page {page} is not allocated"
                )
