"""PageAllocator property fuzz: the host-side ref-count accounting
under random alloc/share/free/cow interleavings, plus the fixed
invariants the engine's admission paths rely on (trash page, LIFO
reuse determinism, OutOfPages rollback)."""

import numpy as np
import pytest

from dlrover_tpu.serving.paged_kv import (
    TRASH_PAGE,
    OutOfPages,
    PageAllocator,
    WindowRings,
)

pytestmark = pytest.mark.paged


def test_ctor_validation():
    with pytest.raises(ValueError):
        PageAllocator(1, 8)       # no room beside the trash page
    with pytest.raises(ValueError):
        PageAllocator(4, 0)


def test_alloc_free_roundtrip():
    a = PageAllocator(5, 8)
    assert a.capacity == 4
    pages = a.alloc(4)
    assert sorted(pages) == [1, 2, 3, 4]
    assert TRASH_PAGE not in pages
    assert a.free_pages == 0
    with pytest.raises(OutOfPages):
        a.alloc(1)
    a.free(pages)
    assert a.free_pages == 4
    a.check()


def test_fresh_pages_ascend_and_reuse_is_lifo():
    """Determinism contract: same op sequence, same page ids."""
    a = PageAllocator(8, 8)
    first = a.alloc(3)
    assert first == [1, 2, 3]
    a.free([2])
    assert a.alloc(1) == [2]          # LIFO reuse
    assert a.alloc(1) == [4]          # then ascending fresh
    a.check()


def test_share_and_cow():
    a = PageAllocator(6, 8)
    run = a.alloc(2)
    a.share(run)                       # published prefix run
    assert a.refcount(run[0]) == 2
    assert a.shared_pages == 2
    fresh, copied = a.cow(run[0])
    assert copied and fresh not in run
    assert a.refcount(run[0]) == 1     # reader keeps the original
    assert a.refcount(fresh) == 1
    same, copied = a.cow(fresh)        # exclusive: no copy
    assert same == fresh and not copied
    a.check()


def test_trash_page_passes_through():
    a = PageAllocator(4, 8)
    a.share([TRASH_PAGE, TRASH_PAGE])  # dead table-row tail
    a.free([TRASH_PAGE])
    a.check()
    with pytest.raises(ValueError):
        a.free([TRASH_PAGE + 1])       # never allocated


def test_double_free_and_bad_share_raise():
    a = PageAllocator(4, 8)
    [p] = a.alloc(1)
    a.free([p])
    with pytest.raises(ValueError):
        a.free([p])
    with pytest.raises(ValueError):
        a.share([p])


def test_cow_oom_leaves_refcount_untouched():
    """The engine retries cow() after reclaiming; a failed attempt
    must not have detached the run."""
    a = PageAllocator(3, 8)
    run = a.alloc(2)                   # pool now dry
    a.share([run[0]])
    with pytest.raises(OutOfPages):
        a.cow(run[0])
    assert a.refcount(run[0]) == 2
    a.check()


def test_property_fuzz_random_ops():
    """1k random alloc/share/free/cow ops against a mirror model;
    check() after every op. The mirror tracks refcounts per page-run
    exactly as the engine does (slot runs + published runs)."""
    rng = np.random.default_rng(0)
    a = PageAllocator(17, 8)
    runs = []                          # live page runs (slot or radix)
    for step in range(1000):
        op = rng.integers(0, 4)
        if op == 0:                    # admission: alloc a run
            n = int(rng.integers(1, 5))
            try:
                runs.append(a.alloc(n))
            except OutOfPages:
                assert a.free_pages < n
        elif op == 1 and runs:         # publish/hit: share a run
            run = runs[int(rng.integers(len(runs)))]
            a.share(run)
            runs.append(list(run))
        elif op == 2 and runs:         # retire/evict: free a run
            run = runs.pop(int(rng.integers(len(runs))))
            a.free(run)
        elif op == 3 and runs:         # frontier CoW on a run's page
            run = runs[int(rng.integers(len(runs)))]
            i = int(rng.integers(len(run)))
            try:
                fresh, copied = a.cow(run[i])
                run[i] = fresh
            except OutOfPages:
                assert a.free_pages == 0
        a.check()
        # cross-check aggregate accounting against the mirror
        refs = {}
        for run in runs:
            for p in run:
                refs[p] = refs.get(p, 0) + 1
        assert a.used_pages == len(refs)
        assert a.shared_pages == sum(1 for r in refs.values() if r > 1)
        for p, r in refs.items():
            assert a.refcount(p) == r
    assert a.pages_allocated >= a.pages_freed
    # crash-evacuate: restart frees every run; nothing may leak
    for run in runs:
        a.free(run)
    assert a.used_pages == 0
    assert a.free_pages == a.capacity
    a.check()


@pytest.mark.parametrize("seed,chunk", [(0, 1), (1, 4), (2, 8)])
def test_property_fuzz_two_classes(seed, chunk):
    """The property test over TWO classes of pages, as a model with
    window and full layers holds them: slots admit (a run of the full
    class for the request's limit, the window class's pages of the
    prompt's last `window` cells), advance by up to `chunk` positions
    a dispatch, finish or are preempted. After every step: no page of
    the window class is held that lies wholly behind the window, none
    was freed that does not (every cell a dispatch may read is still
    mapped, to a page no other slot holds), and both allocators'
    `check()` hold."""
    rng = np.random.default_rng(seed)
    ps, window, n_slots, max_len = 4, 10, 4, 96
    full = PageAllocator(n_slots * (max_len // ps) + 1, ps)
    probe = WindowRings(PageAllocator(2, ps), 0, window, chunk)
    win = PageAllocator(n_slots * probe.ring_pages + 1, ps)
    rings = WindowRings(win, n_slots, window, chunk)
    assert rings.ring_pages * ps >= window + chunk - 1 + (ps - 1)
    slots = {}  # slot -> dict(pos, limit, run)
    freed_total = 0

    def readable(pos):
        return max(pos - window + 1, 0)

    for _ in range(1500):
        op = rng.integers(0, 4)
        free = [s for s in range(n_slots) if s not in slots]
        if op == 0 and free:            # admit
            slot = free[0]
            p = int(rng.integers(1, 50))
            limit = min(p + int(rng.integers(1, 40)), max_len)
            run = full.alloc((limit - 1) // ps + 1)
            rings.hold(slot, max(p - window, 0), p - 1)
            slots[slot] = dict(pos=p - 1, limit=limit, run=run)
        elif op in (1, 2) and slots:    # a dispatch of k steps
            k = int(rng.choice([1, 2, 4, 8][: 1 + int(np.log2(chunk))]))
            for slot, st in list(slots.items()):
                first = readable(st["pos"])
                last = min(st["pos"] + k - 1, st["limit"] - 1)
                freed = rings.hold(slot, first, last)
                freed_total += freed
                # nothing behind the window is held ...
                rings.check(slot, first)
                assert rings.lo[slot] == first // ps
                # ... and nothing inside it was freed: every cell the
                # k steps read or write maps to a page of its own
                pages = {
                    int(rings.table[slot, (c // ps) % rings.ring_pages])
                    for c in range(first, last + 1)
                }
                assert TRASH_PAGE not in pages
                assert len(pages) == last // ps - first // ps + 1
                st["pos"] = min(st["pos"] + k, st["limit"] - 1)
                if st["pos"] + 1 >= st["limit"]:   # finished
                    full.free(st["run"])
                    rings.release(slot)
                    del slots[slot]
        elif op == 3 and slots:         # preempt (or cancel) one
            slot = int(rng.choice(list(slots)))
            full.free(slots.pop(slot)["run"])
            rings.release(slot)
        full.check()
        win.check()
        held = [
            int(p) for row in rings.table for p in row if p != TRASH_PAGE
        ]
        assert len(held) == len(set(held)) == win.used_pages
        assert rings.pages_held == win.used_pages
        assert full.used_pages == sum(len(s["run"]) for s in slots.values())
    assert freed_total == rings.pages_freed_behind > 100
    for slot, st in slots.items():
        full.free(st["run"])
        rings.release(slot)
    assert win.used_pages == 0 and full.used_pages == 0
    full.check()
    win.check()


def test_window_rings_refuse_a_span_wider_than_the_ring():
    rings = WindowRings(PageAllocator(32, 4), 1, window=10, chunk=4)
    with pytest.raises(ValueError, match="ring"):
        rings.hold(0, 0, rings.ring_pages * 4)


def test_pages_for():
    a = PageAllocator(4, 16)
    assert a.pages_for(0) == 1
    assert a.pages_for(16) == 1
    assert a.pages_for(17) == 2
    assert a.pages_for(160) == 10


def test_stats_keys():
    a = PageAllocator(5, 8)
    a.alloc(2)
    s = a.stats()
    for key in (
        "n_pages", "page_size", "used_pages", "free_pages",
        "occupancy", "shared_pages", "shared_ratio",
        "pages_allocated", "pages_freed", "pages_shared", "cow_copies",
    ):
        assert key in s
    assert s["occupancy"] == 0.5
