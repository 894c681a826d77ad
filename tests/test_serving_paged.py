"""Paged KV engine vs the dense oracle: byte parity across every
feature combination, copy-free prefix sharing, preempt-and-swap under
pool pressure, and leak-freedom on every slot release path.

The parity contract (docs/DEVIATIONS.md §10): kv_layout="paged" runs
the SAME attention formulation as the dense bank over gathered pages,
so its outputs are byte-identical — not approximately equal — under
greedy AND sampled decoding, with int8, prefix cache, speculation,
and async dispatch in any combination, including preemption."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.models import llama
from dlrover_tpu.serving.engine import ContinuousBatcher
from dlrover_tpu.serving.metrics import ServingMetrics
from dlrover_tpu.serving.scheduler import RequestScheduler, SloConfig

pytestmark = pytest.mark.paged


@pytest.fixture(scope="module")
def model():
    cfg = dataclasses.replace(
        llama.LlamaConfig.tiny(), dtype=jnp.float32
    )
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _prompts(lengths, seed=0, shared_prefix=0):
    rng = np.random.default_rng(seed)
    base = rng.integers(1, 250, size=shared_prefix).tolist()
    return [
        base + rng.integers(1, 250, size=n).tolist() for n in lengths
    ]


def _run(cfg, params, prompts, **kw):
    kw.setdefault("n_slots", 3)
    kw.setdefault("max_len", 64)
    kw.setdefault("max_new_tokens", 10)
    kw.setdefault("chunk", 4)
    cb = ContinuousBatcher(cfg, params, **kw)
    return cb, [list(map(int, r)) for r in cb.generate_all(prompts)]


CONFIGS = [
    ("plain", {}),
    ("int8", dict(kv_quant=True)),
    ("prefix", dict(prefix_cache_rows=4)),
    ("int8_prefix", dict(kv_quant=True, prefix_cache_rows=4)),
    ("spec", dict(spec_draft_len=4)),
    # the engine keeps a dispatch in flight unless told otherwise, so
    # every other entry runs that order and this one the oracle's
    ("sync", dict(async_depth=0)),
    (
        "kitchen_sink",
        dict(prefix_cache_rows=4, spec_draft_len=4, async_depth=1),
    ),
    ("sampled", dict(temperature=0.8, top_k=20, seed=3)),
]


class TestByteParity:
    @pytest.mark.parametrize(
        "kw", [c[1] for c in CONFIGS], ids=[c[0] for c in CONFIGS]
    )
    def test_paged_matches_dense(self, model, kw):
        cfg, params = model
        prompts = _prompts(
            (3, 5, 2, 7, 12, 9), seed=1, shared_prefix=20
        )
        _, dense = _run(cfg, params, prompts, **kw)
        cb, paged = _run(
            cfg, params, prompts, kv_layout="paged", **kw
        )
        assert dense == paged
        st = cb.paged_stats()
        if kw.get("prefix_cache_rows"):
            # the tentpole win must actually fire: prefix hits share
            # pages by refcount, and warm NON-page-aligned hits never
            # copy (CoW is confined to the admission frontier page)
            assert st["pages_shared"] > 0
        assert st["swap_preemptions"] == 0  # ample pool: no swaps

    def test_fuzzed_parity(self, model):
        """Randomized prompt sets across random knob combinations."""
        cfg, params = model
        rng = np.random.default_rng(7)
        for trial in range(4):
            lengths = rng.integers(2, 26, size=6)
            shared = int(rng.integers(0, 24))
            prompts = _prompts(
                lengths, seed=100 + trial, shared_prefix=shared
            )
            kw = {}
            if rng.integers(2):
                kw["kv_quant"] = True
            if rng.integers(2):
                kw["prefix_cache_rows"] = 4
            if rng.integers(2):
                kw["spec_draft_len"] = 4
            if rng.integers(2):
                kw["temperature"] = 0.7
                kw["seed"] = int(rng.integers(100))
            _, dense = _run(cfg, params, prompts, **kw)
            _, paged = _run(
                cfg, params, prompts, kv_layout="paged", **kw
            )
            assert dense == paged, (trial, kw)


class TestPreemptAndSwap:
    def test_pressure_parity_greedy(self, model):
        """A pool too small for the working set forces preempt-and-
        swap; resume-by-replay keeps greedy byte parity."""
        cfg, params = model
        prompts = _prompts((4, 18, 6, 11, 3, 25, 8), seed=2)
        _, dense = _run(
            cfg, params, prompts, max_new_tokens=24, chunk=3
        )
        cb, paged = _run(
            cfg, params, prompts, max_new_tokens=24, chunk=3,
            kv_layout="paged", n_pages=5,
        )
        assert dense == paged
        st = cb.paged_stats()
        assert st["swap_preemptions"] > 0, "pool never pressured"
        assert st["swap_resumes"] == st["swap_preemptions"]
        cb.allocator.check()
        assert cb.allocator.used_pages == 0  # all drained

    @pytest.mark.parametrize(
        "kw",
        [
            dict(prefix_cache_rows=4),
            dict(temperature=0.7, seed=9),
            dict(async_depth=0),
        ],
        ids=["prefix", "sampled", "sync"],
    )
    def test_pressure_parity_features(self, model, kw):
        cfg, params = model
        prompts = _prompts((4, 18, 6, 11, 3, 25, 8), seed=2)
        _, dense = _run(
            cfg, params, prompts, max_new_tokens=24, chunk=3, **kw
        )
        cb, paged = _run(
            cfg, params, prompts, max_new_tokens=24, chunk=3,
            kv_layout="paged", n_pages=6, **kw,
        )
        assert dense == paged
        assert cb.paged_stats()["swap_preemptions"] > 0

    def test_headroom_gate(self, model):
        cfg, params = model
        cb = ContinuousBatcher(
            cfg, params, n_slots=3, max_len=64, max_new_tokens=24,
            chunk=3, kv_layout="paged", n_pages=5, swap_headroom=1,
        )
        assert cb.admission_headroom_ok()  # empty pool
        cb.submit(list(range(1, 30)))
        cb.step()
        assert not cb.admission_headroom_ok()  # 4-page pool, big run
        # dense engines always say yes
        dense = ContinuousBatcher(
            cfg, params, n_slots=2, max_len=64, max_new_tokens=4
        )
        assert dense.admission_headroom_ok()
        assert dense.paged_stats() == {}


class TestLeakFreedom:
    def _drain(self, cb):
        while cb.has_work():
            cb.step()

    def test_retire_frees_pages_and_pins_in_one_step(self, model):
        """Satellite: retire() must drop slot occupancy, the page
        run, AND the prefix pin in a single call — whatever path led
        to it — so a failed publish can never strand a pinned row."""
        cfg, params = model
        prompts = _prompts((5, 9, 4, 7), seed=3, shared_prefix=18)
        cb = ContinuousBatcher(
            cfg, params, n_slots=2, max_len=64, max_new_tokens=6,
            chunk=3, kv_layout="paged", prefix_cache_rows=2,
        )
        ids = [cb.submit(p) for p in prompts]
        self._drain(cb)
        for i in ids:
            cb.retire(i)
        cb.allocator.check()
        # only PUBLISHED runs may hold pages now; no slot pins remain
        assert all(r is None for r in cb._slot_row)
        assert all(not run for run in cb._slot_pages)
        published = sum(len(r) for r in cb._row_pages.values())
        assert cb.allocator.used_pages == len(
            set(p for r in cb._row_pages.values() for p in r)
        )
        assert published >= 0

    def test_publish_failure_leaks_nothing(self, model):
        """Satellite: when the radix cannot take a publish (every row
        pinned by live slots), admission+retire must leave zero
        stranded pages or pins."""
        cfg, params = model
        # 1-row radix + 2 slots: the second admission's publish-back
        # finds the only row pinned -> insert returns (None, False)
        prompts = _prompts((17, 17, 17, 17), seed=4)
        cb = ContinuousBatcher(
            cfg, params, n_slots=2, max_len=64, max_new_tokens=6,
            chunk=3, kv_layout="paged", prefix_cache_rows=1,
        )
        ids = [cb.submit(p) for p in prompts]
        self._drain(cb)
        for i in ids:
            cb.retire(i)
        cb.allocator.check()
        assert all(r is None for r in cb._slot_row)
        tracked = set(p for r in cb._row_pages.values() for p in r)
        assert cb.allocator.used_pages == len(tracked)

    def test_cancel_frees_pages(self, model):
        cfg, params = model
        cb = ContinuousBatcher(
            cfg, params, n_slots=2, max_len=64, max_new_tokens=20,
            chunk=3, kv_layout="paged", prefix_cache_rows=2,
        )
        ids = [cb.submit(p) for p in _prompts((6, 8, 5), seed=5)]
        cb.step()
        used_live = cb.allocator.used_pages
        assert used_live > 0
        cb.cancel(ids[0])
        cb.cancel(ids[1])
        self._drain(cb)
        cb.allocator.check()
        tracked = set(p for r in cb._row_pages.values() for p in r)
        assert cb.allocator.used_pages == len(tracked)

    def test_reset_rebuilds_pool(self, model):
        cfg, params = model
        cb = ContinuousBatcher(
            cfg, params, n_slots=2, max_len=64, max_new_tokens=8,
            chunk=3, kv_layout="paged", prefix_cache_rows=2,
        )
        cb.generate_all(_prompts((6, 8, 5), seed=6))
        assert cb.allocator.pages_allocated > 0
        cb.reset()
        assert cb.allocator.used_pages == 0
        assert cb.allocator.free_pages == cb.allocator.capacity
        cb.allocator.check()
        # and the engine still serves correctly after the rebuild
        prompts = _prompts((4, 9), seed=8)
        _, dense = _run(
            cfg, params, prompts, n_slots=2, max_new_tokens=8, chunk=3
        )
        out = [list(map(int, r)) for r in cb.generate_all(prompts)]
        assert out == dense

    def test_prefix_eviction_frees_pages(self, model):
        """Radix LRU eviction of a published prefix must drop its
        page run (the on_evict hook)."""
        cfg, params = model
        cb = ContinuousBatcher(
            cfg, params, n_slots=1, max_len=64, max_new_tokens=4,
            chunk=2, kv_layout="paged", prefix_cache_rows=1,
        )
        # distinct 16-aligned prefixes churn the single radix row
        for seed in range(4):
            cb.generate_all(_prompts((20,), seed=20 + seed))
        assert cb.prefix_cache.evictions > 0
        cb.allocator.check()
        tracked = set(p for r in cb._row_pages.values() for p in r)
        assert cb.allocator.used_pages == len(tracked)
        assert len(cb._row_pages) <= 1


class TestKnobValidation:
    def test_bad_layout_rejected(self, model):
        cfg, params = model
        with pytest.raises(ValueError, match="kv_layout"):
            ContinuousBatcher(cfg, params, kv_layout="banana")

    def test_page_size_must_divide_bank(self, model):
        cfg, params = model
        with pytest.raises(ValueError, match="page_size"):
            ContinuousBatcher(
                cfg, params, max_len=64, kv_layout="paged",
                page_size=48,
            )

    def test_pool_must_back_one_request(self, model):
        cfg, params = model
        with pytest.raises(ValueError, match="n_pages"):
            ContinuousBatcher(
                cfg, params, max_len=64, kv_layout="paged",
                page_size=16, n_pages=3,
            )

    def test_auto_page_size_respects_prefix_block(self, model):
        cfg, params = model
        cb = ContinuousBatcher(
            cfg, params, max_len=64, kv_layout="paged",
            prefix_cache_rows=2, prefix_block=8,
        )
        assert cb.page_size == 8
        assert 8 % cb.page_size == 0


class TestSchedulerIntegration:
    def test_memory_aware_admission_and_metrics(self, model):
        """The scheduler holds admissions while the pool lacks
        headroom (preferring queue-wait over swap thrash) yet still
        completes everything; page-pool metrics reach /metrics."""
        cfg, params = model
        engine = ContinuousBatcher(
            cfg, params, n_slots=3, max_len=64, max_new_tokens=16,
            chunk=4, kv_layout="paged", n_pages=5,
            prefix_cache_rows=2,
        )
        metrics = ServingMetrics()
        sched = RequestScheduler(
            engine,
            slo=SloConfig(max_queue_depth=16, max_new_tokens=16,
                          default_deadline_s=1e9),
            metrics=metrics,
        )
        reqs = [
            sched.submit(p, max_new=16)
            for p in _prompts((20, 22, 18, 24), seed=9)
        ]
        sched.run_to_completion()
        for r in reqs:
            assert r.state.value == "done"
            assert len(r.tokens) > 0
        # the gate kept concurrent residency at 1 on this tiny pool,
        # so the engine never had to preempt anything
        assert engine.paged_stats()["swap_preemptions"] == 0
        text = metrics.render()
        assert "serving_paged_pool_occupancy" in text
        assert "serving_paged_cow_copies_total" in text
        assert "serving_paged_swap_preemptions_total 0" in text
        assert metrics.paged_occupancy >= 0.0

    def test_gate_never_starves_empty_engine(self, model):
        """With zero active slots the gate must admit (the engine
        reclaims inline), or a single over-sized request would wait
        forever."""
        cfg, params = model
        engine = ContinuousBatcher(
            cfg, params, n_slots=2, max_len=64, max_new_tokens=30,
            chunk=4, kv_layout="paged", n_pages=5,
        )
        sched = RequestScheduler(
            engine,
            slo=SloConfig(max_new_tokens=64, default_deadline_s=1e9),
        )
        r = sched.submit(list(range(1, 30)), max_new=30)
        sched.run_to_completion()
        assert r.state.value == "done"
        assert len(r.tokens) == 30
