"""Interleaved chunked prefill (engine.py `prefill_chunk` +
models/decode.py chunk-resume programs): chunked-vs-blocking byte
parity across dense/paged x greedy/sampled x prefix/spec x async,
TTFT decomposition counters, crash at a fuzzed mid-prefill step with
replay resume and zero leaked pages, preempt-and-swap of a partially
prefilled slot, mid-prefill cancellation, and the scheduler's
coldness ranking (a latency arrival never evicts a decoding slot
while a cheaper mid-prefill victim exists)."""

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _serve_oracle import lockstep_oracle
from dlrover_tpu.models import llama
from dlrover_tpu.serving.chaos import FaultInjector
from dlrover_tpu.serving.engine import ContinuousBatcher
from dlrover_tpu.serving.metrics import ServingMetrics
from dlrover_tpu.serving.replica import InferenceReplica, ReplicaPool
from dlrover_tpu.serving.scheduler import (
    RequestScheduler,
    RequestState,
    SloConfig,
)

pytestmark = pytest.mark.interleave


@pytest.fixture(scope="module")
def model():
    cfg = dataclasses.replace(
        llama.LlamaConfig.tiny(), dtype=jnp.float32
    )
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _prompts(lengths, seed=0, shared_prefix=0):
    rng = np.random.default_rng(seed)
    prefix = rng.integers(1, 250, size=shared_prefix).tolist()
    return [
        prefix + rng.integers(1, 250, size=n).tolist()
        for n in lengths
    ]


def _run(cfg, params, prompts, **kw):
    kw.setdefault("n_slots", 2)
    kw.setdefault("max_len", 64)
    kw.setdefault("max_new_tokens", 8)
    kw.setdefault("chunk", 4)
    cb = ContinuousBatcher(cfg, params, **kw)
    return cb, [list(map(int, r)) for r in cb.generate_all(prompts)]


# (name, engine kwargs) — every serving discipline the chunk program
# variants must ride along with. The blocking baseline is the SAME
# kwargs minus prefill_chunk, so each pair isolates exactly the
# interleaving.
CONFIGS = [
    ("dense-greedy", {}),
    ("paged-greedy", {"kv_layout": "paged", "n_pages": 24}),
    ("dense-sampled", {"temperature": 0.8, "top_k": 20, "seed": 11}),
    (
        "paged-sampled",
        {
            "kv_layout": "paged",
            "n_pages": 24,
            "temperature": 0.8,
            "top_p": 0.9,
            "seed": 11,
        },
    ),
    ("prefix", {"prefix_cache_rows": 4, "prefix_block": 16}),
    (
        "paged-prefix",
        {
            "kv_layout": "paged",
            "n_pages": 24,
            "prefix_cache_rows": 4,
            "prefix_block": 16,
        },
    ),
    ("spec", {"spec_draft_len": 3}),
    # every entry above keeps a dispatch in flight (the engine's
    # default); these two harvest in the same step()
    ("sync", {"async_depth": 0}),
    (
        "paged-sync",
        {"kv_layout": "paged", "n_pages": 24, "async_depth": 0},
    ),
]


class TestChunkedParity:
    """The acceptance oracle: for every engine discipline, chunked
    admission produces byte-identical streams to blocking admission
    — interleaving may only change WHEN work runs, never its
    bytes."""

    @pytest.mark.parametrize(
        "kw", [c[1] for c in CONFIGS], ids=[c[0] for c in CONFIGS]
    )
    def test_parity_vs_blocking(self, model, kw):
        cfg, params = model
        prompts = _prompts((23, 5, 40, 11), seed=3, shared_prefix=8)
        _, want = _run(cfg, params, prompts, **kw)
        cb, got = _run(
            cfg, params, prompts, prefill_chunk=4, **kw
        )
        assert got == want
        st = cb.prefill_stats()
        assert st["prefill_chunks_total"] > 0, "chunking never engaged"
        assert st["prefilling_slots"] == 0  # all flipped to decode

    @pytest.mark.parametrize("pc", [1, 3, 16])
    def test_chunk_size_sweep(self, model, pc):
        """Chunk budget is a latency knob, not a semantics knob:
        pow2-down tail slicing keeps any budget byte-exact, including
        a budget larger than every prompt (degenerates to blocking)
        and a non-power-of-two one."""
        cfg, params = model
        prompts = _prompts((23, 5, 40, 11), seed=3)
        _, want = _run(cfg, params, prompts)
        for kw in ({}, {"kv_layout": "paged", "n_pages": 24}):
            _, got = _run(
                cfg, params, prompts, prefill_chunk=pc, **kw
            )
            assert got == want, (pc, kw)

    def test_zero_knob_is_inert(self, model):
        """prefill_chunk=0 (the default) must not even BIND the
        chunk-prefill program variant: same cache keys, same bytes —
        the bit-exact parity oracle the ISSUE pins."""
        cfg, params = model
        from dlrover_tpu.serving import engine as eng_mod

        prompts = _prompts((9, 17), seed=4)
        before = set(eng_mod._CHUNK_PROGRAMS)
        cb, got = _run(cfg, params, prompts, prefill_chunk=0)
        assert cb._run_pf is None
        added = set(eng_mod._CHUNK_PROGRAMS) - before
        assert not any("prefill" in k for k in added), (
            "pc=0 engine bound a chunk-prefill program variant"
        )
        _, want = _run(cfg, params, prompts)
        assert got == want

    def test_negative_knob_rejected(self, model):
        cfg, params = model
        with pytest.raises(ValueError):
            ContinuousBatcher(
                cfg, params, n_slots=1, max_len=32, prefill_chunk=-1
            )

    @pytest.mark.slow
    @pytest.mark.parametrize("fuzz_seed", [1, 2, 3])
    @pytest.mark.parametrize(
        "kw",
        [c[1] for c in CONFIGS],
        ids=[c[0] for c in CONFIGS],
    )
    def test_fuzzed_parity_sweep(self, model, fuzz_seed, kw):
        """Deep fuzz: random prompt lengths and chunk budgets per
        seed, every discipline — the static-shape chunk programs must
        stay byte-exact at ANY frontier alignment."""
        cfg, params = model
        rng = np.random.default_rng(fuzz_seed)
        lengths = tuple(rng.integers(2, 48, size=5))
        pc = int(rng.integers(1, 9))
        prompts = _prompts(lengths, seed=fuzz_seed, shared_prefix=4)
        _, want = _run(cfg, params, prompts, **kw)
        _, got = _run(
            cfg, params, prompts, prefill_chunk=pc, **kw
        )
        assert got == want, (fuzz_seed, pc)


class TestTtftTelemetry:
    def test_stall_and_chunk_counters(self, model):
        """TTFT decomposition: admission stall time and chunk count
        are measured on the engine and folded into ServingMetrics by
        the scheduler pump."""
        cfg, params = model
        metrics = ServingMetrics()
        eng = ContinuousBatcher(
            cfg, params, n_slots=2, max_len=64, max_new_tokens=6,
            chunk=4, prefill_chunk=4,
        )
        sched = RequestScheduler(eng, metrics=metrics)
        for p in _prompts((21, 9), seed=5):
            sched.submit(p, deadline_s=600.0)
        sched.run_to_completion()
        st = eng.prefill_stats()
        assert st["prefill_chunks_total"] >= 2
        assert st["admission_stall_ms"] >= 0.0
        text = metrics.render()
        assert "serving_admission_stall_ms" in text
        assert "serving_prefill_chunks_total" in text
        assert "serving_prefill_chunk_tokens 4" in text
        assert "serving_prefilling_slots 0" in text


class TestMidPrefillLifecycle:
    def test_cancel_mid_prefill_frees_pages(self, model):
        """Cancelling a partially prefilled slot releases its whole
        page run and clears the frontier — no leak, slot reusable."""
        cfg, params = model
        eng = ContinuousBatcher(
            cfg, params, n_slots=1, max_len=64, max_new_tokens=6,
            chunk=4, prefill_chunk=2, kv_layout="paged", n_pages=24,
        )
        prompt = _prompts((40,), seed=6)[0]
        idx = eng.submit(prompt)
        eng.step()  # admit + first prefill chunk
        assert eng._prefilling.any()
        assert eng.request_progress(idx) < 0  # mid-prefill: negative
        assert eng.allocator.used_pages > 0
        eng.cancel(idx)
        eng.drain_inflight()
        assert not eng._prefilling.any()
        assert int(eng._frontier.sum()) == 0
        eng.allocator.check()
        assert eng.allocator.used_pages == 0
        # the slot admits and serves fresh work afterwards
        _, out = (
            eng,
            [
                list(map(int, r))
                for r in eng.generate_all(_prompts((7,), seed=7))
            ],
        )
        assert out[0] == list(
            lockstep_oracle(
                cfg, params, _prompts((7,), seed=7)[0], 6
            )
        )

    def test_swap_preempts_partially_prefilled_slot(self, model):
        """Page pressure mid-prefill: a fresh arrival's preempt-and-
        swap picks the partially prefilled slot (coldest footprint —
        zero tokens to regenerate), the victim's readmission WAITS
        for pages instead of swapping back (the seniority gate that
        kills the mutual-eviction livelock), and the final bytes
        match an unpressured dense blocking run."""
        cfg, params = model
        prompts = _prompts((40, 36), seed=8)
        _, want = _run(
            cfg, params, prompts, max_new_tokens=6, chunk=2
        )
        eng = ContinuousBatcher(
            cfg, params, n_slots=2, max_len=64, max_new_tokens=6,
            chunk=2, prefill_chunk=4, kv_layout="paged", n_pages=5,
        )
        eng.submit(prompts[0])
        eng.step()  # slot 0 admitted, first chunk in
        assert eng._prefilling.any()
        eng.submit(prompts[1])
        n = 0
        while eng.has_work():
            eng.step()
            n += 1
            assert n < 500, "admission livelocked"
        st = eng.paged_stats()
        assert st["swap_preemptions"] >= 1, "pool never pressured"
        assert st["swap_resumes"] == st["swap_preemptions"]
        got = [
            list(map(int, r))
            for r in (
                np.asarray(eng._requests[i].out, np.int32)
                for i in sorted(eng._pending)
            )
        ]
        assert got == want
        eng.allocator.check()
        assert eng.allocator.used_pages == 0


def _drive(reps, max_iters=400):
    for _ in range(max_iters):
        busy = False
        for r in reps:
            busy = r.scheduler.pump() or busy
        if not busy:
            return
    raise AssertionError("pool did not drain")


def _make_chaos_pool(cfg, params, fi, engine_kw, n_replicas=2):
    metrics = ServingMetrics()
    pool = ReplicaPool(metrics=metrics, clock=time.monotonic)
    reps = []
    for i in range(n_replicas):
        tag = f"replica-{i}"
        eng = ContinuousBatcher(
            cfg, params, n_slots=2, max_len=64, max_new_tokens=6,
            chunk=2, chaos=fi, chaos_tag=tag, **engine_kw,
        )
        rep = InferenceReplica(
            tag, RequestScheduler(eng, metrics=metrics), chaos=fi
        )
        pool.add(rep)
        reps.append(rep)
    return pool, reps, metrics


class TestMidPrefillCrash:
    """Chaos: a replica killed while a slot is partially prefilled.
    The prompt is long and the chunk budget tiny, so every step in
    the crash window is a prefill dispatch — the crash is guaranteed
    to land mid-prefill."""

    @pytest.mark.chaos
    @pytest.mark.parametrize(
        "engine_kw",
        [
            {"prefill_chunk": 2},
            {
                "prefill_chunk": 2,
                "kv_layout": "paged",
                "n_pages": 24,
            },
        ],
        ids=["dense", "paged"],
    )
    def test_crash_mid_prefill_replays(self, model, engine_kw):
        cfg, params = model
        prompts = _prompts((40, 7), seed=9)
        ref_kw = {
            k: v for k, v in engine_kw.items() if k != "n_pages"
        }
        ref_kw.pop("kv_layout", None)
        _, want = _run(
            cfg, params, prompts, max_new_tokens=6, chunk=2, **ref_kw
        )
        fi = FaultInjector(seed=0)
        step = fi.crash_replica("replica-0", between=(2, 8))
        pool, reps, metrics = _make_chaos_pool(
            cfg, params, fi, engine_kw
        )
        reqs = [
            reps[0].scheduler.submit(p, max_new=6, deadline_s=600.0)
            for p in prompts
        ]
        _drive(reps)
        assert fi.fired, f"crash plan at step {step} never fired"
        for p, r, w in zip(prompts, reqs, want):
            assert r.state is RequestState.DONE
            assert r.tokens == w, "mid-prefill crash-resume diverged"
        assert metrics.failed_total == 0
        assert metrics.failovers_total >= 1
        if "n_pages" in engine_kw:
            # survivor drained cleanly; crashed engine rebuilt empty
            surv = reps[1].scheduler.engine
            surv.allocator.check()
            assert surv.allocator.used_pages == 0
            reps[0].scheduler.restart()
            crashed = reps[0].scheduler.engine
            crashed.allocator.check()
            assert crashed.allocator.used_pages == 0
            assert not crashed._prefilling.any()

    @pytest.mark.chaos
    @pytest.mark.slow
    @pytest.mark.parametrize("fuzz_seed", [1, 2, 3, 4])
    def test_fuzzed_crash_step_sweep(self, model, fuzz_seed):
        """Fuzz the crash step across the whole prefill+decode span
        on the paged layout — every landing point must replay to the
        same bytes with zero leaked pages."""
        cfg, params = model
        prompts = _prompts((40, 7), seed=9)
        _, want = _run(
            cfg, params, prompts, max_new_tokens=6, chunk=2
        )
        fi = FaultInjector(seed=fuzz_seed)
        fi.crash_replica("replica-0", between=(1, 20))
        pool, reps, metrics = _make_chaos_pool(
            cfg,
            params,
            fi,
            {"prefill_chunk": 2, "kv_layout": "paged", "n_pages": 24},
        )
        reqs = [
            reps[0].scheduler.submit(p, max_new=6, deadline_s=600.0)
            for p in prompts
        ]
        _drive(reps)
        assert fi.fired
        for r, w in zip(reqs, want):
            assert r.state is RequestState.DONE
            assert r.tokens == w
        assert metrics.failed_total == 0
        surv = reps[1].scheduler.engine
        surv.allocator.check()
        assert surv.allocator.used_pages == 0


class TestTierRanking:
    def test_latency_prefers_mid_prefill_victim(self, model):
        """Satellite regression: a latency arrival must never evict a
        decoding batch slot while a cheaper mid-prefill batch victim
        exists — replaying a mid-prefill slot regenerates zero
        tokens, replaying a decoder regenerates its whole stream."""
        cfg, params = model
        metrics = ServingMetrics()
        eng = ContinuousBatcher(
            cfg, params, n_slots=2, max_len=64, max_new_tokens=8,
            chunk=2, prefill_chunk=2,
        )
        sched = RequestScheduler(eng, SloConfig(), metrics=metrics)
        p_decode, p_prefill, p_lat = _prompts((5, 40, 6), seed=10)
        decoding = sched.submit(
            p_decode, max_new=8, deadline_s=600.0, tier="batch"
        )
        sched.pump()  # short prompt admits and starts decoding
        assert decoding.state is RequestState.RUNNING
        prefilling = sched.submit(
            p_prefill, max_new=8, deadline_s=600.0, tier="batch"
        )
        sched.pump()  # long prompt mid-prefill in the second slot
        assert prefilling.state is RequestState.RUNNING
        assert eng._prefilling.any()
        latency = sched.submit(
            p_lat, max_new=4, deadline_s=600.0, tier="latency"
        )
        sched.pump()  # blocked latency arrival must pick a victim
        assert prefilling.preemptions == 1, (
            "mid-prefill victim not chosen"
        )
        assert decoding.preemptions == 0, (
            "decoding slot evicted despite cheaper mid-prefill victim"
        )
        assert metrics.tier_preempted_total["batch"] == 1
        sched.run_to_completion()
        for r, p, n in (
            (latency, p_lat, 4),
            (decoding, p_decode, 8),
            (prefilling, p_prefill, 8),
        ):
            assert r.state is RequestState.DONE
            assert r.tokens == lockstep_oracle(cfg, params, p, n)
