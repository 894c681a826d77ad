"""Crash-safe serving (dlrover_tpu/serving/failover.py + chaos.py):
request-level failover across replica death, resume-by-replay parity
(greedy byte-identical, sampled continues the journaled PRNG key),
circuit-breaker probation, probe isolation, heartbeat KV retry, and
client-disconnect cancellation. Faults are injected through the
deterministic seed-driven FaultInjector hooks — never monkeypatching.
"""

import dataclasses
import json
import socket
import struct
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.master.kv_store import KVStoreService, RetryingKV
from dlrover_tpu.models import llama
from dlrover_tpu.serving.chaos import (
    ChaosError,
    ChaosKV,
    FaultInjector,
    KVFlake,
    ReplicaCrashed,
)
from dlrover_tpu.serving.engine import ContinuousBatcher
from dlrover_tpu.serving.failover import (
    CLOSED,
    HALF_OPEN,
    OPEN,
    CircuitBreaker,
)
from dlrover_tpu.serving.gateway import ServingGateway
from dlrover_tpu.serving.metrics import ServingMetrics
from dlrover_tpu.serving.replica import InferenceReplica, ReplicaPool
from dlrover_tpu.serving.scheduler import (
    AdmissionError,
    RequestScheduler,
    RequestState,
)


@pytest.fixture(scope="module")
def model():
    cfg = dataclasses.replace(
        llama.LlamaConfig.tiny(), dtype=jnp.float32
    )
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 250, size=n).tolist() for n in lengths]


def _engine(cfg, params, **kw):
    kw.setdefault("n_slots", 2)
    kw.setdefault("max_len", 64)
    kw.setdefault("max_new_tokens", 8)
    kw.setdefault("chunk", 2)
    return ContinuousBatcher(cfg, params, **kw)


def _drive(reps, max_iters=400):
    """Round-robin direct-drive across replicas (no threads): the
    crashing scheduler's on_failure fires synchronously inside its
    own pump, so evacuation + resume are fully deterministic."""
    for _ in range(max_iters):
        busy = False
        for r in reps:
            busy = r.scheduler.pump() or busy
        if not busy:
            return
    raise AssertionError("pool did not drain")


def _make_chaos_pool(
    cfg, params, fi, n_replicas=2, clock=None, engine_kw=None,
    **pool_kw,
):
    """Direct-drive pool (schedulers NOT started): every replica's
    engine is chaos-wired under the tag `replica-<i>`."""
    metrics = ServingMetrics()
    pool = ReplicaPool(
        metrics=metrics, clock=clock or time.monotonic, **pool_kw
    )
    reps = []
    for i in range(n_replicas):
        tag = f"replica-{i}"
        eng = _engine(
            cfg, params, chaos=fi, chaos_tag=tag, **(engine_kw or {})
        )
        sched = RequestScheduler(eng, metrics=metrics)
        rep = InferenceReplica(tag, sched, chaos=fi)
        pool.add(rep)
        reps.append(rep)
    return pool, reps, metrics


# ---------------------------------------------------------------------------
# circuit breaker (pure host logic, no engine)


class TestCircuitBreaker:
    def test_trips_after_max_strikes_first_trip_immediate(self):
        t = [0.0]
        b = CircuitBreaker(max_strikes=2, clock=lambda: t[0])
        b.record_failure()
        assert b.state == CLOSED and b.should_probe()
        b.record_failure()
        assert b.state == OPEN
        # first trip: zero probation delay — a transient blip heals
        # on the very next check pass
        assert b.should_probe() and b.state == HALF_OPEN

    def test_failed_probation_grows_backoff_capped(self):
        t = [0.0]
        b = CircuitBreaker(
            max_strikes=1, backoff_base_s=1.0, backoff_max_s=4.0,
            clock=lambda: t[0],
        )
        b.record_failure()          # trip 1: delay 0
        assert b.should_probe()
        b.record_failure()          # failed probation: delay 1.0
        assert not b.should_probe()
        assert b.retry_in_s == pytest.approx(1.0)
        t[0] += 1.0
        assert b.should_probe()
        b.record_failure()          # delay 2.0
        t[0] += 2.0
        assert b.should_probe()
        b.record_failure()          # delay 4.0
        t[0] += 4.0
        assert b.should_probe()
        b.record_failure()          # capped at 4.0, not 8.0
        assert b.retry_in_s == pytest.approx(4.0)

    def test_success_closes_and_resets_backoff(self):
        t = [0.0]
        b = CircuitBreaker(max_strikes=1, clock=lambda: t[0])
        b.record_failure()
        assert b.should_probe()
        b.record_success()
        assert b.state == CLOSED
        # next trip is a FIRST trip again: immediate probation
        b.record_failure()
        assert b.should_probe()


# ---------------------------------------------------------------------------
# fault injector


class TestFaultInjector:
    def test_fuzzed_crash_step_is_seed_deterministic(self):
        steps = [
            FaultInjector(seed=5).crash_replica(
                "r", between=(1, 100)
            )
            for _ in range(3)
        ]
        assert steps[0] == steps[1] == steps[2]
        assert 1 <= steps[0] < 100

    def test_crash_persists_until_revive(self):
        fi = FaultInjector()
        fi.crash_replica("r", at_step=0)
        with pytest.raises(ReplicaCrashed):
            fi.on_engine_step("r", 0)
        assert not fi.probe_ok("r")
        with pytest.raises(ReplicaCrashed):  # still dead next step
            fi.on_engine_step("r", 1)
        fi.revive("r")
        assert fi.probe_ok("r")
        fi.on_engine_step("r", 2)  # no raise
        assert fi.fired == [("engine", "r", 0)]

    def test_transient_step_fault_fires_once(self):
        fi = FaultInjector()
        fi.fail_engine_step("r", at_step=1)
        fi.on_engine_step("r", 0)
        with pytest.raises(ChaosError):
            fi.on_engine_step("r", 1)
        assert fi.probe_ok("r")       # not a crash
        fi.on_engine_step("r", 2)     # one-shot: no re-raise

    def test_flaky_kv_budget(self):
        fi = FaultInjector()
        store = KVStoreService()
        kv = ChaosKV(store, fi, tag="kv")
        fi.flaky_kv("kv", fail_next=2)
        with pytest.raises(KVFlake):
            kv.set("a", b"1")
        with pytest.raises(KVFlake):
            kv.set("a", b"1")
        kv.set("a", b"2")             # budget spent
        assert kv.get("a") == b"2"
        assert store.get("a") == b"2"


# ---------------------------------------------------------------------------
# RetryingKV + heartbeat (satellite: transient KV errors must not
# propagate out of the heartbeat path)


class TestKVRetry:
    def _flaky(self, fail_next):
        fi = FaultInjector()
        store = KVStoreService()
        fi.flaky_kv("kv", fail_next=fail_next)
        return ChaosKV(store, fi, tag="kv"), store

    def test_retries_through_transient_failures(self):
        kv, store = self._flaky(2)
        naps = []
        rkv = RetryingKV(kv, retries=3, sleep=naps.append)
        rkv.set("k", b"v")
        assert store.get("k") == b"v"
        # capped exponential backoff between attempts
        assert naps == [0.05, 0.1]

    def test_exhausted_retries_propagate(self):
        kv, _ = self._flaky(10)
        rkv = RetryingKV(kv, retries=2, sleep=lambda _s: None)
        with pytest.raises(KVFlake):
            rkv.set("k", b"v")

    def test_non_transient_errors_pass_through(self):
        class Bad:
            def set(self, key, value):
                raise ValueError("bug, not weather")

        rkv = RetryingKV(Bad(), retries=3, sleep=lambda _s: None)
        with pytest.raises(ValueError):
            rkv.set("k", b"v")

    def test_heartbeat_survives_flaky_kv(self, model):
        """register/heartbeat retry transient KV errors and, when the
        budget is exhausted, log instead of raising into the pool
        thread."""
        cfg, params = model
        fi = FaultInjector()
        store = KVStoreService()
        kv = ChaosKV(store, fi, tag="kv")
        sched = RequestScheduler(_engine(cfg, params))
        rep = InferenceReplica(
            "rep", sched, kv=kv, kv_retries=3, kv_backoff_s=0.0
        )
        fi.flaky_kv("kv", fail_next=2)
        rep.heartbeat()               # retries through the flake
        assert json.loads(store.get(rep.kv_key))["id"] == "rep"
        store.delete(rep.kv_key)
        fi.flaky_kv("kv", fail_next=50)
        rep.heartbeat()               # exhausted: swallowed, no raise
        assert store.get(rep.kv_key) == b""


# ---------------------------------------------------------------------------
# health-check loop isolation (satellite: one raising probe must not
# abort the pass)


class TestProbeIsolation:
    def test_raising_probe_counts_as_failure_not_abort(self, model):
        cfg, params = model
        pool, reps, _ = _make_chaos_pool(
            cfg, params, FaultInjector(), n_replicas=2
        )
        store = KVStoreService()
        reps[1].kv = store

        boom = {"n": 0}

        def bad_probe():
            boom["n"] += 1
            raise RuntimeError("probe exploded")

        reps[0].probe = bad_probe
        pool.check_replicas()
        # replica-1 was still probed AND heartbeated this same pass
        assert json.loads(store.get(reps[1].kv_key))["id"] == \
            "replica-1"
        assert reps[0].healthy        # one strike: weather
        pool.check_replicas()
        assert boom["n"] == 2
        assert not reps[0].healthy    # two strikes: ejected
        assert reps[1].healthy


# ---------------------------------------------------------------------------
# the tentpole: crash mid-decode -> zero failed requests, greedy
# byte-parity with the uncrashed run


def _reference(cfg, params, prompts, engine_kw=None):
    # the parity oracle harvests in the same step() that dispatched,
    # whatever order the engine under test runs
    eng = _engine(cfg, params, **{**(engine_kw or {}), "async_depth": 0})
    return {
        tuple(p): list(o)
        for p, o in zip(prompts, eng.generate_all(prompts))
    }


class TestFailoverParity:
    def _crash_run(self, cfg, params, prompts, fuzz_seed, engine_kw=None):
        fi = FaultInjector(seed=fuzz_seed)
        step = fi.crash_replica("replica-0", between=(1, 8))
        pool, reps, metrics = _make_chaos_pool(
            cfg, params, fi, n_replicas=2, engine_kw=engine_kw
        )
        # everything lands on the victim so the crash strands both
        # running AND queued requests
        reqs = [
            reps[0].scheduler.submit(p, deadline_s=600.0)
            for p in prompts
        ]
        _drive(reps)
        assert fi.fired, f"crash plan at step {step} never fired"
        return reqs, metrics, reps

    def test_greedy_crash_parity(self, model):
        """The acceptance criterion: a replica killed mid-decode loses
        ZERO requests and every completed stream is byte-identical to
        the uncrashed run."""
        cfg, params = model
        prompts = _prompts((5, 9, 3, 7), seed=1)
        want = _reference(cfg, params, prompts)
        reqs, metrics, reps = self._crash_run(
            cfg, params, prompts, fuzz_seed=0
        )
        for p, r in zip(prompts, reqs):
            assert r.state is RequestState.DONE
            assert r.tokens == want[tuple(p)], (
                f"crash-resume diverged for prompt {p}"
            )
        assert metrics.failed_total == 0
        assert metrics.failovers_total >= 1
        assert metrics.replica_ejections == 1
        assert not reps[0].healthy and reps[1].healthy

    @pytest.mark.chaos
    @pytest.mark.slow
    @pytest.mark.parametrize("fuzz_seed", [1, 2, 3])
    @pytest.mark.parametrize(
        "engine_kw",
        [
            {"async_depth": 0},
            {"async_depth": 0, "kv_quant": True},
            {"async_depth": 0, "prefix_cache_rows": 4},
            {"async_depth": 0, "spec_draft_len": 4},
            {"async_depth": 1},
            {"async_depth": 1, "kv_quant": True},
            {"async_depth": 1, "prefix_cache_rows": 4},
            {"async_depth": 1, "spec_draft_len": 4},
        ],
        ids=[
            "plain", "int8", "prefix", "spec",
            "async", "async-int8", "async-prefix", "async-spec",
        ],
    )
    def test_greedy_parity_sweep(self, model, fuzz_seed, engine_kw):
        """Deep sweep: fuzzed crash steps x engine variants (int8 KV,
        prefix-warm resume, speculative decoding, async dispatch) —
        replay-resume must be byte-exact under every KV/decode
        discipline. The reference always runs SYNCHRONOUS
        (_reference builds it so): the sync path is the parity oracle
        the pipelined path must reproduce, crashes and all."""
        cfg, params = model
        prompts = _prompts((5, 9, 3, 7), seed=fuzz_seed)
        want = _reference(cfg, params, prompts, engine_kw)
        reqs, metrics, _ = self._crash_run(
            cfg, params, prompts, fuzz_seed, engine_kw
        )
        for p, r in zip(prompts, reqs):
            assert r.state is RequestState.DONE
            assert r.tokens == want[tuple(p)]
        assert metrics.failed_total == 0

    def test_async_crash_parity_vs_sync_reference(self, model):
        """Cheap always-on cousin of the sweep: a replica running
        async_depth=1 killed mid-decode (possibly with a dispatch in
        flight — it is abandoned, journal stays at last harvest) must
        still complete every request byte-identical to an uncrashed
        SYNCHRONOUS run."""
        cfg, params = model
        prompts = _prompts((5, 9, 3, 7), seed=2)
        want = _reference(cfg, params, prompts)
        reqs, metrics, _ = self._crash_run(
            cfg, params, prompts, fuzz_seed=0,
            engine_kw={"async_depth": 1},
        )
        for p, r in zip(prompts, reqs):
            assert r.state is RequestState.DONE
            assert r.tokens == want[tuple(p)]
        assert metrics.failed_total == 0
        assert metrics.failovers_total >= 1

    def test_sampled_resume_continues_journaled_key(self, model):
        """Sampled crash resume: the journaled per-slot PRNG key moves
        with the request, so the resumed stream equals an uncrashed
        same-seed run — even though the rescuing engine has a
        DIFFERENT seed."""
        cfg, params = model
        prompt = _prompts((6,), seed=2)[0]
        sample_kw = dict(temperature=0.9, top_k=20)

        # uncrashed comparator: seed 7, sole request -> its key is
        # the first split of PRNGKey(7)
        ref_eng = _engine(
            cfg, params, n_slots=1, seed=7, **sample_kw
        )
        want = list(ref_eng.generate_all([prompt])[0])

        fi = FaultInjector()
        fi.crash_replica("replica-0", at_step=2)
        pool, reps, metrics = _make_chaos_pool(
            cfg, params, fi, n_replicas=2,
            engine_kw=dict(n_slots=1, **sample_kw),
        )
        # victim seeded like the comparator; rescuer seeded
        # differently — only the journaled key can give parity
        reps[0].scheduler.engine.key = jax.random.PRNGKey(7)
        reps[1].scheduler.engine.key = jax.random.PRNGKey(99)
        req = reps[0].scheduler.submit(prompt, deadline_s=600.0)
        _drive(reps)
        assert req.state is RequestState.DONE
        assert len(req.tokens) == len(want)
        assert req.tokens == want
        # the crash landed mid-generation (tokens from BOTH replicas)
        assert metrics.failovers_total == 1

    def test_retry_budget_exhaustion_fails_request(self, model):
        """A request whose replicas keep dying under it is failed
        after max_retries, not retried forever."""
        cfg, params = model
        fi = FaultInjector()
        fi.crash_replica("replica-0", at_step=1)
        fi.crash_replica("replica-1", at_step=1)
        pool, reps, metrics = _make_chaos_pool(
            cfg, params, fi, n_replicas=2, max_retries=1
        )
        req = reps[0].scheduler.submit(
            _prompts((5,), seed=3)[0], deadline_s=600.0
        )
        _drive(reps)
        # crashed on replica-0 (retry 1 -> replica-1), crashed again:
        # retry 2 > budget 1 -> FAILED... unless no target remained,
        # which also fails it. Either way: terminal, not stuck.
        assert req.state is RequestState.FAILED
        assert metrics.failed_total == 1

    def test_failure_without_callback_fails_inflight(self, model):
        cfg, params = model
        fi = FaultInjector()
        fi.crash_replica("solo", at_step=1)
        eng = _engine(cfg, params, chaos=fi, chaos_tag="solo")
        metrics = ServingMetrics()
        sched = RequestScheduler(eng, metrics=metrics)
        req = sched.submit(_prompts((5,), seed=3)[0], deadline_s=600.0)
        while sched.pump():
            pass
        assert sched.crashed
        assert req.state is RequestState.FAILED
        assert metrics.failed_total == 1
        # a crashed scheduler 429s new work until restarted
        with pytest.raises(AdmissionError):
            sched.submit(_prompts((4,), seed=4)[0])

    def test_readmit_sheds_expired_deadline(self, model):
        """Failover never violates the SLO contract: a request whose
        deadline passed while its replica died is shed, not resumed."""
        cfg, params = model
        t = [0.0]
        fi = FaultInjector()
        fi.crash_replica("replica-0", at_step=1)
        metrics = ServingMetrics()
        pool = ReplicaPool(metrics=metrics, clock=lambda: t[0])
        reps = []
        for i in range(2):
            tag = f"replica-{i}"
            eng = _engine(cfg, params, chaos=fi, chaos_tag=tag)
            sched = RequestScheduler(
                eng, metrics=metrics, clock=lambda: t[0]
            )
            rep = InferenceReplica(tag, sched, chaos=fi)
            pool.add(rep)
            reps.append(rep)
        req = reps[0].scheduler.submit(
            _prompts((5,), seed=5)[0], deadline_s=10.0
        )
        reps[0].scheduler.pump()      # admits; step 0 decodes
        t[0] = 11.0                   # deadline passes mid-flight
        reps[0].scheduler.pump()      # step 1: crash -> evacuation
        assert req.state is RequestState.SHED
        assert metrics.shed_total == 1
        assert metrics.failovers_total == 0


# ---------------------------------------------------------------------------
# breaker-driven probation: ejection -> backoff -> restart -> re-admit


class TestAsyncParity:
    """async_depth=1 must be an invisible optimization: the same
    interleaving of submit/cancel/step against depth 0 and depth 1
    engines yields byte-identical streams for every surviving
    request. Cancelled requests are excluded from the byte compare —
    a cancel landing between a dispatch and its harvest legitimately
    truncates the stream one dispatch earlier than the sync engine
    would (the tokens existed on device but were never surfaced) —
    but their side effects (freed slot, admission order) must still
    leave every OTHER stream untouched."""

    def _interleaved(self, cfg, params, depth, seed, engine_kw=None):
        rng = np.random.default_rng(seed)
        eng = _engine(
            cfg, params, n_slots=2, async_depth=depth,
            **(engine_kw or {}),
        )
        prompts = _prompts((5, 9, 3, 7, 4, 6, 8, 5), seed=seed)
        emitted = {}
        submitted = []
        cancelled = set()
        pi = 0
        # the op sequence depends only on (rng, host-deterministic
        # bookkeeping), never on step() results — so both depths
        # replay the exact same interleaving
        for _ in range(120):
            r = rng.random()
            if r < 0.35 and pi < len(prompts):
                idx = eng.submit(prompts[pi])
                submitted.append(idx)
                emitted[idx] = []
                pi += 1
            elif r < 0.5 and submitted:
                victim = submitted[
                    int(rng.integers(len(submitted)))
                ]
                if victim not in cancelled:
                    eng.cancel(victim)
                    cancelled.add(victim)
            else:
                for idx, toks, _fin in eng.step():
                    emitted[idx].extend(toks)
        while eng.has_work():
            for idx, toks, _fin in eng.step():
                emitted[idx].extend(toks)
        survivors = {
            i: t for i, t in emitted.items() if i not in cancelled
        }
        return survivors, cancelled

    @pytest.mark.parametrize("seed", [0, 1, 2, 3, 4])
    def test_fuzzed_submit_cancel_interleaving_parity(
        self, model, seed
    ):
        cfg, params = model
        sync, sync_cancelled = self._interleaved(
            cfg, params, 0, seed
        )
        async_, async_cancelled = self._interleaved(
            cfg, params, 1, seed
        )
        assert async_cancelled == sync_cancelled
        assert async_.keys() == sync.keys()
        for idx in sync:
            assert async_[idx] == sync[idx], (
                f"seed={seed} request {idx} diverged across depths"
            )

    @pytest.mark.parametrize(
        "engine_kw",
        [{"spec_draft_len": 4}, {"prefix_cache_rows": 4}],
        ids=["spec", "prefix"],
    )
    def test_fuzzed_interleaving_parity_variants(
        self, model, engine_kw
    ):
        cfg, params = model
        sync, _ = self._interleaved(
            cfg, params, 0, 7, engine_kw
        )
        async_, _ = self._interleaved(
            cfg, params, 1, 7, engine_kw
        )
        assert async_ == sync


class TestProbationCycle:
    def test_dead_replica_reenters_pool_via_probation(self, model):
        cfg, params = model
        t = [0.0]
        fi = FaultInjector()
        fi.crash_replica("replica-0", at_step=2)
        pool, reps, metrics = _make_chaos_pool(
            cfg, params, fi, n_replicas=2, clock=lambda: t[0]
        )
        prompts = _prompts((5, 9), seed=6)
        want = _reference(cfg, params, prompts)
        reqs = [
            reps[0].scheduler.submit(p, deadline_s=600.0)
            for p in prompts
        ]
        _drive(reps)
        for p, r in zip(prompts, reqs):
            assert r.tokens == want[tuple(p)]
        assert not reps[0].healthy
        b = pool.breakers["replica-0"]
        assert b.state == OPEN

        # probation probe fails (tag still crashed): backoff grows
        pool.check_replicas()
        assert not reps[0].healthy
        t[0] += 0.01
        pool.check_replicas()         # inside backoff: probe skipped
        assert b.state == OPEN

        # fault clears; past the backoff deadline the probation probe
        # passes, the crashed scheduler restarts, replica re-admits
        fi.revive("replica-0")
        t[0] += 60.0
        pool.check_replicas()
        assert reps[0].healthy
        assert not reps[0].scheduler.crashed
        assert metrics.replica_readmissions == 1

        # and it actually serves again, correctly
        req = reps[0].scheduler.submit(prompts[0], deadline_s=600.0)
        while reps[0].scheduler.pump():
            pass
        assert req.tokens == want[tuple(prompts[0])]


# ---------------------------------------------------------------------------
# engine-level cancel/reset


class TestEngineLifecycle:
    def test_cancel_frees_slot_and_prefix_pin(self, model):
        cfg, params = model
        # async_depth=0: nothing is left in flight behind a step(), so
        # a cancel of everything leaves no work at once
        eng = _engine(
            cfg, params, n_slots=1, prefix_cache_rows=4, async_depth=0
        )
        prompts = _prompts((20, 5), seed=7)
        a = eng.submit(prompts[0])
        b = eng.submit(prompts[1])
        eng.step()
        assert eng.active_count() == 1
        eng.cancel(a)                  # live in the only slot
        eng.cancel(b)                  # still queued
        assert eng.active_count() == 0 and not eng.has_work()
        assert eng._slot_row[0] is None   # prefix pin released
        # the freed slot admits and serves fresh work
        c = eng.submit(prompts[1])
        while eng.has_work():
            eng.step()
        assert len(eng.retire(c)) > 0

    def test_reset_rebuilds_device_state(self, model):
        cfg, params = model
        eng = _engine(cfg, params, prefix_cache_rows=4)
        prompts = _prompts((5, 9), seed=8)
        want = [
            list(o) for o in _engine(
                cfg, params, prefix_cache_rows=4
            ).generate_all(prompts)
        ]
        eng.submit(prompts[0])
        eng.step()
        eng.reset()
        assert not eng.has_work() and eng.active_count() == 0
        got = [list(o) for o in eng.generate_all(prompts)]
        assert got == want


# ---------------------------------------------------------------------------
# gateway: client disconnect mid-stream cancels the request


class TestGatewayDisconnect:
    def test_disconnect_cancels_and_frees_slot(self, model):
        cfg, params = model
        fi = FaultInjector()
        # stretch every dispatch so the client can vanish mid-stream
        fi.slow_replica("gw", delay_s=0.05)
        eng = _engine(
            cfg, params, n_slots=1, max_len=256,
            max_new_tokens=128, chunk=1, chaos=fi, chaos_tag="gw",
        )
        metrics = ServingMetrics()
        sched = RequestScheduler(eng, metrics=metrics)
        sched.start()
        gw = ServingGateway(sched, metrics=metrics)
        gw.start()
        try:
            # raw socket (not http.client, which drops its socket
            # reference on Connection: close responses): we need to
            # own the fd to force an RST disconnect
            body = json.dumps(
                {
                    "tokens": _prompts((5,), seed=9)[0],
                    "max_new": 128,
                    "deadline_s": 600,
                }
            ).encode()
            sock = socket.create_connection(
                ("127.0.0.1", gw.port), timeout=30
            )
            sock.sendall(
                b"POST /v1/generate HTTP/1.1\r\n"
                b"Host: localhost\r\n"
                b"Content-Type: application/json\r\n"
                + b"Content-Length: %d\r\n\r\n" % len(body)
                + body
            )
            buf = b""
            while b'"tokens"' not in buf:   # one real chunk arrived
                chunk = sock.recv(4096)
                assert chunk, "stream closed before first chunk"
                buf += chunk
            assert b"200" in buf.split(b"\r\n", 1)[0]
            # hard disconnect: RST on close, so the gateway's next
            # write raises instead of filling a dead socket buffer
            sock.setsockopt(
                socket.SOL_SOCKET,
                socket.SO_LINGER,
                struct.pack("ii", 1, 0),    # onoff=1, linger=0
            )
            sock.close()
            deadline = time.monotonic() + 30.0
            while time.monotonic() < deadline:
                if metrics.cancelled_total >= 1:
                    break
                time.sleep(0.05)
            assert metrics.cancelled_total == 1
            # the slot freed long before the 128-token stream would
            # have finished decoding
            deadline = time.monotonic() + 10.0
            while time.monotonic() < deadline:
                if sched.active_count() == 0 and \
                        eng.active_count() == 0:
                    break
                time.sleep(0.05)
            assert sched.active_count() == 0
            assert eng.active_count() == 0
        finally:
            gw.stop()
            sched.stop()
