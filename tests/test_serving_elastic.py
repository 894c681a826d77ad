"""Elastic mesh serving: chip-loss shrink/grow + drain-free refresh.

The contract under test (serving/elastic.py + engine/scheduler/pool
hooks):

  - a replica that loses a chip mid-decode re-forms LIVE at the
    largest valid smaller tp and completes every in-flight request
    byte-identically to a run that never lost the chip (greedy AND
    sampled — the journaled per-request key stream survives the
    replay), leaking zero pages and zero journal entries;
  - when the chip comes back, the replica grows back to its
    constructed tp and keeps serving;
  - a shrunk replica is DEGRADED, not dead: the pool marks it,
    routes around nothing, and never feeds the circuit breaker;
  - weight refreshes are version-fenced: deferred swaps commit only
    at an idle boundary (no request ever sees two versions), `raise`
    refuses mid-drain, `live` replays opted-in slots, and a poisoned
    tree rolls back leaving the old version serving.

Everything is driven through chaos.py's seeded FaultInjector —
deterministic faults, no monkeypatching — on the conftest-forced
8-device CPU host.
"""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.models import llama
from dlrover_tpu.serving.chaos import ChipLost, FaultInjector
from dlrover_tpu.serving.engine import ContinuousBatcher
from dlrover_tpu.serving.gateway import ServingGateway
from dlrover_tpu.serving.metrics import ServingMetrics
from dlrover_tpu.serving.failover import CLOSED
from dlrover_tpu.serving.replica import InferenceReplica, ReplicaPool
from dlrover_tpu.serving.scheduler import (
    RequestScheduler,
    RequestState,
    SloConfig,
)

multi_device = pytest.mark.skipif(
    len(jax.devices()) < 2, reason="needs >= 2 devices for tp=2"
)
four_device = pytest.mark.skipif(
    len(jax.devices()) < 4, reason="needs >= 4 devices for tp=4"
)


@pytest.fixture(scope="module")
def model():
    cfg = dataclasses.replace(
        llama.LlamaConfig.tiny(), dtype=jnp.float32
    )
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


@pytest.fixture(scope="module")
def model4():
    # 4 KV heads so the mesh factory admits tp=4 (tiny() has 2)
    cfg = dataclasses.replace(
        llama.LlamaConfig.tiny(n_kv_heads=4), dtype=jnp.float32
    )
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 250, size=n).tolist() for n in lengths]


def _engine(cfg, params, **kw):
    # chunk small relative to max_new so one drain spans several
    # engine steps — a mid-decode fault plan has steps to land on
    kw.setdefault("n_slots", 3)
    kw.setdefault("max_len", 64)
    kw.setdefault("max_new_tokens", 12)
    kw.setdefault("chunk", 2)
    kw.setdefault("pad_id", -1)
    return ContinuousBatcher(cfg, params, **kw)


def _drive(eng, prompts, max_iters=400):
    """Submit and run to completion, resizing live on chip loss.
    Returns (continuations in submission order, resize reports)."""
    idxs = [eng.submit(pr) for pr in prompts]
    reports = []
    for _ in range(max_iters):
        if not eng.has_work():
            break
        try:
            eng.step()
        except ChipLost:
            reports.append(eng.resize(eng.surviving_chips()))
    else:
        raise AssertionError("engine did not drain")
    return [list(eng._requests[i].out) for i in idxs], reports


def _pump_all(scheds, max_iters=600):
    scheds = scheds if isinstance(scheds, list) else [scheds]
    for _ in range(max_iters):
        if not any(s.pump() for s in scheds):
            return
    raise AssertionError("scheduler did not drain")


# ---------------------------------------------------------------------------
# shrink-mid-decode parity sweep


# every axis value (layout, sampling, prefix/spec feature, async
# depth) appears at least twice across the sweep; the fault step is
# fuzzed per-case from the injector's own seed
SHRINK_CASES = [
    # layout, temperature, feature,  async_depth, seed
    ("dense", 0.0, "plain", 0, 11),
    ("dense", 0.0, "spec", 1, 12),
    ("dense", 0.8, "prefix", 0, 13),
    ("dense", 0.8, "plain", 1, 14),
    ("paged", 0.0, "prefix", 1, 15),
    ("paged", 0.0, "spec", 0, 16),
    ("paged", 0.8, "plain", 0, 17),
    ("paged", 0.8, "prefix", 1, 18),
]


def _case_kw(layout, temperature, feature, async_depth):
    kw = dict(async_depth=async_depth)
    if layout == "paged":
        # auto page size / dense-equivalent pool: stays valid under
        # any spec_draft_len (bank_len must split into whole pages)
        kw.update(kv_layout="paged")
    if temperature > 0.0:
        kw.update(temperature=temperature, top_k=5)
    if feature == "prefix":
        kw.update(prefix_cache_rows=4, prefix_block=8)
    if feature == "spec":
        kw.update(spec_draft_len=3)
    return kw


@multi_device
class TestShrinkParity:
    @pytest.mark.parametrize(
        "layout,temperature,feature,async_depth,seed", SHRINK_CASES
    )
    def test_tp2_to_tp1_mid_decode(
        self, model, layout, temperature, feature, async_depth, seed
    ):
        cfg, params = model
        kw = _case_kw(layout, temperature, feature, async_depth)
        prompts = _prompts((6, 9, 13), seed=seed)

        oracle = _engine(cfg, params, mesh_spec=2, **kw)
        want = [list(o) for o in oracle.generate_all(prompts)]

        fi = FaultInjector(seed=seed)
        step = fi.lose_chip("e", 1, between=(1, 4))
        eng = _engine(
            cfg, params, mesh_spec=2, chaos=fi, chaos_tag="e", **kw
        )
        got, reports = _drive(eng, prompts)

        # the fault must actually land (non-vacuous sweep)
        assert fi.fired == [("engine", "e", step)]
        assert [r.direction for r in reports] == ["shrink"]
        assert (reports[0].old_tp, reports[0].new_tp) == (2, 1)
        assert eng.mesh_tp == 1 and eng.mesh is None
        assert got == want, f"parity broke after shrink @step {step}"
        if layout == "paged":
            eng.allocator.check()  # zero leaked pages
        stats = eng.elastic_stats()
        assert stats["resize_shrink"] == 1.0
        assert stats["tp"] == 1.0 and stats["full_tp"] == 2.0
        assert stats["resize_downtime_ms"] > 0.0

    @four_device
    @pytest.mark.parametrize(
        "layout,temperature",
        [("paged", 0.0), ("dense", 0.8)],
    )
    def test_tp4_to_tp2_mid_decode(self, model4, layout, temperature):
        # losing 1 of 4 chips leaves 3: the largest tp dividing 4 KV
        # heads that fits is 2, not 3 — the factory must skip the
        # invalid degree, not crash on it
        cfg, params = model4
        kw = _case_kw(layout, temperature, "plain", 0)
        prompts = _prompts((6, 9, 13), seed=21)

        oracle = _engine(cfg, params, mesh_spec=4, **kw)
        want = [list(o) for o in oracle.generate_all(prompts)]

        fi = FaultInjector(seed=21)
        fi.lose_chip("e", 1, at_step=2)
        eng = _engine(
            cfg, params, mesh_spec=4, chaos=fi, chaos_tag="e", **kw
        )
        got, reports = _drive(eng, prompts)

        assert (reports[0].old_tp, reports[0].new_tp) == (4, 2)
        assert eng.mesh_tp == 2
        assert got == want
        if layout == "paged":
            eng.allocator.check()

    @four_device
    def test_double_loss_shrinks_again(self, model4):
        # two separate chip losses on a tp=4 slice: the first drops
        # to tp=2 (3 survivors, 3 doesn't divide the KV heads); the
        # second leaves 2 survivors — already the serving tp, so the
        # resize is a reported noop and the drain just continues
        cfg, params = model4
        prompts = _prompts((6, 9), seed=31)
        oracle = _engine(cfg, params, mesh_spec=4)
        want = [list(o) for o in oracle.generate_all(prompts)]

        fi = FaultInjector(seed=31)
        fi.lose_chip("e", 1, at_step=1)
        fi.lose_chip("e", 1, at_step=3)
        eng = _engine(
            cfg, params, mesh_spec=4, chaos=fi, chaos_tag="e"
        )
        got, reports = _drive(eng, prompts)
        assert [r.direction for r in reports] == ["shrink", "noop"]
        assert got == want


@pytest.mark.parametrize("temperature", [0.0, 0.8], ids=["greedy", "sampled"])
def test_a_chip_lost_outside_the_slice_loses_no_token(model, temperature):
    """A resize that turns out to be a no-op (the chip lost was none
    of this slice's: tp stays 1) replays nothing, so the dispatch in
    flight when the fault was injected has to survive it: the next
    step harvests it. (It used to be dropped by the raising step, and
    its tokens with it, while the device state had moved on.)"""
    cfg, params = model
    kw = dict(temperature=temperature, top_k=5, seed=3)
    prompts = _prompts((6, 9, 13), seed=41)
    want = [
        list(o)
        for o in _engine(cfg, params, async_depth=0, **kw).generate_all(
            prompts
        )
    ]
    fi = FaultInjector(seed=41)
    fi.lose_chip("e", 1, at_step=2)
    eng = _engine(cfg, params, chaos=fi, chaos_tag="e", **kw)
    idxs = [eng.submit(pr) for pr in prompts]
    reports = []
    for _ in range(400):
        if not eng.has_work():
            break
        try:
            eng.step()
        except ChipLost:
            assert eng._inflight is not None  # left for the next step
            reports.append(eng.resize(1))
            assert eng._inflight is not None  # and by the no-op too
    assert [r.direction for r in reports] == ["noop"]
    assert [list(eng._requests[i].out) for i in idxs] == want


# ---------------------------------------------------------------------------
# grow-back


@multi_device
class TestGrowBack:
    def test_tp2_round_trip(self, model):
        cfg, params = model
        batch1 = _prompts((6, 9, 13), seed=41)
        batch2 = _prompts((7, 11), seed=42)
        oracle = _engine(cfg, params, mesh_spec=2)
        want1 = [list(o) for o in oracle.generate_all(batch1)]
        want2 = [list(o) for o in oracle.generate_all(batch2)]

        fi = FaultInjector(seed=41)
        fi.lose_chip("e", 1, at_step=2)
        eng = _engine(
            cfg, params, mesh_spec=2, chaos=fi, chaos_tag="e",
            kv_layout="paged", page_size=8, n_pages=32,
        )
        got1, reports = _drive(eng, batch1)
        assert eng.mesh_tp == 1
        assert got1 == want1

        # chip relinked: the same resize entry point grows back to
        # the constructed tp and the replica keeps serving
        fi.restore_chip("e")
        report = eng.resize()
        assert report.direction == "grow"
        assert (report.old_tp, report.new_tp) == (1, 2)
        assert eng.mesh_tp == 2 and eng.mesh is not None
        got2, more = _drive(eng, batch2)
        assert more == [] and got2 == want2
        eng.allocator.check()
        stats = eng.elastic_stats()
        assert stats["resize_shrink"] == 1.0
        assert stats["resize_grow"] == 1.0

    @four_device
    def test_tp4_round_trip(self, model4):
        cfg, params = model4
        batch1 = _prompts((6, 9), seed=43)
        batch2 = _prompts((8,), seed=44)
        oracle = _engine(cfg, params, mesh_spec=4)
        want1 = [list(o) for o in oracle.generate_all(batch1)]
        want2 = [list(o) for o in oracle.generate_all(batch2)]

        fi = FaultInjector(seed=43)
        fi.lose_chip("e", 2, at_step=1)
        eng = _engine(
            cfg, params, mesh_spec=4, chaos=fi, chaos_tag="e"
        )
        got1, reports = _drive(eng, batch1)
        assert (reports[0].old_tp, reports[0].new_tp) == (4, 2)
        assert got1 == want1

        fi.restore_chip("e")
        report = eng.resize()
        assert (report.old_tp, report.new_tp) == (2, 4)
        got2, _ = _drive(eng, batch2)
        assert got2 == want2

    def test_grow_never_exceeds_constructed_tp(self, model):
        # 8 healthy devices but the replica was built at tp=2: grow
        # is a return to the constructed slice, not an expansion past
        # the params' sharding contract
        cfg, params = model
        eng = _engine(cfg, params, mesh_spec=2)
        report = eng.resize(8)
        assert report.direction == "noop"
        assert eng.mesh_tp == 2


# ---------------------------------------------------------------------------
# scheduler path: ChipLost inside pump


@multi_device
class TestSchedulerChipLoss:
    def _sched(self, cfg, params, fi, tag="r0", **kw):
        eng = _engine(
            cfg, params, mesh_spec=2, chaos=fi, chaos_tag=tag, **kw
        )
        return RequestScheduler(eng, SloConfig(max_new_tokens=12))

    def test_pump_resizes_and_completes_every_request(self, model):
        cfg, params = model
        prompts = _prompts((6, 9, 13), seed=51)
        oracle = _engine(cfg, params, mesh_spec=2)
        want = [list(o) for o in oracle.generate_all(prompts)]

        fi = FaultInjector(seed=51)
        step = fi.lose_chip("r0", 1, between=(1, 4))
        sched = self._sched(cfg, params, fi)
        reqs = [sched.submit(p, max_new=12) for p in prompts]
        _pump_all(sched)

        assert fi.fired == [("engine", "r0", step)]
        assert not sched.crashed  # degraded, never crashed
        # success 1.0: every admitted request completes
        assert [r.state for r in reqs] == [RequestState.DONE] * 3
        assert [r.tokens for r in reqs] == want
        # zero orphaned journal entries after the drain
        assert sched.journal._keys == {}
        assert sched.engine.mesh_tp == 1
        assert sched.metrics.resize_total == {"shrink": 1, "grow": 0}

    def test_elastic_resize_off_falls_back_to_crash_path(self, model):
        # the knob: with live resize disabled, ChipLost takes the
        # ordinary crash/failover path — tickets snapshot, the
        # scheduler marks itself crashed
        cfg, params = model
        fi = FaultInjector(seed=52)
        fi.lose_chip("r0", 1, at_step=1)
        sched = self._sched(cfg, params, fi)
        sched.elastic_resize = False
        tickets = []
        sched.on_failure = lambda s, ts, exc: tickets.extend(ts)
        reqs = [sched.submit(p, max_new=12) for p in _prompts((6, 9))]
        for _ in range(50):
            if not sched.pump():
                break
        assert sched.crashed
        assert len(tickets) == len(reqs)
        assert sched.engine.mesh_tp == 2  # untouched

    def test_total_chip_loss_falls_back_to_crash_path(self, model):
        # losing EVERY chip of the slice is not resizable: the
        # in-pump resize raises, and the handler falls through to the
        # ordinary crash/failover path instead of spinning
        cfg, params = model
        fi = FaultInjector(seed=54)
        fi.lose_chip("r0", 2, at_step=1)
        sched = self._sched(cfg, params, fi)
        tickets = []
        sched.on_failure = lambda s, ts, exc: tickets.extend(ts)
        sched.submit(_prompts((6,), 54)[0], max_new=12)
        for _ in range(50):
            if not sched.pump():
                break
        assert sched.crashed
        assert len(tickets) == 1

    def test_resize_engine_entry_point(self, model):
        # operator-facing resize without a fault in flight: the
        # scheduler-level wrapper takes its own lock and delegates
        cfg, params = model
        fi = FaultInjector(seed=53)
        sched = self._sched(cfg, params, fi)
        report = sched.resize_engine(1)
        assert (report.old_tp, report.new_tp) == (2, 1)
        assert sched.resize_engine(2).direction == "grow"


# ---------------------------------------------------------------------------
# degraded pool state (no breaker strikes for shrunk replicas)


@multi_device
class TestDegradedPool:
    def test_shrunk_replica_degraded_not_ejected(self, model):
        cfg, params = model
        fi = FaultInjector(seed=61)
        fi.lose_chip("replica-0", 1, at_step=1)
        metrics = ServingMetrics()
        pool = ReplicaPool(metrics=metrics)
        eng = _engine(
            cfg, params, mesh_spec=2, chaos=fi, chaos_tag="replica-0"
        )
        sched = RequestScheduler(
            eng, SloConfig(max_new_tokens=12), metrics=metrics
        )
        rep = InferenceReplica("replica-0", sched, chaos=fi)
        pool.add(rep)

        reqs = [
            pool.submit(p, max_new=12) for p in _prompts((6, 9), 61)
        ]
        _pump_all(sched)
        assert [r.state for r in reqs] == [RequestState.DONE] * 2
        assert eng.mesh_tp == 1

        pool.check_replicas()
        breaker = pool.breakers["replica-0"]
        # degraded-but-alive: visible in meta, still routable, and
        # the breaker never saw a strike
        assert rep.degraded and rep.healthy
        assert breaker.state == CLOSED and breaker.strikes == 0
        assert pool.healthy_replicas() == [rep]
        assert metrics.replica_degradations == 1

        # probation re-probe grows it back once the chip returns
        fi.restore_chip("replica-0")
        pool.check_replicas()
        assert not rep.degraded
        assert eng.mesh_tp == 2
        assert breaker.state == CLOSED and breaker.strikes == 0

    def test_pool_check_resizes_without_a_pump_in_flight(self, model):
        # the deficit can surface between requests: an idle replica's
        # health check alone must shrink it (and mark it degraded)
        # before the next admission dispatches onto a dead chip
        cfg, params = model
        fi = FaultInjector(seed=62)
        eng = _engine(
            cfg, params, mesh_spec=2, chaos=fi, chaos_tag="replica-0"
        )
        sched = RequestScheduler(eng, SloConfig(max_new_tokens=12))
        rep = InferenceReplica("replica-0", sched, chaos=fi)
        pool = ReplicaPool()
        pool.add(rep)

        # the deficit lands outside any scheduler pump (the fault
        # fires against a bare step hook) — the pool's health pass
        # alone must shrink the idle replica and mark it degraded
        fi.lose_chip("replica-0", 1, at_step=0)
        with pytest.raises(ChipLost):
            fi.on_engine_step("replica-0", 0)
        pool.check_replicas()
        assert rep.degraded and rep.healthy
        assert eng.mesh_tp == 1
        # and it still serves at the shrunk tp
        req = sched.submit(_prompts((6,), 62)[0], max_new=12)
        _pump_all(sched)
        assert req.state is RequestState.DONE

    def test_degraded_rides_health_meta(self, model):
        cfg, params = model
        eng = _engine(cfg, params, mesh_spec=2)
        sched = RequestScheduler(eng, SloConfig())
        rep = InferenceReplica("r", sched)
        assert rep._meta() is not None
        rep.degraded = True
        assert json.loads(rep._meta())["degraded"] is True


# ---------------------------------------------------------------------------
# drain-free weight refresh (version fence)


class TestWeightRefresh:
    def _bumped(self, params):
        return jax.tree_util.tree_map(lambda x: x * 1.01, params)

    def test_idle_refresh_commits_immediately(self, model):
        cfg, params = model
        eng = _engine(cfg, params)
        assert eng.weight_version == 0
        eng.update_params(self._bumped(params))
        assert eng.weight_version == 1
        out = eng.generate_all(_prompts((6,), 71))
        assert len(out[0]) > 0

    def test_defer_fences_each_request_to_one_version(self, model):
        cfg, params = model
        eng = _engine(cfg, params, weight_refresh_mode="defer")
        i0 = eng.submit(_prompts((6,), 72)[0])
        eng.step()  # mid-drain
        eng.update_params(self._bumped(params))
        # staged, not committed: the in-flight request keeps its
        # version to the end of its drain
        assert eng.weight_version == 0
        while eng.has_work():
            eng.step()
        assert eng._requests[i0].versions == {0}
        # next submit crosses the fence: the swap commits first
        i1 = eng.submit(_prompts((7,), 73)[0])
        assert eng.weight_version == 1
        while eng.has_work():
            eng.step()
        assert eng._requests[i1].versions == {1}
        stats = eng.elastic_stats()
        assert stats["refresh_deferred"] == 1.0
        assert stats["refresh_committed"] == 1.0

    def test_raise_mode_refuses_mid_drain(self, model):
        cfg, params = model
        eng = _engine(cfg, params, weight_refresh_mode="raise")
        eng.submit(_prompts((6,), 74)[0])
        eng.step()
        with pytest.raises(RuntimeError, match="in flight"):
            eng.update_params(self._bumped(params))
        while eng.has_work():
            eng.step()
        eng.update_params(self._bumped(params))  # idle: fine
        assert eng.weight_version == 1

    def test_live_mode_replays_under_new_version(self, model):
        cfg, params = model
        eng = _engine(cfg, params, weight_refresh_mode="live")
        idx = eng.submit(_prompts((6,), 75)[0])
        eng.step()
        eng.update_params(self._bumped(params))
        assert eng.weight_version == 1
        while eng.has_work():
            eng.step()
        # the opted-in live swap is the ONE case a request may span
        # two versions — and only via replay, never a mixed dispatch
        assert eng._requests[idx].versions <= {0, 1}
        assert 1 in eng._requests[idx].versions
        assert eng.elastic_stats()["replayed_requests"] >= 1.0

    def test_poisoned_refresh_rolls_back(self, model):
        cfg, params = model
        eng = _engine(cfg, params)
        leaves, treedef = jax.tree_util.tree_flatten(params)
        leaves = [jnp.zeros((3,), jnp.float32)] + leaves[1:]
        poisoned = jax.tree_util.tree_unflatten(treedef, leaves)
        baseline = [list(o) for o in
                    eng.generate_all(_prompts((6,), 76))]
        with pytest.raises(ValueError):
            eng.update_params(poisoned)
        # old version still serving, byte-identically
        assert eng.weight_version == 0
        assert eng.elastic_stats()["refresh_rolled_back"] == 1.0
        again = [list(o) for o in
                 eng.generate_all(_prompts((6,), 76))]
        assert again == baseline

    def test_refresh_retires_stale_program_cache_keys(self, model):
        cfg, params = model
        eng = _engine(cfg, params)
        old = list(eng._bound_keys)
        assert old, "engine must record its bound program keys"
        eng.update_params(self._bumped(params))
        for cache, key in old:
            assert key not in cache, (
                "stale-version closure survived the refresh"
            )
        # and the new bindings are installed under the new version
        assert eng._bound_keys and eng._bound_keys != old

    def test_scheduler_refresh_entry_point(self, model):
        cfg, params = model
        eng = _engine(cfg, params)
        sched = RequestScheduler(eng, SloConfig(max_new_tokens=12))
        sched.refresh_weights(self._bumped(params))
        assert eng.weight_version == 1
        req = sched.submit(_prompts((6,), 77)[0], max_new=12)
        _pump_all(sched)
        assert req.state is RequestState.DONE
        assert sched.journal._keys == {}


# ---------------------------------------------------------------------------
# metrics + gateway exposition


class TestElasticMetrics:
    def test_update_and_render(self):
        m = ServingMetrics()
        m.update_elastic({
            "resize_shrink": 2.0, "resize_grow": 1.0,
            "refresh_committed": 3.0, "refresh_deferred": 1.0,
            "refresh_rolled_back": 1.0, "resize_downtime_ms": 12.5,
            "weight_version": 3.0, "tp": 1.0, "full_tp": 2.0,
            "replayed_requests": 4.0,
        })
        m.replica_degraded()
        text = m.render()
        for needle in (
            'serving_resize_total{direction="shrink"} 2',
            'serving_resize_total{direction="grow"} 1',
            'serving_weight_refresh_total{outcome="committed"} 3',
            'serving_weight_refresh_total{outcome="rolled_back"} 1',
            "serving_resize_downtime_ms_total 12.5",
            "serving_weight_version 3",
            "serving_replica_degradations_total 1",
        ):
            assert needle in text, text

    def test_counters_are_monotonic_across_replicas(self):
        # two replicas report through one metrics object: a fresher
        # replica's smaller counter must not walk totals backwards
        m = ServingMetrics()
        m.update_elastic({"resize_shrink": 3.0})
        m.update_elastic({"resize_shrink": 1.0})
        assert m.resize_total["shrink"] == 3
        m.update_elastic({"resize_downtime_ms": 9.0})
        m.update_elastic({"resize_downtime_ms": 2.0})
        assert m.resize_downtime_ms == 9.0


@multi_device
class TestGatewayElasticHealth:
    def test_healthz_reports_elastic_and_device_health(self, model):
        cfg, params = model
        fi = FaultInjector(seed=81)
        fi.lose_chip("e", 1, at_step=1)
        eng = _engine(
            cfg, params, mesh_spec=2, chaos=fi, chaos_tag="e"
        )
        sched = RequestScheduler(eng, SloConfig(max_new_tokens=12))
        gw = ServingGateway(sched)
        try:
            req = sched.submit(_prompts((6,), 81)[0], max_new=12)
            _pump_all(sched)
            assert req.state is RequestState.DONE
            health = gw._health()
            assert health["elastic"]["resize_total"] == {
                "shrink": 1, "grow": 0,
            }
            assert health["elastic"]["weight_version"] == 0
            assert health["elastic"]["resize_downtime_ms"] > 0.0
            assert health["device_health"] == {
                "chips_total": 2, "chips_lost": 1, "chips_up": 1,
            }
            text = sched.metrics.render()
            assert 'serving_resize_total{direction="shrink"} 1' in text
        finally:
            gw._server.server_close()
