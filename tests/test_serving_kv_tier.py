"""Host-DRAM KV tier (serving/kv_tier.py) acceptance tests.

The tier's whole contract is BYTE parity: demote→promote must hand
back exactly the bytes the device held (a promoted prefix row equals
the originally published one; a swapped-in page run equals what
deterministic replay would recompute), so a tiered engine's outputs
are identical to a kv_tier_bytes=0 oracle across every feature
combination. Plus: leak-freedom on every release path, the
crash-mid-demotion chaos leg (replay fallback, nothing stored,
nothing leaked), the scheduler's swap-to-host admission preemption,
the fleet digest map's host-tier bit, metrics exposition, and the
off-by-default guarantee (kv_tier_bytes=0 traces zero tier
programs)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _serve_oracle import lockstep_oracle
from dlrover_tpu.serving import kv_tier as kv_tier_mod
from dlrover_tpu.serving.affinity import (
    FleetDigestMap,
    prefix_digest_chain,
)
from dlrover_tpu.serving.chaos import FaultInjector
from dlrover_tpu.serving.engine import ContinuousBatcher
from dlrover_tpu.serving.kv_tier import HostKVTier
from dlrover_tpu.serving.metrics import ServingMetrics
from dlrover_tpu.serving.scheduler import (
    RequestScheduler,
    RequestState,
    SloConfig,
)
from dlrover_tpu.models import llama

pytestmark = pytest.mark.kv_tier


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


def _mk(cfg, params, **kw):
    kw.setdefault("n_slots", 1)
    kw.setdefault("max_len", 64)
    kw.setdefault("max_new_tokens", 6)
    kw.setdefault("chunk", 4)
    return ContinuousBatcher(cfg, params, **kw)


def _churn(cb, prompt_sets):
    """Sequential generate_all rounds: with prefix_cache_rows=1 every
    distinct published prefix evicts the previous one (the demotion
    trigger), and a repeat round re-requests what was demoted (the
    promotion trigger)."""
    out = []
    for prompts in prompt_sets:
        for p in prompts:
            out.append([int(t) for t in cb.generate_all([p])[0]])
    return out


def _entry_bytes(staged=64):
    """A synthetic staged dict whose nbytes the tier will count."""
    return {"k": np.zeros(staged, np.int8)}


# ---------------------------------------------------------------------------
# HostKVTier unit semantics (no engine, no device)


class TestHostKVTierUnit:
    def test_validation(self):
        with pytest.raises(ValueError):
            HostKVTier(0)
        with pytest.raises(ValueError):
            HostKVTier(-1)
        with pytest.raises(ValueError):
            HostKVTier(1024, block=0)

    def test_prefix_roundtrip_and_lru(self):
        tier = HostKVTier(150, block=2)
        toks_a = [1, 2, 3, 4]
        toks_b = [5, 6, 7, 8]
        assert tier.put_prefix(toks_a, _entry_bytes(64), 4)
        assert tier.put_prefix(toks_b, _entry_bytes(64), 4)
        # match walks deepest-first and finalizes
        ent = tier.match_prefix(toks_a + [9])
        assert ent is not None and ent.depth == 4
        assert ent.final and isinstance(ent.data["k"], np.ndarray)
        # a third entry must evict the LRU one — which is B, because
        # the match just touched A
        assert tier.put_prefix([9, 9, 9, 9], _entry_bytes(64), 4)
        assert tier.evictions == 1
        assert tier.match_prefix(toks_b) is None
        assert tier.match_prefix(toks_a) is not None

    def test_min_depth_gates_shallow_matches(self):
        # the tier only wins when strictly deeper than the radix
        # cache's own match: PCIe must beat recompute
        tier = HostKVTier(1 << 20, block=2)
        tier.put_prefix([1, 2], _entry_bytes(), 2)
        assert tier.match_prefix([1, 2, 3, 4], min_depth=2) is None
        assert tier.match_prefix([1, 2, 3, 4], min_depth=0) is not None

    def test_oversize_put_rejected_without_eviction(self):
        tier = HostKVTier(100, block=2)
        assert tier.put_prefix([1, 2], _entry_bytes(64), 2)
        assert not tier.put_prefix([3, 4], _entry_bytes(101), 2)
        assert tier.rejects == 1
        # the resident entry survived the rejected put
        assert tier.match_prefix([1, 2]) is not None
        assert tier.bytes_used == 64

    def test_pinned_entries_never_evicted(self):
        tier = HostKVTier(100, block=2)
        tier.put_prefix([1, 2], _entry_bytes(64), 2)
        ent = tier.match_prefix([1, 2])
        tier.acquire(ent)
        # needs eviction of the pinned entry -> reject, keep bytes
        assert not tier.put_prefix([3, 4], _entry_bytes(64), 2)
        assert tier.evictions == 0 and tier.rejects == 1
        tier.release(ent)
        assert tier.put_prefix([3, 4], _entry_bytes(64), 2)
        assert tier.evictions == 1

    def test_swap_entries_consumed_once_and_salted(self):
        tier = HostKVTier(1 << 20, block=2)
        toks = [1, 2, 3]
        tier.put_swap(toks, _entry_bytes(), 1, 8, salt="")
        tier.put_swap(toks, _entry_bytes(), 1, 8, salt="lora-a")
        # peek does not consume (OutOfPages retries keep the bytes);
        # consume pops exactly one salt's entry
        ent = tier.peek_swap(toks)
        assert ent is not None and ent.n_pages == 1
        assert tier.peek_swap(toks) is not None
        tier.consume(ent)
        assert tier.peek_swap(toks) is None
        assert tier.peek_swap(toks, salt="lora-a") is not None
        assert tier.swap_ins == 1

    def test_swap_replaced_same_key(self):
        # re-demoting the same folded sequence replaces, not leaks
        tier = HostKVTier(1 << 20, block=2)
        tier.put_swap([1, 2], _entry_bytes(64), 1, 8)
        tier.put_swap([1, 2], _entry_bytes(96), 1, 8)
        assert tier.entry_count("swap") == 1
        assert tier.bytes_used == 96

    def test_prefix_digests_match_affinity_chain(self):
        # what the tier advertises is exactly what a routed prompt's
        # digest chain will contain — the fleet `tier` bit contract
        tier = HostKVTier(1 << 20, block=2)
        toks = [4, 5, 6, 7]
        tier.put_prefix(toks, _entry_bytes(), 4)
        ads = tier.prefix_digests()
        assert ads == [prefix_digest_chain(toks, 2)[-1]]
        # swap entries never advertise
        tier.put_swap([9, 9], _entry_bytes(), 1, 8)
        assert len(tier.prefix_digests()) == 1

    def test_clear_and_stats_consistency(self):
        tier = HostKVTier(1 << 20, block=2)
        tier.put_prefix([1, 2], _entry_bytes(), 2)
        tier.put_swap([3, 4], _entry_bytes(), 1, 8)
        st = tier.stats()
        assert st["entries"] == 2
        assert st["bytes_used"] == tier.bytes_used > 0
        tier.clear()
        assert tier.entry_count() == 0 and tier.bytes_used == 0
        # counters survive a clear (Prometheus monotonicity)
        assert tier.stats()["demotions"] == 2


# ---------------------------------------------------------------------------
# demote→promote byte parity vs the no-tier oracle


TIER_CONFIGS = [
    ("greedy", {}),
    ("sampled", dict(temperature=0.8, top_k=20, seed=3)),
    ("spec", dict(spec_draft_len=4)),
    # the others keep a dispatch in flight (the engine's default)
    ("sync", dict(async_depth=0)),
]


class TestDemotePromoteParity:
    @pytest.mark.parametrize(
        "kw",
        [c[1] for c in TIER_CONFIGS],
        ids=[c[0] for c in TIER_CONFIGS],
    )
    def test_churn_parity_paged(self, model, kw):
        """Distinct >=block prompts through a 1-row radix cache force
        an eviction (demotion) per publish; the repeat round promotes
        them back. Outputs must equal the no-tier oracle's exactly —
        promoted bytes flow through the same install programs as
        originally published ones."""
        cfg, params = model
        prompts = _prompts((20, 21, 22, 23), seed=11)
        rounds = [prompts, prompts]
        o = _churn(
            _mk(cfg, params, kv_layout="paged",
                prefix_cache_rows=1, **kw),
            rounds,
        )
        cb = _mk(
            cfg, params, kv_layout="paged", prefix_cache_rows=1,
            kv_tier_bytes=32 << 20, **kw,
        )
        t = _churn(cb, rounds)
        assert o == t, kw
        st = cb.kv_tier_stats()
        assert st["demotions"] >= 3, st
        assert st["promotions"] >= 1, st
        assert st["promote_hits"] >= 1, st
        assert cb.paged_stats()["pages_promoted"] > 0
        cb.allocator.check()
        # the only pages still out belong to the live published
        # prefix row; a reset must hand back every page
        cb.reset()
        assert cb.allocator.used_pages == 0

    def test_churn_parity_dense(self, model):
        """The tier also backs the DENSE engine's prefix pool: same
        churn, same parity, no page pool involved."""
        cfg, params = model
        prompts = _prompts((20, 22, 24), seed=13)
        rounds = [prompts, prompts]
        o = _churn(_mk(cfg, params, prefix_cache_rows=1), rounds)
        cb = _mk(
            cfg, params, prefix_cache_rows=1, kv_tier_bytes=32 << 20
        )
        assert o == _churn(cb, rounds)
        st = cb.kv_tier_stats()
        assert st["demotions"] >= 2 and st["promotions"] >= 1

    def test_fuzzed_matrix(self, model):
        """Randomized lengths/knobs: paged × greedy/sampled ×
        prefix/spec × async 0/1 against the kv_tier_bytes=0 oracle."""
        cfg, params = model
        rng = np.random.default_rng(21)
        for trial in range(4):
            lengths = rng.integers(17, 30, size=4)
            prompts = _prompts(lengths, seed=300 + trial)
            kw = {}
            if rng.integers(2):
                kw["temperature"] = 0.7
                kw["seed"] = int(rng.integers(100))
            if rng.integers(2):
                kw["spec_draft_len"] = 4
            if rng.integers(2):
                kw["async_depth"] = 0
            rounds = [prompts, prompts]
            o = _churn(
                _mk(cfg, params, kv_layout="paged",
                    prefix_cache_rows=1, **kw),
                rounds,
            )
            cb = _mk(
                cfg, params, kv_layout="paged", prefix_cache_rows=1,
                kv_tier_bytes=32 << 20, **kw,
            )
            assert o == _churn(cb, rounds), (trial, kw)
            assert cb.kv_tier_stats()["demotions"] > 0, (trial, kw)
            cb.allocator.check()

    def test_promote_never_gate(self, model):
        """kv_tier_promote="never" demotes but never uploads: outputs
        still match (cold re-prefill is always correct), promotions
        stay zero."""
        cfg, params = model
        prompts = _prompts((20, 21, 22), seed=15)
        rounds = [prompts, prompts]
        o = _churn(
            _mk(cfg, params, kv_layout="paged", prefix_cache_rows=1),
            rounds,
        )
        cb = _mk(
            cfg, params, kv_layout="paged", prefix_cache_rows=1,
            kv_tier_bytes=32 << 20, kv_tier_promote="never",
        )
        assert o == _churn(cb, rounds)
        st = cb.kv_tier_stats()
        assert st["demotions"] > 0 and st["promotions"] == 0


# ---------------------------------------------------------------------------
# swap-to-host preemption


class TestSwapToHost:
    def test_pressure_swap_parity(self, model):
        """A pool too small for the working set preempts; with the
        tier on, victims swap to host and resume from the stored
        bytes instead of replay — byte-identical either way."""
        cfg, params = model
        rng = np.random.default_rng(7)
        prompts = [
            rng.integers(1, 250, size=int(n)).tolist()
            for n in rng.integers(12, 30, size=8)
        ]

        def run(**kw):
            cb = _mk(
                cfg, params, n_slots=3, max_new_tokens=12,
                kv_layout="paged", page_size=8, n_pages=14, **kw,
            )
            outs = cb.generate_all(prompts)
            return cb, [[int(t) for t in o] for o in outs]

        cb0, oracle = run()
        cb1, tiered = run(kv_tier_bytes=64 << 20)
        assert oracle == tiered
        assert cb0._swap_preemptions > 0, "scenario never preempted"
        st = cb1.kv_tier_stats()
        assert st["swap_outs"] > 0 and st["swap_ins"] > 0
        # every preemption resumed (success 1.0 under pressure)
        assert cb1._swap_resumes == cb1._swap_preemptions
        cb1.allocator.check()
        cb1.reset()
        assert cb1.allocator.used_pages == 0

    def test_swap_to_host_off_knob(self, model):
        """swap_to_host=False keeps the tier for prefixes but demotes
        no victims: swap counters stay zero, parity holds via the
        replay fallback."""
        cfg, params = model
        rng = np.random.default_rng(7)
        prompts = [
            rng.integers(1, 250, size=int(n)).tolist()
            for n in rng.integers(12, 30, size=6)
        ]

        def run(**kw):
            cb = _mk(
                cfg, params, n_slots=3, max_new_tokens=12,
                kv_layout="paged", page_size=8, n_pages=14, **kw,
            )
            return cb, [
                [int(t) for t in o] for o in cb.generate_all(prompts)
            ]

        _, oracle = run()
        cb, tiered = run(kv_tier_bytes=64 << 20, swap_to_host=False)
        assert oracle == tiered
        st = cb.kv_tier_stats()
        assert st["swap_outs"] == 0 and st["swap_ins"] == 0

    def test_scheduler_admission_preemption_swaps(self, model):
        """The scheduler's latency-over-batch preemption rides
        engine.swap_out: the victim's live run demotes, readmission
        promotes it back, and both requests finish byte-identical to
        undisturbed runs."""
        cfg, params = model
        rng = np.random.default_rng(7)
        p_batch = rng.integers(1, 250, size=9).tolist()
        p_lat = rng.integers(1, 250, size=6).tolist()
        eng = _mk(
            cfg, params, max_new_tokens=8, chunk=2, pad_id=-1,
            kv_layout="paged", kv_tier_bytes=32 << 20,
        )
        sched = RequestScheduler(eng, SloConfig())
        batch = sched.submit(
            p_batch, max_new=8, deadline_s=600.0, tier="batch"
        )
        sched.pump()
        sched.pump()  # decode a couple of tokens: victim mid-decode
        lat = sched.submit(
            p_lat, max_new=4, deadline_s=600.0, tier="latency"
        )
        sched.pump()
        assert batch.preemptions == 1
        assert eng.kv_tier_stats()["swap_outs"] == 1
        sched.run_to_completion()
        assert batch.state is RequestState.DONE
        assert lat.state is RequestState.DONE
        st = eng.kv_tier_stats()
        assert st["swap_ins"] == 1, st
        assert batch.tokens == lockstep_oracle(
            cfg, params, p_batch, 8
        )
        assert lat.tokens == lockstep_oracle(cfg, params, p_lat, 4)
        eng.allocator.check()


# ---------------------------------------------------------------------------
# leak-freedom on every release path


class TestLeakFreedom:
    def test_cancel_and_reset_leak_free(self, model):
        cfg, params = model
        cb = _mk(
            cfg, params, n_slots=2, kv_layout="paged",
            prefix_cache_rows=1, kv_tier_bytes=32 << 20,
        )
        prompts = _prompts((20, 21), seed=17)
        idx = [cb.submit(p, max_new=8) for p in prompts]
        for _ in range(3):
            cb.step()
        cb.cancel(idx[0])
        for _ in range(2):
            cb.step()
        cb.reset()
        cb.allocator.check()
        assert cb.allocator.used_pages == 0
        assert cb.kv_tier.entry_count() == 0  # reset clears the tier
        assert cb.kv_tier.bytes_used == 0
        # the engine still serves correctly after the reset
        out = [int(t) for t in cb.generate_all([prompts[0]])[0]]
        o = _mk(cfg, params, n_slots=2, kv_layout="paged")
        assert out == [int(t) for t in o.generate_all([prompts[0]])[0]]

    def test_tier_pressure_eviction_accounting(self, model):
        """A tier far too small for the churn set evicts/rejects
        constantly; byte accounting must stay exact (bytes_used ==
        sum of resident entries) and parity must hold."""
        cfg, params = model
        prompts = _prompts((20, 21, 22, 23, 24), seed=19)
        rounds = [prompts, prompts]
        o = _churn(
            _mk(cfg, params, kv_layout="paged", prefix_cache_rows=1),
            rounds,
        )
        # ~1-2 entries' worth of capacity
        cb = _mk(
            cfg, params, kv_layout="paged", prefix_cache_rows=1,
            kv_tier_bytes=24 << 10,
        )
        assert o == _churn(cb, rounds)
        tier = cb.kv_tier
        resident = sum(
            e.nbytes for e in tier._entries.values()
        )
        assert tier.bytes_used == resident
        assert tier.bytes_used <= tier.capacity_bytes
        assert tier.evictions + tier.rejects > 0
        cb.allocator.check()

    def test_chaos_crash_mid_demotion_falls_back_to_replay(
        self, model
    ):
        """The chaos leg: a fault injected inside the tier's record
        path fires mid-demotion. Nothing is stored, nothing leaks —
        the engine counts a demote failure and the affected prefix
        just dies the way it did before the tier existed; outputs
        stay byte-identical (success 1.0)."""
        cfg, params = model
        prompts = _prompts((20, 21, 22), seed=23)
        rounds = [prompts, prompts]
        o = _churn(
            _mk(cfg, params, kv_layout="paged", prefix_cache_rows=1),
            rounds,
        )
        fi = FaultInjector()
        fi.fail_engine_step("eng#kvtier", at_step=1)
        cb = _mk(
            cfg, params, kv_layout="paged", prefix_cache_rows=1,
            kv_tier_bytes=32 << 20, chaos=fi, chaos_tag="eng",
        )
        assert o == _churn(cb, rounds)
        tier = cb.kv_tier
        assert tier.demote_failures >= 1
        assert fi.fired, "fault never fired"
        # the crashed demotion recorded nothing
        assert tier.bytes_used == sum(
            e.nbytes for e in tier._entries.values()
        )
        cb.allocator.check()
        cb.reset()
        assert cb.allocator.used_pages == 0


# ---------------------------------------------------------------------------
# off-by-default: kv_tier_bytes=0 is bit-exact with zero new programs


class TestTierOffDefault:
    def test_default_engine_has_no_tier(self, model):
        cfg, params = model
        cb = _mk(cfg, params, kv_layout="paged", prefix_cache_rows=2)
        assert cb.kv_tier is None
        assert cb.kv_tier_stats() == {}

    def test_zero_tier_programs_traced_when_off(self, model):
        """The off-path guarantee the acceptance pins: with
        kv_tier_bytes=0 (the default) a full churn run traces NONE of
        the tier's transfer programs — no new program-cache keys."""
        cfg, params = model
        progs = [
            kv_tier_mod._row_slice_prog,
            kv_tier_mod._row_install_prog,
            kv_tier_mod._page_gather_prog,
            kv_tier_mod._page_scatter_prog,
            kv_tier_mod._pages_install_prog,
        ]
        before = [p._cache_size() for p in progs]
        cb = _mk(
            cfg, params, kv_layout="paged", prefix_cache_rows=1
        )
        _churn(cb, [_prompts((20, 21), seed=29)])
        after = [p._cache_size() for p in progs]
        assert before == after, "tier-off run traced tier programs"

    def test_knob_validation(self, model):
        cfg, params = model
        with pytest.raises(ValueError):
            _mk(cfg, params, kv_tier_bytes=-1)
        with pytest.raises(ValueError):
            _mk(
                cfg, params, kv_tier_bytes=1 << 20,
                kv_tier_promote="sometimes",
            )


# ---------------------------------------------------------------------------
# fleet routing: the digest map's host-tier bit


class TestFleetTierBit:
    def test_host_match_scores_between_depths(self):
        m = FleetDigestMap()
        chain = ["d0", "d1", "d2"]
        m.update("dev", ["d1"])                  # device-warm at 2
        m.update("host", (), host_digests=["d2"])  # tier-warm at 3
        m.update("shallow", ["d0"])              # device-warm at 1
        depths = m.match_depths(chain)
        # host tier at depth i scores i+0.5: deeper than any
        # SHALLOWER device match, shallower than the SAME depth
        assert depths["dev"] == 2
        assert depths["host"] == 2.5
        assert depths["shallow"] == 1
        assert depths["host"] > depths["dev"]

    def test_device_match_beats_host_at_same_depth(self):
        m = FleetDigestMap()
        m.update("a", ["d0"], host_digests=())
        m.update("b", (), host_digests=["d0"])
        depths = m.match_depths(["d0"])
        assert depths["a"] == 1 and depths["b"] == 0.5

    def test_drop_clears_host_index_too(self):
        m = FleetDigestMap()
        m.update("r", ["d0"], host_digests=["d1"])
        assert m.stats()["host_digests"] == 1
        m.drop("r")
        st = m.stats()
        assert st["digests"] == 0 and st["host_digests"] == 0

    def test_heartbeat_refresh_replaces_host_set(self):
        m = FleetDigestMap()
        m.update("r", (), host_digests=["d1", "d2"])
        m.update("r", (), host_digests=["d2", "d3"])
        depths = m.match_depths(["d1"])
        assert "r" not in depths
        assert m.match_depths(["d3"])["r"] == 0.5


# ---------------------------------------------------------------------------
# metrics exposition


class TestMetricsExposition:
    def test_update_and_render_families(self):
        m = ServingMetrics()
        m.update_kv_tier(
            {
                "bytes_used": 4096,
                "capacity_bytes": 65536,
                "entries": 3,
                "demotions": 5,
                "promotions": 2,
                "swap_outs": 1,
                "swap_ins": 1,
                "evictions": 4,
                "promote_hit_rate": 0.5,
            }
        )
        text = m.render()
        for needle in (
            "# TYPE serving_kv_tier_bytes gauge",
            "serving_kv_tier_bytes 4096",
            "serving_kv_tier_capacity_bytes 65536",
            "serving_kv_tier_entries 3",
            "# TYPE serving_kv_tier_demotions_total counter",
            "serving_kv_tier_demotions_total 5",
            "serving_kv_tier_promotions_total 2",
            "serving_kv_tier_swap_outs_total 1",
            "serving_kv_tier_swap_ins_total 1",
            "serving_kv_tier_evictions_total 4",
            "serving_kv_tier_promote_hit_rate 0.5",
        ):
            assert needle in text, needle

    def test_counters_monotone_under_stale_update(self):
        # a restarted engine reports zeros; exposition never regresses
        m = ServingMetrics()
        m.update_kv_tier({"demotions": 5, "swap_outs": 2})
        m.update_kv_tier({"demotions": 0, "swap_outs": 0})
        text = m.render()
        assert "serving_kv_tier_demotions_total 5" in text
        assert "serving_kv_tier_swap_outs_total 2" in text

    def test_scheduler_pump_feeds_tier_metrics(self, model):
        cfg, params = model
        metrics = ServingMetrics()
        eng = _mk(
            cfg, params, kv_layout="paged", prefix_cache_rows=1,
            kv_tier_bytes=32 << 20, pad_id=-1,
        )
        sched = RequestScheduler(eng, SloConfig(), metrics=metrics)
        for p in _prompts((20, 21, 20), seed=31):
            r = sched.submit(p, max_new=4, deadline_s=600.0)
            sched.run_to_completion()
            assert r.state is RequestState.DONE
        text = metrics.render()
        assert "# TYPE serving_kv_tier_capacity_bytes gauge" in text
        cap_line = next(
            ln for ln in text.splitlines()
            if ln.startswith("serving_kv_tier_capacity_bytes")
        )
        # the exposition's %g keeps 6 significant digits
        assert float(cap_line.split()[1]) == pytest.approx(
            float(32 << 20), rel=1e-5
        )
        st = eng.kv_tier_stats()
        assert (
            f"serving_kv_tier_demotions_total {int(st['demotions'])}"
            in text
        )


# ---------------------------------------------------------------------------
# slow soak: seeded diurnal trace through a tiered+tiered scheduler


@pytest.mark.slow
class TestTierSoak:
    def test_trace_soak_no_starvation_monotone_metrics(self, model):
        """The PR 14 leftover: a seeded workload.py trace (multi-turn
        sessions, all three SLO classes) replayed through ONE slot
        backed by a deliberately tight paged pool + 1-row radix cache
        with the host tier on — constant churn, preemptions, and
        swap traffic. Locks: zero starvation (every turn of every
        session completes; nothing shed) and every per-tier counter
        family sampled during the run is monotone non-decreasing."""
        from dlrover_tpu.serving.workload import (
            SessionBook,
            WorkloadConfig,
            generate_trace,
        )

        cfg, params = model
        max_new_hi = 6
        wcfg = WorkloadConfig(
            seed=42,
            horizon_s=40.0,
            base_rate=0.3,
            period_s=40.0,
            turns_lo=1,
            turns_hi=3,
            think_time_s=1.0,
            user_tokens_lo=4,
            user_tokens_hi=14,
            max_new_lo=2,
            max_new_hi=max_new_hi,
            long_context_prob=0.0,
            system_prompt_tokens=8,
            vocab=250,
            max_prompt_tokens=64 - max_new_hi - 1,
            latency_frac=0.4,
            batch_frac=0.3,
            latency_deadline_s=600.0,
            standard_deadline_s=600.0,
            batch_deadline_s=600.0,
        )
        trace = generate_trace(wcfg)
        assert len(trace.events) >= 10
        assert {ev.tier for ev in trace.events} == {
            "latency", "standard", "batch",
        }
        metrics = ServingMetrics()
        eng = _mk(
            cfg, params, n_slots=1, max_len=64,
            max_new_tokens=max_new_hi, chunk=2, pad_id=-1,
            kv_layout="paged", page_size=8, n_pages=24,
            prefix_cache_rows=1, kv_tier_bytes=64 << 20,
        )
        sched = RequestScheduler(
            eng,
            SloConfig(
                max_queue_depth=len(trace.events) + 4,
                max_new_tokens=max_new_hi,
                default_deadline_s=600.0,
            ),
            metrics=metrics,
        )
        book = SessionBook(trace)
        todo = list(trace.events)
        live = {}
        done = 0
        tier_counters = ("demotions", "promotions", "swap_outs",
                         "swap_ins", "evictions", "rejects")
        prev_tier = {k: 0.0 for k in tier_counters}
        prev_class = {t: 0 for t in ("latency", "standard", "batch")}
        for _ in range(100_000):
            if not todo and not live:
                break
            for ev in list(todo):
                if book.ready(ev):
                    r = sched.submit(
                        book.prompt_for(ev).tolist(),
                        max_new=ev.max_new,
                        deadline_s=ev.deadline_s,
                        tier=ev.tier,
                    )
                    live[id(r)] = (ev, r)
                    todo.remove(ev)
            sched.pump()
            # monotonicity, sampled mid-flight every pump
            st = eng.kv_tier_stats()
            for k in tier_counters:
                assert st[k] >= prev_tier[k], (k, st)
                prev_tier[k] = st[k]
            comp = metrics.tier_admitted_total
            for t, n in prev_class.items():
                assert comp[t] >= n, comp
                prev_class[t] = comp[t]
            for key, (ev, r) in list(live.items()):
                if r.state.value in ("done", "shed", "failed"):
                    assert r.state is RequestState.DONE, (
                        ev, r.state
                    )  # zero starvation: nothing sheds or fails
                    book.record_reply(ev, list(r.tokens))
                    done += 1
                    del live[key]
        else:
            raise AssertionError("soak did not drain")
        assert done == len(trace.events)
        assert metrics.shed_total == 0
        st = eng.kv_tier_stats()
        # the tight pool + 1-row radix actually exercised the tier
        assert st["demotions"] > 0, st
        assert st["promotions"] > 0, st
        eng.allocator.check()
        eng.reset()
        assert eng.allocator.used_pages == 0
