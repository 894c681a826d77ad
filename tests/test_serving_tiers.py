"""Priority tiers + admission preemption (serving/scheduler.py):
strict-priority dispatch across the per-tier EDF heaps, tier
admission budgets, the aging escalator's starvation-freedom
guarantee, scheduler-level preemption of batch work for latency
arrivals with byte-exact resume-by-replay (fuzzed across KV layouts,
sampling, and async dispatch against a no-preemption oracle),
per-tier metrics exposition, and the gateway's tier field."""

import dataclasses
import json

import http.client

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _serve_oracle import lockstep_oracle
from dlrover_tpu.models import llama
from dlrover_tpu.serving.engine import ContinuousBatcher
from dlrover_tpu.serving.gateway import ServingGateway
from dlrover_tpu.serving.metrics import ServingMetrics
from dlrover_tpu.serving.replica import InferenceReplica, ReplicaPool
from dlrover_tpu.serving.scheduler import (
    TIERS,
    AdmissionError,
    RequestScheduler,
    RequestState,
    SloConfig,
)

pytestmark = pytest.mark.tiers


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
    kw.setdefault("chunk", 4)
    kw.setdefault("pad_id", -1)
    return ContinuousBatcher(cfg, params, **kw)


class TestStrictPriority:
    def test_tiers_constant_shape(self):
        assert TIERS == ("latency", "standard", "batch")

    def test_priority_beats_edf_across_tiers(self, model):
        """One slot, three requests submitted batch-first with the
        BATCH deadline tightest: EDF alone would run batch first,
        strict priority must run latency, then standard, then batch.
        Within a tier EDF still rules (pinned by the scheduler
        suite); across tiers class wins."""
        cfg, params = model
        now = [0.0]
        sched = RequestScheduler(
            _engine(cfg, params, n_slots=1),
            SloConfig(tier_aging_s=0.0),
            clock=lambda: now[0],
        )
        ps = _prompts((5, 6, 7), seed=1)
        batch = sched.submit(
            ps[0], max_new=2, deadline_s=1000.0, tier="batch"
        )
        standard = sched.submit(
            ps[1], max_new=2, deadline_s=2000.0, tier="standard"
        )
        latency = sched.submit(
            ps[2], max_new=2, deadline_s=3000.0, tier="latency"
        )
        while sched.pump():
            now[0] += 1.0
        assert latency.finish_ts < standard.finish_ts < batch.finish_ts
        for r in (latency, standard, batch):
            assert r.state is RequestState.DONE

    def test_unknown_tier_rejected(self, model):
        cfg, params = model
        sched = RequestScheduler(_engine(cfg, params), SloConfig())
        with pytest.raises(AdmissionError, match="unknown tier"):
            sched.submit(_prompts((4,), seed=2)[0], tier="gold")
        assert sched.metrics.rejected_total == 1

    def test_tier_budget_rejects(self, model):
        """tier_budgets caps live requests per CLASS: the second
        batch submit 429s while standard traffic is untouched — the
        spare-capacity filler can never crowd out the queue."""
        cfg, params = model
        sched = RequestScheduler(
            _engine(cfg, params),
            SloConfig(tier_budgets={"batch": 1}),
        )
        p = _prompts((4,), seed=3)[0]
        sched.submit(p, tier="batch")
        with pytest.raises(AdmissionError, match="admission budget"):
            sched.submit(p, tier="batch")
        sched.submit(p, tier="standard")  # other classes unaffected
        assert sched.metrics.rejected_total == 1

    def test_tier_queue_depths(self, model):
        cfg, params = model
        sched = RequestScheduler(_engine(cfg, params), SloConfig())
        p = _prompts((4,), seed=4)[0]
        sched.submit(p, tier="latency")
        sched.submit(p, tier="latency")
        sched.submit(p, tier="batch")
        assert sched.tier_queue_depths() == {
            "latency": 2, "standard": 0, "batch": 1,
        }


class TestAgingEscalator:
    def _starved_run(self, model, aging_s):
        """One slot under sustained latency pressure (the queue never
        runs dry at admission time) with one batch request waiting.
        Returns the batch request + scheduler after ~24 virtual
        seconds."""
        cfg, params = model
        now = [0.0]
        sched = RequestScheduler(
            _engine(cfg, params, n_slots=1),
            SloConfig(tier_aging_s=aging_s),
            clock=lambda: now[0],
        )
        batch = sched.submit(
            _prompts((5,), seed=5)[0],
            max_new=2,
            deadline_s=300.0,
            tier="batch",
        )
        lat = _prompts((4, 6), seed=6)
        for _ in range(12):
            for p in lat:
                sched.submit(
                    p, max_new=2, deadline_s=500.0, tier="latency"
                )
            sched.pump()
            sched.pump()
            now[0] += 2.0
            if batch.state is RequestState.DONE:
                break
        return batch, sched

    def test_aging_prevents_starvation(self, model):
        """With the escalator on, the batch request is promoted into
        the latency heap after 2 aging periods, where its fixed
        deadline beats every later arrival under EDF — it completes
        DESPITE the latency queue never draining."""
        batch, sched = self._starved_run(model, aging_s=4.0)
        assert batch.state is RequestState.DONE
        assert batch.effective_tier == "latency"
        assert sched.metrics.tier_escalated_total["batch"] >= 1

    def test_no_aging_starves(self, model):
        """The control arm: escalator off, same pressure — the batch
        request is still waiting at the end. Strict priority without
        aging DOES starve; the escalator is what makes it safe."""
        batch, sched = self._starved_run(model, aging_s=0.0)
        assert batch.state is RequestState.QUEUED
        assert sched.metrics.tier_escalated_total["batch"] == 0


class TestPreemption:
    def test_latency_preempts_running_batch(self, model):
        """The Podracer move: batch work occupies the only slot; a
        latency arrival evicts it (snapshot -> cancel -> requeue),
        decodes first, and the victim resumes BYTE-IDENTICAL to an
        undisturbed run via replay-prefill."""
        cfg, params = model
        metrics = ServingMetrics()
        sched = RequestScheduler(
            _engine(cfg, params, n_slots=1, chunk=2),
            SloConfig(),
            metrics=metrics,
        )
        p_batch, p_lat = _prompts((6, 9), seed=7)
        batch = sched.submit(
            p_batch, max_new=8, deadline_s=600.0, tier="batch"
        )
        sched.pump()  # batch admitted, first chunk decoding
        assert batch.state is RequestState.RUNNING
        latency = sched.submit(
            p_lat, max_new=4, deadline_s=600.0, tier="latency"
        )
        sched.pump()  # blocked latency arrival evicts the batch slot
        assert batch.preemptions == 1
        assert batch.state in (
            RequestState.QUEUED, RequestState.RUNNING
        )
        assert metrics.tier_preempted_total["batch"] == 1
        sched.run_to_completion()
        assert latency.state is RequestState.DONE
        assert batch.state is RequestState.DONE
        assert latency.finish_ts <= batch.finish_ts
        assert latency.tokens == lockstep_oracle(cfg, params, p_lat, 4)
        assert batch.tokens == lockstep_oracle(cfg, params, p_batch, 8)

    def test_standard_does_not_preempt(self, model):
        """Only a latency-tier waiter may evict: a standard arrival
        waits for the batch slot like anyone else."""
        cfg, params = model
        sched = RequestScheduler(
            _engine(cfg, params, n_slots=1, chunk=2), SloConfig()
        )
        ps = _prompts((5, 7), seed=8)
        batch = sched.submit(
            ps[0], max_new=8, deadline_s=600.0, tier="batch"
        )
        sched.pump()
        standard = sched.submit(
            ps[1], max_new=2, deadline_s=600.0, tier="standard"
        )
        sched.pump()
        assert batch.preemptions == 0
        assert standard.state is RequestState.QUEUED
        assert sched.metrics.tier_preempted_total["batch"] == 0
        sched.run_to_completion()
        assert batch.finish_ts <= standard.finish_ts

    def test_no_batch_victim_means_no_preemption(self, model):
        """A latency arrival blocked behind RUNNING standard work has
        no legal victim — preemption never touches non-batch tiers."""
        cfg, params = model
        sched = RequestScheduler(
            _engine(cfg, params, n_slots=1, chunk=2), SloConfig()
        )
        ps = _prompts((5, 7), seed=9)
        standard = sched.submit(
            ps[0], max_new=8, deadline_s=600.0, tier="standard"
        )
        sched.pump()
        latency = sched.submit(
            ps[1], max_new=2, deadline_s=600.0, tier="latency"
        )
        sched.pump()
        assert standard.preemptions == 0
        assert standard.state is RequestState.RUNNING
        assert latency.state is RequestState.QUEUED
        sched.run_to_completion()
        assert standard.state is RequestState.DONE
        assert latency.state is RequestState.DONE


class TestPreemptResumeParity:
    """The fuzzed sweep the ISSUE pins: preempt-resume must be
    byte-exact against a NO-PREEMPTION oracle under every KV layout
    (dense/paged), decode discipline (greedy/sampled), and dispatch
    depth (sync/async). Sampled runs pin per-request PRNG keys at
    submit so the oracle engine draws the identical streams."""

    def _oracle(self, cfg, params, prompts, keys, engine_kw):
        """Undisturbed reference: every prompt decodes to completion
        on one engine with the same pinned keys. Always SYNCHRONOUS —
        the sync path is the parity oracle (failover-suite idiom)."""
        ref_kw = dict(engine_kw, async_depth=0, n_slots=len(prompts))
        eng = _engine(cfg, params, **ref_kw)
        ids = [
            eng.submit(p, max_new=8, prng_key=k)
            for p, k in zip(prompts, keys)
        ]
        streamed = {i: [] for i in ids}
        while eng.has_work():
            for idx, toks, _done in eng.step():
                streamed[idx].extend(toks)
        return [streamed[i] for i in ids]

    @pytest.mark.parametrize("fuzz_seed", [0, 1])
    @pytest.mark.parametrize(
        "engine_kw",
        [
            {"async_depth": 0},
            {"async_depth": 0, "kv_layout": "paged"},
            {"async_depth": 0, "temperature": 0.9, "top_k": 20, "seed": 5},
            {
                "async_depth": 0,
                "kv_layout": "paged",
                "temperature": 0.9,
                "top_k": 20,
                "seed": 5,
            },
            {"async_depth": 1},
            {
                "async_depth": 1,
                "temperature": 0.9,
                "top_k": 20,
                "seed": 5,
            },
            {"async_depth": 1, "kv_layout": "paged"},
            {
                "async_depth": 1,
                "kv_layout": "paged",
                "temperature": 0.9,
                "top_k": 20,
                "seed": 5,
            },
        ],
        ids=[
            "dense-greedy", "paged-greedy",
            "dense-sampled", "paged-sampled",
            "async-dense-greedy", "async-dense-sampled",
            "async-paged-greedy", "async-paged-sampled",
        ],
    )
    def test_preempt_resume_parity_sweep(
        self, model, fuzz_seed, engine_kw
    ):
        cfg, params = model
        rng = np.random.default_rng(fuzz_seed)
        prompts = _prompts((6, 9, 4, 7), seed=20 + fuzz_seed)
        keys = [
            np.asarray(jax.random.PRNGKey(100 + i), np.uint32)
            for i in range(len(prompts))
        ]
        want = self._oracle(cfg, params, prompts, keys, engine_kw)

        metrics = ServingMetrics()
        sched = RequestScheduler(
            _engine(cfg, params, chunk=2, **engine_kw),
            SloConfig(),
            metrics=metrics,
        )
        # two batch requests fill both slots, decode a fuzzed number
        # of chunks, then a latency + a standard arrival land: the
        # latency one is blocked and must preempt a running victim
        tiers = ("batch", "batch", "latency", "standard")
        reqs = []
        for i in (0, 1):
            reqs.append(
                sched.submit(
                    prompts[i],
                    max_new=8,
                    deadline_s=600.0,
                    tier=tiers[i],
                    prng_key=keys[i],
                )
            )
        for _ in range(int(rng.integers(1, 3))):
            sched.pump()
        for i in (2, 3):
            reqs.append(
                sched.submit(
                    prompts[i],
                    max_new=8,
                    deadline_s=600.0,
                    tier=tiers[i],
                    prng_key=keys[i],
                )
            )
        sched.run_to_completion()
        assert metrics.tier_preempted_total["batch"] >= 1
        assert sum(r.preemptions for r in reqs[:2]) >= 1
        for r, w, p in zip(reqs, want, prompts):
            assert r.state is RequestState.DONE
            assert r.tokens == w, (
                f"preempt-resume diverged for prompt {p}"
            )


class TestTierMetrics:
    def test_exposition_needles(self):
        m = ServingMetrics()
        m.tier_admitted("latency")
        m.tier_preempted("batch")
        m.tier_escalated("batch")
        m.request_shed("standard")
        m.observe_ttft(12.0, tier="latency")
        m.observe_tpot(3.0, tier="latency")
        text = m.render()
        for needle in (
            "# TYPE serving_tier_admitted_total counter",
            'serving_tier_admitted_total{tier="latency"} 1',
            'serving_tier_admitted_total{tier="batch"} 0',
            'serving_tier_preempted_total{tier="batch"} 1',
            'serving_tier_escalated_total{tier="batch"} 1',
            'serving_tier_shed_total{tier="standard"} 1',
            "# TYPE serving_tier_ttft_ms summary",
            'serving_tier_ttft_ms{tier="latency",quantile="0.5"}',
            'serving_tier_ttft_ms_count{tier="latency"} 1',
            'serving_tier_tpot_ms_count{tier="latency"} 1',
        ):
            assert needle in text, needle

    def test_unknown_tier_counts_globally_only(self):
        """A shed with an unattributable tier must not KeyError and
        must not invent a label — the global counter still moves."""
        m = ServingMetrics()
        m.request_shed("bogus")
        m.tier_admitted("bogus")
        assert m.shed_total == 1
        assert sum(m.tier_shed_total.values()) == 0
        assert sum(m.tier_admitted_total.values()) == 0

    def test_shed_attributed_per_tier(self, model):
        """Expired waiters shed under the tier THAT MISSED: one batch
        + one latency request both expire; each tier's counter moves
        by exactly one."""
        cfg, params = model
        now = [0.0]
        metrics = ServingMetrics()
        sched = RequestScheduler(
            _engine(cfg, params),
            SloConfig(),
            metrics=metrics,
            clock=lambda: now[0],
        )
        ps = _prompts((4, 5), seed=10)
        b = sched.submit(ps[0], deadline_s=5.0, tier="batch")
        l = sched.submit(ps[1], deadline_s=5.0, tier="latency")
        now[0] = 6.0
        sched.run_to_completion()
        assert b.state is RequestState.SHED
        assert l.state is RequestState.SHED
        assert metrics.tier_shed_total == {
            "latency": 1, "standard": 0, "batch": 1,
        }
        assert metrics.shed_total == 2


class TestGatewayTier:
    def _post(self, port, payload):
        conn = http.client.HTTPConnection(
            "127.0.0.1", port, timeout=60
        )
        try:
            conn.request("POST", "/v1/generate", json.dumps(payload))
            resp = conn.getresponse()
            return resp.status, json.loads(resp.read())
        finally:
            conn.close()

    def _get(self, port, path):
        conn = http.client.HTTPConnection(
            "127.0.0.1", port, timeout=60
        )
        try:
            conn.request("GET", path)
            resp = conn.getresponse()
            return resp.status, json.loads(resp.read())
        finally:
            conn.close()

    def test_tier_field_validated_and_plumbed(self, model):
        """Unknown or non-string tiers 400 at the front door (never a
        500 from the scheduler); a valid tier flows through to the
        scheduler and shows up in /healthz per-tier counters."""
        cfg, params = model
        metrics = ServingMetrics()
        pool = ReplicaPool()
        eng = _engine(cfg, params, n_slots=4)
        sched = RequestScheduler(eng, SloConfig(), metrics=metrics)
        rep = InferenceReplica("replica-0", sched)
        rep.start()
        pool.add(rep)
        gw = ServingGateway(pool, metrics=metrics)
        gw.start()
        try:
            p = _prompts((5,), seed=11)[0]
            for payload in (
                {"tokens": p, "tier": "gold"},      # unknown class
                {"tokens": p, "tier": 3},           # wrong type
                {"tokens": p, "tier": True},        # bool is not str
                {"tokens": p, "tier": ["latency"]},
            ):
                status, body = self._post(gw.port, payload)
                assert status == 400, (payload, status, body)
                assert "tier" in body["error"], body
            status, body = self._post(
                gw.port,
                {
                    "tokens": p,
                    "max_new": 3,
                    "stream": False,
                    "tier": "batch",
                },
            )
            assert status == 200, body
            assert body["tokens"] == lockstep_oracle(
                cfg, params, p, 3
            )
            status, health = self._get(gw.port, "/healthz")
            assert status == 200
            assert health["tiers"]["admitted"]["batch"] == 1
            assert health["tiers"]["preempted"]["batch"] == 0
        finally:
            gw.stop()
            pool.stop()
