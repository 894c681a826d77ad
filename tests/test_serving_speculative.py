"""Speculative decoding subsystem (serving/speculative.py + the verify
program in models/decode.py + the engine integration): the parity
oracle — greedy output with spec_draft_len>0 must be token-identical
to the non-speculative engine, including int8 KV and prefix-cache-warm
admissions, and spec_draft_len=0 must leave today's path bit-exact —
plus drafter/controller units, a Monte-Carlo distribution-preservation
test of the rejection-sampling acceptance rule, metrics/healthz
propagation, and slow chaos/fuzz sweeps."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _serve_oracle import lockstep_oracle
from dlrover_tpu.models import llama
from dlrover_tpu.models.decode import (
    spec_accept_greedy,
    spec_accept_sampled,
)
from dlrover_tpu.serving.engine import ContinuousBatcher
from dlrover_tpu.serving.metrics import ServingMetrics
from dlrover_tpu.serving.scheduler import RequestScheduler, SloConfig
from dlrover_tpu.serving.speculative import (
    NgramDrafter,
    SpecController,
    SpeculativeDecoder,
)


@pytest.fixture(scope="module")
def model():
    cfg = dataclasses.replace(
        llama.LlamaConfig.tiny(), dtype=jnp.float32
    )
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _engine(cfg, params, **kw):
    kw.setdefault("n_slots", 2)
    kw.setdefault("max_len", 64)
    kw.setdefault("max_new_tokens", 8)
    kw.setdefault("chunk", 4)
    kw.setdefault("pad_id", -1)
    return ContinuousBatcher(cfg, params, **kw)


def _mixed_prompts(seed=0, n=6):
    """Random prompts plus pattern-repeat prompts, so the drafter sees
    both regimes (misses on noise, hits on repetition)."""
    rng = np.random.default_rng(seed)
    out = [
        rng.integers(1, 250, size=int(n)).tolist()
        for n in rng.integers(3, 20, size=n)
    ]
    pat = rng.integers(1, 250, size=4).tolist()
    return out + [pat * 5, (pat * 3)[:-1]]


def _drain(eng, prompts):
    return [list(map(int, o)) for o in eng.generate_all(prompts)]


# ---------------------------------------------------------------------------
# drafter units
# ---------------------------------------------------------------------------


class TestNgramDrafter:
    def test_no_recurrence_proposes_nothing(self):
        d = NgramDrafter(1)
        d.begin(0, [1, 2, 3, 4, 5])
        assert d.propose(0, 4).size == 0

    def test_finds_continuation_of_repeated_gram(self):
        # ...7 8 9 10 11... then suffix 7 8 9 -> proposes 10 11
        d = NgramDrafter(1)
        d.begin(0, [7, 8, 9, 10, 11, 42, 7, 8, 9])
        assert d.propose(0, 2).tolist() == [10, 11]

    def test_most_recent_occurrence_wins(self):
        # 1 2 -> 3 early, 1 2 -> 9 later; suffix 1 2 follows the later
        d = NgramDrafter(1, ngram_max=2, ngram_min=2)
        d.begin(0, [1, 2, 3, 0, 1, 2, 9, 5, 1, 2])
        assert d.propose(0, 2).tolist() == [9, 5]

    def test_tiles_short_window_cyclically(self):
        # period-2 tail: the match window is [5, 6]; k=5 tiles it
        d = NgramDrafter(1)
        d.begin(0, [9, 5, 6, 5, 6, 5, 6])
        assert d.propose(0, 5).tolist() == [5, 6, 5, 6, 5]

    def test_extend_is_incremental(self):
        """Feeding tokens one at a time equals one-shot indexing."""
        rng = np.random.default_rng(3)
        seq = rng.integers(0, 6, size=80).tolist()
        one = NgramDrafter(1)
        one.begin(0, seq)
        inc = NgramDrafter(1)
        inc.begin(0, seq[:10])
        for t in seq[10:]:
            inc.extend(0, [t])
        for k in (1, 3, 6):
            assert one.propose(0, k).tolist() == inc.propose(0, k).tolist()

    def test_begin_resets_slot(self):
        d = NgramDrafter(2)
        d.begin(0, [1, 2, 3, 1, 2])
        assert d.propose(0, 1).size > 0
        d.begin(0, [4, 5, 6])
        assert d.propose(0, 1).size == 0

    def test_slots_are_independent(self):
        d = NgramDrafter(2)
        d.begin(0, [1, 2, 3, 1, 2])
        d.begin(1, [9, 9, 9, 9])
        assert d.propose(0, 1).tolist() == [3]
        assert d.propose(1, 2).tolist() == [9, 9]

    def test_bad_ngram_range_rejected(self):
        with pytest.raises(ValueError):
            NgramDrafter(1, ngram_max=2, ngram_min=3)


# ---------------------------------------------------------------------------
# controller units
# ---------------------------------------------------------------------------


class TestSpecController:
    def test_high_acceptance_grows_to_k_max(self):
        c = SpecController(1, k_max=4)
        c._slots[0].k = 1
        for _ in range(5):
            c.observe(0, proposed=2, accepted=2)
        assert c.current_k(0) == 4

    def test_low_acceptance_disables(self):
        c = SpecController(1, k_max=4)
        for _ in range(10):
            c.observe(0, proposed=4, accepted=0)
        assert c.current_k(0) == 0

    def test_disabled_slot_probes_then_revives(self):
        c = SpecController(1, k_max=4, probe_interval=3)
        for _ in range(10):
            c.observe(0, proposed=4, accepted=0)
        assert c.current_k(0) == 0
        # two rounds of silence, then the probe fires
        assert c.k_for(0) == 0
        assert c.k_for(0) == 0
        assert c.k_for(0) == 1
        # a winning probe revives with a fresh EMA
        c.observe(0, proposed=1, accepted=1)
        assert c.current_k(0) == 1
        c.observe(0, proposed=1, accepted=1)
        assert c.current_k(0) == 2

    def test_failed_probe_stays_disabled(self):
        c = SpecController(1, k_max=4, probe_interval=2)
        for _ in range(10):
            c.observe(0, proposed=4, accepted=0)
        assert c.k_for(0) == 0
        assert c.k_for(0) == 1
        c.observe(0, proposed=1, accepted=0)
        assert c.current_k(0) == 0

    def test_reset_restores_k_max(self):
        c = SpecController(1, k_max=4)
        for _ in range(10):
            c.observe(0, proposed=4, accepted=0)
        c.reset(0)
        assert c.current_k(0) == 4

    def test_validation(self):
        with pytest.raises(ValueError):
            SpecController(1, k_max=0)
        with pytest.raises(ValueError):
            SpecController(1, k_max=2, threshold=0.0)
        with pytest.raises(ValueError):
            SpecController(1, k_max=2, probe_interval=0)


# ---------------------------------------------------------------------------
# acceptance rules (models/decode.py)
# ---------------------------------------------------------------------------


class TestAcceptGreedy:
    def test_prefix_match_and_bonus(self):
        # targets per position: argmax = [3, 1, 4, 2]
        v = 6
        logits = np.zeros((1, 4, v), np.float32)
        for i, t in enumerate([3, 1, 4, 2]):
            logits[0, i, t] = 9.0
        drafts = np.array([[3, 1, 9]], np.int32)  # diverges at j=2
        m, extra = spec_accept_greedy(
            jnp.asarray(logits), jnp.asarray(drafts),
            jnp.asarray([3], jnp.int32),
        )
        assert int(m[0]) == 2
        assert int(extra[0]) == 4  # target token at the divergence

    def test_all_accepted_emits_bonus(self):
        v = 6
        logits = np.zeros((1, 3, v), np.float32)
        for i, t in enumerate([2, 5, 1]):
            logits[0, i, t] = 9.0
        m, extra = spec_accept_greedy(
            jnp.asarray(logits),
            jnp.asarray([[2, 5]], np.int32),
            jnp.asarray([2], jnp.int32),
        )
        assert int(m[0]) == 2
        assert int(extra[0]) == 1

    def test_draft_len_masks_padding(self):
        """Rows draft fewer than K tokens; padding must not count as
        accepted even when it happens to match the target."""
        v = 4
        logits = np.zeros((1, 3, v), np.float32)
        for i in range(3):
            logits[0, i, 0] = 9.0  # target argmax 0 everywhere
        m, extra = spec_accept_greedy(
            jnp.asarray(logits),
            jnp.asarray([[0, 0]], np.int32),  # pad tokens equal target
            jnp.asarray([1], jnp.int32),      # but only 1 is a draft
        )
        assert int(m[0]) == 1
        assert int(extra[0]) == 0


class TestDistributionPreservation:
    """The provable core of speculative sampling: whatever the drafter
    proposes, the emitted marginal equals the target distribution."""

    def test_first_token_marginal_matches_target(self):
        b, v = 20000, 8
        rng = np.random.default_rng(0)
        p = rng.dirichlet(np.ones(v))  # one target distribution
        probs = np.broadcast_to(
            p.astype(np.float32), (b, 2, v)
        ).copy()
        # drafts from a very DIFFERENT proposal distribution
        q = rng.dirichlet(np.ones(v) * 0.3)
        drafts = rng.choice(v, size=(b, 1), p=q).astype(np.int32)
        m, extra = spec_accept_sampled(
            jax.random.PRNGKey(7),
            jnp.asarray(probs),
            jnp.asarray(drafts),
            jnp.ones(b, jnp.int32),
        )
        m, extra = np.asarray(m), np.asarray(extra)
        first = np.where(m >= 1, drafts[:, 0], extra)
        emp = np.bincount(first, minlength=v) / b
        assert np.abs(emp - p).max() < 0.02, (emp, p)

    def test_point_mass_draft_never_accepted_when_p_zero(self):
        b, v = 64, 4
        probs = np.zeros((b, 2, v), np.float32)
        probs[:, :, 1] = 1.0  # target is a point mass on token 1
        drafts = np.full((b, 1), 3, np.int32)  # p(3) = 0
        m, extra = spec_accept_sampled(
            jax.random.PRNGKey(0),
            jnp.asarray(probs),
            jnp.asarray(drafts),
            jnp.ones(b, jnp.int32),
        )
        assert int(np.asarray(m).max()) == 0
        assert (np.asarray(extra) == 1).all()

    def test_matching_point_mass_always_accepted(self):
        b, v = 64, 4
        probs = np.zeros((b, 3, v), np.float32)
        probs[:, :, 2] = 1.0
        drafts = np.full((b, 2), 2, np.int32)
        m, extra = spec_accept_sampled(
            jax.random.PRNGKey(1),
            jnp.asarray(probs),
            jnp.asarray(drafts),
            jnp.full(b, 2, jnp.int32),
        )
        assert (np.asarray(m) == 2).all()
        assert (np.asarray(extra) == 2).all()  # bonus from p itself


# ---------------------------------------------------------------------------
# the parity oracle: spec on == spec off, token for token (greedy)
# ---------------------------------------------------------------------------


class TestParityOracle:
    def test_greedy_matches_lockstep(self, model):
        cfg, params = model
        prompts = _mixed_prompts(seed=0)
        eng = _engine(cfg, params, spec_draft_len=4)
        out = _drain(eng, prompts)
        assert eng.spec.proposed > 0, "drafter never fired; vacuous"
        for p, o in zip(prompts, out):
            assert o == lockstep_oracle(cfg, params, p, 8)

    def test_greedy_with_eos_matches_lockstep(self, model):
        """EOS inside an accepted draft run must truncate identically
        to the one-token-at-a-time path."""
        cfg, params = model
        prompts = _mixed_prompts(seed=1)
        eng = _engine(cfg, params, spec_draft_len=4, eos_id=7)
        out = _drain(eng, prompts)
        for p, o in zip(prompts, out):
            assert o == lockstep_oracle(cfg, params, p, 8, eos_id=7)

    def test_int8_kv_matches_nonspec(self, model):
        cfg, params = model
        prompts = _mixed_prompts(seed=2)
        spec = _drain(
            _engine(cfg, params, spec_draft_len=4, kv_quant=True),
            prompts,
        )
        plain = _drain(
            _engine(cfg, params, kv_quant=True), prompts
        )
        assert spec == plain

    def test_prefix_cache_warm_matches_lockstep(self, model):
        """Warm admissions (prefill skipped via the radix cache) under
        speculation — both subsystems on at once."""
        cfg, params = model
        rng = np.random.default_rng(4)
        shared = rng.integers(1, 250, size=40).tolist()
        prompts = [shared + [3], shared + [9, 9, 9]]
        eng = _engine(
            cfg, params, spec_draft_len=4, prefix_cache_rows=4
        )
        out = _drain(eng, prompts)
        assert eng.prefix_cache.hits > 0, "no reuse; vacuous"
        for p, o in zip(prompts, out):
            assert o == lockstep_oracle(cfg, params, p, 8)

    def test_oversubscribed_readmission(self, model):
        """More prompts than slots: retiring + re-admitting slots must
        reset drafter context and controller state per request."""
        cfg, params = model
        prompts = _mixed_prompts(seed=5, n=10)
        eng = _engine(cfg, params, n_slots=2, spec_draft_len=4)
        out = _drain(eng, prompts)
        for p, o in zip(prompts, out):
            assert o == lockstep_oracle(cfg, params, p, 8)

    def test_zero_draft_len_is_bit_exact(self, model):
        """spec_draft_len=0 must not even change the cache allocation,
        let alone the tokens."""
        cfg, params = model
        prompts = _mixed_prompts(seed=6)
        off = _engine(cfg, params, spec_draft_len=0)
        assert off.spec is None
        base = _engine(cfg, params)
        assert (
            off.cache["k"].shape == base.cache["k"].shape
        ), "spec_draft_len=0 changed the KV bank shape"
        assert _drain(off, prompts) == _drain(base, prompts)

    def test_sampled_mode_runs_and_terminates(self, model):
        """Sampled speculation is distribution-preserving (proved at
        the rule level above), not stream-identical — here we pin that
        the engine path runs, respects budgets, and emits no pads."""
        cfg, params = model
        prompts = _mixed_prompts(seed=7)
        eng = _engine(
            cfg, params, spec_draft_len=4,
            temperature=0.9, top_k=40, top_p=0.95, seed=3,
        )
        out = _drain(eng, prompts)
        for o in out:
            assert 0 < len(o) <= 8
            assert all(0 <= t < cfg.vocab_size for t in o)

    def test_spec_draft_len_validation(self, model):
        cfg, params = model
        with pytest.raises(ValueError):
            _engine(cfg, params, spec_draft_len=-1)
        with pytest.raises(ValueError):
            _engine(cfg, params, spec_draft_len=64, max_len=64)


# ---------------------------------------------------------------------------
# adaptive behavior + metrics plumbing
# ---------------------------------------------------------------------------


class TestAdaptiveAndMetrics:
    def test_controller_disables_on_noise(self, model):
        """Pure-noise prompts: acceptance collapses and the controller
        turns drafting off for those slots (graceful degradation)."""
        cfg, params = model
        rng = np.random.default_rng(8)
        prompts = [rng.integers(1, 250, size=12).tolist() for _ in range(2)]
        eng = _engine(
            cfg, params, max_new_tokens=24, max_len=96,
            spec_draft_len=4, spec_probe_interval=64,
        )
        _drain(eng, prompts)
        st = eng.spec.stats()
        # what the controller promises is one step of back-off per
        # LOSING scored round, and off after k_max of them in a row —
        # not that 24 noise tokens contain k_max rounds: on noise the
        # n-gram drafter seldom proposes at all (the token stream, and
        # so the count of scored rounds, depends on the XLA build's
        # numerics; jax 0.9.0 gives slot 1 a single one). So: every
        # slot that was scored has backed off, and a run of k_max
        # losing rounds does turn a slot off.
        ctl = eng.spec.controller
        if st["acceptance_rate"] < 0.5:
            scored = [
                i for i in range(eng.n_slots) if ctl._slots[i].seen
            ]
            assert scored
            assert all(
                ctl.current_k(i) < eng.spec.draft_len for i in scored
            )
        for _ in range(eng.spec.draft_len):
            ctl.observe(0, 4, 0)
        assert ctl.current_k(0) == 0

    def test_counters_are_consistent(self, model):
        cfg, params = model
        eng = _engine(cfg, params, spec_draft_len=4)
        _drain(eng, _mixed_prompts(seed=9))
        s = eng.spec
        assert 0 <= s.accepted <= s.proposed
        assert s.emitted >= s.rounds  # every live round emits >= 1
        st = s.stats()
        assert st["tokens_per_step"] >= 1.0
        assert st["acceptance_rate"] == pytest.approx(
            s.accepted / max(1, s.proposed)
        )

    def test_scheduler_pump_copies_spec_stats(self, model):
        cfg, params = model
        eng = _engine(cfg, params, spec_draft_len=4)
        metrics = ServingMetrics()
        sched = RequestScheduler(eng, SloConfig(), metrics=metrics)
        for p in _mixed_prompts(seed=10):
            sched.submit(p, max_new=8)
        sched.run_to_completion()
        assert metrics.spec_proposed == eng.spec.proposed
        assert metrics.spec_accepted == eng.spec.accepted
        text = metrics.render()
        for needle in (
            "# TYPE serving_spec_proposed_total counter",
            f"serving_spec_proposed_total {eng.spec.proposed}",
            f"serving_spec_accepted_total {eng.spec.accepted}",
            "# TYPE serving_spec_acceptance_rate gauge",
            "# TYPE serving_spec_tokens_per_step gauge",
        ):
            assert needle in text, text

    def test_monotonic_guard(self):
        m = ServingMetrics()
        m.update_speculative(10, 5, 4, 9)
        m.update_speculative(3, 1, 1, 2)  # lagging replica
        assert m.spec_proposed == 10
        assert m.spec_accepted == 5

    def test_healthz_carries_spec_stats(self, model):
        from dlrover_tpu.serving.gateway import ServingGateway

        cfg, params = model
        eng = _engine(cfg, params, spec_draft_len=4)
        sched = RequestScheduler(
            eng, SloConfig(), metrics=ServingMetrics()
        )
        for p in _mixed_prompts(seed=11):
            sched.submit(p, max_new=8)
        sched.run_to_completion()
        gw = ServingGateway(sched)
        try:
            health = gw._health()
            assert health["speculative"]["proposed"] == eng.spec.proposed
            assert health["speculative"]["draft_len"] == 4
        finally:
            gw._server.server_close()


# ---------------------------------------------------------------------------
# chaos / fuzz sweeps (slow: excluded from tier-1)
# ---------------------------------------------------------------------------


@pytest.mark.slow
class TestSpecFuzz:
    def test_parity_fuzz_sweep(self, model):
        """Random engine shapes x random prompt sets: greedy parity
        with the lockstep oracle must hold everywhere."""
        cfg, params = model
        rng = np.random.default_rng(123)
        for trial in range(8):
            n_slots = int(rng.integers(1, 4))
            chunk = int(rng.integers(1, 6))
            k = int(rng.integers(1, 6))
            max_new = int(rng.integers(2, 12))
            eos = int(rng.integers(2, 9)) if rng.random() < 0.5 else None
            prompts = [
                rng.integers(1, 250, size=int(n)).tolist()
                for n in rng.integers(1, 30, size=int(rng.integers(1, 9)))
            ]
            pat = rng.integers(1, 250, size=3).tolist()
            prompts.append(pat * 6)
            eng = _engine(
                cfg, params, n_slots=n_slots, chunk=chunk,
                max_new_tokens=max_new, spec_draft_len=k, eos_id=eos,
            )
            out = _drain(eng, prompts)
            for p, o in zip(prompts, out):
                want = lockstep_oracle(cfg, params, p, max_new, eos_id=eos)
                assert o == want, (
                    f"trial {trial}: slots={n_slots} chunk={chunk} "
                    f"k={k} max_new={max_new} eos={eos} prompt={p}"
                )

    def test_near_max_len_boundary_sweep(self, model):
        """Prompts that leave only a handful of cells before max_len:
        the over-allocated verify window must never corrupt live
        cells or emit past the limit."""
        cfg, params = model
        rng = np.random.default_rng(7)
        max_len = 32
        for k in (1, 3, 5):
            prompts = [
                rng.integers(1, 250, size=n).tolist()
                for n in (max_len - 2, max_len - 3, max_len - 6, 5)
            ]
            eng = _engine(
                cfg, params, max_len=max_len, max_new_tokens=16,
                spec_draft_len=k,
            )
            out = _drain(eng, prompts)
            plain = _drain(
                _engine(cfg, params, max_len=max_len,
                        max_new_tokens=16),
                prompts,
            )
            assert out == plain, f"k={k}"

    def test_distribution_preservation_multiposition(self):
        """Monte-Carlo over K=3 with position-varying targets: the
        SECOND position's marginal, conditioned on the first draft
        being accepted, must also equal the target."""
        b, v, k = 40000, 6, 3
        rng = np.random.default_rng(1)
        p = rng.dirichlet(np.ones(v), size=k + 1).astype(np.float32)
        probs = np.broadcast_to(p, (b, k + 1, v)).copy()
        q = rng.dirichlet(np.ones(v) * 0.5, size=k)
        drafts = np.stack(
            [rng.choice(v, size=b, p=q[j]) for j in range(k)], axis=1
        ).astype(np.int32)
        m, extra = spec_accept_sampled(
            jax.random.PRNGKey(5),
            jnp.asarray(probs),
            jnp.asarray(drafts),
            jnp.full(b, k, jnp.int32),
        )
        m, extra = np.asarray(m), np.asarray(extra)
        first = np.where(m >= 1, drafts[:, 0], extra)
        emp = np.bincount(first, minlength=v) / b
        assert np.abs(emp - p[0]).max() < 0.02
        # position 1, conditioned on draft 0 accepted
        sel = m >= 1
        second = np.where(m[sel] >= 2, drafts[sel, 1], extra[sel])
        emp2 = np.bincount(second, minlength=v) / sel.sum()
        assert np.abs(emp2 - p[1]).max() < 0.03


# ---------------------------------------------------------------------------
# async dispatch: the drafter staleness contract
# ---------------------------------------------------------------------------


class TestAsyncStaleness:
    """The staleness contract documented on SpeculativeDecoder: under
    async_depth=1 the engine harvests dispatch N-1 (extend + record)
    BEFORE drafting for dispatch N, so the drafter conditions on the
    full history through the previous dispatch — exactly what the
    sync path sees. Outputs AND acceptance counters must therefore be
    byte-identical across depths; only when events surface shifts."""

    def test_outputs_and_spec_stats_identical_across_depths(
        self, model
    ):
        cfg, params = model
        prompts = _mixed_prompts(seed=3)
        e0 = _engine(cfg, params, spec_draft_len=4, async_depth=0)
        e1 = _engine(cfg, params, spec_draft_len=4, async_depth=1)
        assert _drain(e0, prompts) == _drain(e1, prompts)
        # the controller's adaptive-k trajectory is part of the
        # contract: identical stats prove the drafter never saw a
        # stale context under pipelining
        assert e0.spec.stats() == e1.spec.stats()

    def test_draft_batch_matches_per_slot_draft(self, model):
        """The vectorized padded assembly must be semantically the
        per-slot loop it replaced: same drafts, same lengths, zeros
        (a valid embedding row, never pad_id) beyond each length."""
        spec = SpeculativeDecoder(4, 3, ngram_max=3, ngram_min=1)
        pat = [5, 6, 7]
        spec.begin_slot(0, pat * 4)          # repetitive: will draft
        spec.begin_slot(1, [9, 8, 7, 6, 5])  # noise: drafts nothing
        spec.begin_slot(3, pat * 3)
        done = np.array([False, False, True, False])
        drafts, dlens = spec.draft_batch(done)
        assert drafts.shape == (4, 3) and dlens.shape == (4,)
        # fresh decoder, same state, driven through draft() directly
        ref = SpeculativeDecoder(4, 3, ngram_max=3, ngram_min=1)
        ref.begin_slot(0, pat * 4)
        ref.begin_slot(1, [9, 8, 7, 6, 5])
        ref.begin_slot(3, pat * 3)
        for slot in range(4):
            if done[slot]:
                assert dlens[slot] == 0
                assert not drafts[slot].any()
                continue
            prop = ref.draft(slot)
            assert dlens[slot] == prop.size
            assert drafts[slot, : prop.size].tolist() == prop.tolist()
            assert not drafts[slot, prop.size :].any()
