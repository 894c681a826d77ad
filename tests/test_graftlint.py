"""graftlint self-tests (dlrover_tpu/analysis).

Two halves, mirroring tests/test_layering.py's vacuity-guard
discipline:

1. the CLEAN-TREE contract: the whole registry runs over the repo and
   must report zero unsuppressed findings (this is how the registry
   runs in tier-1 by default), and every suppression on the tree
   carries a reason.
2. per-rule OFFENDER probes: each rule must flag a synthetic
   known-bad snippet — a rule that cannot detect its own violation
   pattern is passing vacuously.

Plus pragma semantics (same-line, comment-line-above, reasonless →
GRAFT-000) and the CLI end-to-end (--json exit status contract the
bench preflights rely on).
"""

import json
import pathlib
import subprocess
import sys
import textwrap

import pytest

from dlrover_tpu import analysis
from dlrover_tpu.analysis import (
    CRITICAL,
    SourceFile,
    run_rules,
    unsuppressed,
)
from dlrover_tpu.analysis.rules import (
    REGISTRY,
    AdapterBankRule,
    BroadExceptRule,
    ClockDisciplineRule,
    DeviceAllocRule,
    EagerJnpImportRule,
    ElasticReshardRule,
    FleetRoutingRule,
    HandoffAdoptionRule,
    HbmTransferRule,
    HostCopyRule,
    IntegrityChecksumRule,
    JitSelfCaptureRule,
    KernelHygieneRule,
    LockDisciplineRule,
    PrefillFrontierRule,
    ProgramCacheKeyRule,
    RawMeshRule,
    RlImportRule,
    TierPreemptionRule,
    WeightQuantSiteRule,
    frontier_write_sites,
    get_rules,
    hbm_transfer_sites,
    integrity_checksum_sites,
    weight_quant_sites,
)

pytestmark = pytest.mark.lint

SERVING_REL = "dlrover_tpu/serving/probe.py"
ENGINE_REL = "dlrover_tpu/serving/engine.py"


def probe(tmp_path, code, rel=SERVING_REL, name="probe.py"):
    """A synthetic SourceFile impersonating `rel` so per-file rule
    config applies to it."""
    path = tmp_path / name
    path.write_text(textwrap.dedent(code))
    return SourceFile.parse(path, rel=rel)


def hits(rule, src):
    return [
        f
        for f in unsuppressed(run_rules([rule], files=[src]))
        if f.rule_id == rule.id
    ]


# ---------------------------------------------------------------------------
# the clean-tree contract (the registry's tier-1 entry point)


def test_registry_clean_on_tree():
    findings = analysis.run()
    active = unsuppressed(findings)
    assert not active, "graftlint findings on the tree:\n" + "\n".join(
        f.render() for f in active
    )


def test_tree_suppressions_all_carry_reasons():
    suppressed = [f for f in analysis.run() if f.suppressed]
    # the tree is expected to carry a few deliberate pragmas …
    assert suppressed, "expected at least one pragma'd site"
    # … and every one of them must explain itself
    for f in suppressed:
        assert f.suppression_reason, f.render()


def test_no_outstanding_critical_findings():
    assert analysis.critical_findings() == []


def test_bench_preflight_gate(monkeypatch, capsys):
    # clean tree: no-op — and the refusal path must actually fire,
    # exit code 2 with the finding rendered, when criticals exist
    analysis.bench_preflight("probe-bench")
    bad = analysis.Finding(
        rule_id="CLOCK-001",
        severity=CRITICAL,
        path="dlrover_tpu/serving/replica.py",
        line=1,
        message="synthetic",
    )
    monkeypatch.setattr(analysis, "critical_findings", lambda: [bad])
    with pytest.raises(SystemExit) as exc:
        analysis.bench_preflight("probe-bench")
    assert exc.value.code == 2
    out = capsys.readouterr().out
    assert "refusing to run" in out and "CLOCK-001" in out


# ---------------------------------------------------------------------------
# per-rule synthetic offenders


def test_layer_rule_flags_rl_imports(tmp_path):
    src = probe(
        tmp_path,
        """
        import dlrover_tpu.rl
        from dlrover_tpu.rl import serve
        from dlrover_tpu import rl
        """,
    )
    assert len(hits(RlImportRule(), src)) == 3


def test_layer_rule_ignores_relative_imports(tmp_path):
    src = probe(tmp_path, "from . import engine\n")
    assert not hits(RlImportRule(), src)


def test_host_copy_rule_flags_stray_fetch(tmp_path):
    src = probe(
        tmp_path,
        """
        import numpy as np
        def step(self):
            return np.array(self.tok)
        def _to_host(*arrays):
            return tuple(np.array(a) for a in arrays)
        """,
        rel=ENGINE_REL,
    )
    found = hits(HostCopyRule(), src)
    assert len(found) == 1 and "step" in found[0].message


def test_host_copy_rule_flags_the_fetch_an_admission_made(tmp_path):
    # the engine's `_admit` as it stood before PR 36: the request's key
    # split on the device and fetched behind the admission's own
    # prefill. `_admit` had the allowlist's wholesale leave, so the
    # lint let it by; it is off the list now
    src = probe(
        tmp_path,
        """
        import jax
        import numpy as np
        class ContinuousBatcher:
            def _admit(self, slot, req):
                self.key, sub = jax.random.split(self.key)
                req.prng_key = np.asarray(sub, np.uint32)
        """,
        rel=ENGINE_REL,
    )
    found = hits(HostCopyRule(), src)
    assert len(found) == 1 and "_admit" in found[0].message


def test_host_copy_rule_generalizes_beyond_engine(tmp_path):
    # decode.py and paged_kv.py have EMPTY allowlists: any host
    # materialization at all is a finding there
    for rel in (
        "dlrover_tpu/models/decode.py",
        "dlrover_tpu/serving/paged_kv.py",
    ):
        src = probe(
            tmp_path,
            """
            import jax
            def anything(x):
                return jax.device_get(x)
            """,
            rel=rel,
        )
        assert len(hits(HostCopyRule(), src)) == 1, rel


def test_alloc_rule_flags_hot_path_allocation(tmp_path):
    src = probe(
        tmp_path,
        """
        import jax.numpy as jnp
        class ContinuousBatcher:
            def __init__(self):
                self.bank = jnp.zeros((4, 4))
            def reset(self):
                self.bank = jnp.zeros((4, 4))
            def step(self):
                return jnp.zeros((4,)), init_page_pool()
        """,
        rel=ENGINE_REL,
    )
    found = hits(DeviceAllocRule(), src)
    # jnp.zeros AND the bulk constructor in step(); __init__/reset ok
    assert len(found) == 2
    assert all("step" in f.message for f in found)


def test_mesh_rule_flags_raw_mesh(tmp_path):
    src = probe(
        tmp_path,
        """
        from jax.sharding import Mesh
        import jax
        m = jax.sharding.Mesh(devs, ("tp",))
        """,
    )
    assert len(hits(RawMeshRule(), src)) == 2


def test_lock_rule_requires_guarded_fields_declaration(tmp_path):
    src = probe(
        tmp_path,
        """
        import threading
        class Sched:
            def __init__(self):
                self._lock = threading.Lock()
        """,
    )
    found = hits(LockDisciplineRule(), src)
    assert len(found) == 1 and "GUARDED_FIELDS" in found[0].message


_LOCKED_CLASS = """
import threading
class Sched:
    GUARDED_FIELDS = frozenset({"_q"})
    def __init__(self):
        self._lock = threading.Lock()
        self._q = []
    def {method}(self):
        {body}
"""


def _lock_probe(tmp_path, method, body):
    return probe(
        tmp_path,
        _LOCKED_CLASS.replace("{method}", method).replace(
            "{body}", body
        ),
    )


def test_lock_rule_flags_unguarded_access(tmp_path):
    src = _lock_probe(tmp_path, "drain", "return len(self._q)")
    found = hits(LockDisciplineRule(), src)
    assert len(found) == 1 and "self._q" in found[0].message


def test_lock_rule_accepts_with_lock(tmp_path):
    src = _lock_probe(
        tmp_path,
        "drain",
        "with self._lock:\n            return len(self._q)",
    )
    assert not hits(LockDisciplineRule(), src)


def test_lock_rule_accepts_locked_convention(tmp_path):
    src = _lock_probe(tmp_path, "drain_locked", "return len(self._q)")
    assert not hits(LockDisciplineRule(), src)


def test_lock_rule_accepts_cond_guard(tmp_path):
    src = probe(
        tmp_path,
        """
        import threading
        class Sched:
            GUARDED_FIELDS = frozenset({"_q"})
            def __init__(self):
                self._lock = threading.RLock()
                self._cond = threading.Condition(self._lock)
                self._q = []
            def pump(self):
                with self._cond:
                    self._q.append(1)
        """,
    )
    assert not hits(LockDisciplineRule(), src)


def test_lock_rule_catches_the_pre_pr9_shed_bug(tmp_path):
    # regression probe for the exact latent pattern this PR fixed:
    # scheduler._shed_expired touched the EDF heap with neither a
    # lexical lock nor the _locked naming convention
    src = probe(
        tmp_path,
        """
        import threading
        class RequestScheduler:
            GUARDED_FIELDS = frozenset({"_waiting"})
            def __init__(self):
                self._lock = threading.RLock()
                self._cond = threading.Condition(self._lock)
                self._waiting = []
            def _shed_expired(self, now):
                while self._waiting:
                    self._waiting.pop()
        """,
    )
    assert len(hits(LockDisciplineRule(), src)) == 2


def test_clock_rule_flags_wall_clock(tmp_path):
    src = probe(
        tmp_path,
        """
        import time
        def deadline():
            return time.time() + 5.0
        def ok():
            return time.monotonic() + 5.0
        """,
    )
    found = hits(ClockDisciplineRule(), src)
    assert len(found) == 1
    assert found[0].severity == CRITICAL


def test_jit_rule_flags_self_capture(tmp_path):
    src = probe(
        tmp_path,
        """
        import jax
        from functools import partial
        class Engine:
            @partial(jax.jit, static_argnums=(0,))
            def _step(self, tok):
                return tok + self.offset
        @jax.jit
        def good(tok):
            return tok + 1
        """,
    )
    found = hits(JitSelfCaptureRule(), src)
    assert len(found) == 1 and "self" in found[0].message


def test_jit_rule_flags_jitted_lambda_capture(tmp_path):
    src = probe(
        tmp_path,
        """
        import jax
        class Engine:
            def build(self):
                return jax.jit(lambda t: t + self.offset)
        """,
    )
    assert len(hits(JitSelfCaptureRule(), src)) == 1


def test_eager_jnp_rule_flags_import_time_calls(tmp_path):
    src = probe(
        tmp_path,
        """
        import jax.numpy as jnp
        _TABLE = jnp.arange(16)
        def fine():
            return jnp.arange(16)
        _LAZY = lambda: jnp.arange(16)
        """,
    )
    found = hits(EagerJnpImportRule(), src)
    assert len(found) == 1 and "arange" in found[0].message


def test_cache_key_rule_flags_unhashable_keys(tmp_path):
    src = probe(
        tmp_path,
        """
        def build():
            pass
        a = _cached_program(C, (cfg, pad_id), build)
        b = _cached_program(C, [cfg, pad_id], build)
        c = _cached_program(C, (cfg, [1, 2]), build)
        """,
        rel=ENGINE_REL,
    )
    found = hits(ProgramCacheKeyRule(), src)
    assert len(found) == 2
    assert any("tuple literal" in f.message for f in found)
    assert any("List display" in f.message for f in found)


def test_except_rule_flags_silent_swallows(tmp_path):
    src = probe(
        tmp_path,
        """
        def a():
            try:
                risky()
            except Exception:
                pass
        def b():
            try:
                risky()
            except:
                continue_on()
        def c():
            try:
                risky()
            except Exception:
                logger.exception("boom")
        def d():
            try:
                risky()
            except Exception:
                raise
        def e():
            try:
                risky()
            except ValueError:
                pass
        """,
    )
    found = hits(BroadExceptRule(), src)
    assert len(found) == 2  # a() and b(); c/d dispose, e is typed


OPS_REL = "dlrover_tpu/ops/probe.py"


def test_kernel_rule_flags_ungated_pallas_call(tmp_path):
    src = probe(
        tmp_path,
        """
        from jax.experimental import pallas as pl
        def bad_missing():
            return pl.pallas_call(kernel, out_shape=o)(x)
        def bad_hardcoded():
            return pl.pallas_call(kernel, interpret=True)(x)
        def good():
            return pl.pallas_call(kernel, interpret=_interpret())(x)
        def good_prefixed():
            return pl.pallas_call(kernel, interpret=fa._interpret())(x)
        """,
        rel=OPS_REL,
    )
    found = hits(KernelHygieneRule(), src)
    assert len(found) == 2
    assert all("interpret" in f.message for f in found)


def test_kernel_rule_flags_shard_map_outside_ops_parallel(tmp_path):
    code = """
    from jax.experimental.shard_map import shard_map
    def body(x):
        return shard_map(f, mesh=m, in_specs=s, out_specs=s)(x)
    """
    # serving/ (and any other layer): both the import and the call
    src = probe(tmp_path, code, rel=ENGINE_REL)
    assert len(hits(KernelHygieneRule(), src)) == 2
    src = probe(tmp_path, code, rel="dlrover_tpu/models/decode.py")
    assert len(hits(KernelHygieneRule(), src)) == 2


def test_kernel_rule_allows_shard_map_in_ops_and_parallel(tmp_path):
    code = """
    from jax import shard_map
    def wrap(x):
        return shard_map(f, mesh=m, in_specs=s, out_specs=s)(x)
    """
    for rel in (OPS_REL, "dlrover_tpu/parallel/mesh.py"):
        src = probe(tmp_path, code, rel=rel)
        assert not hits(KernelHygieneRule(), src), rel


def test_kernel_rule_ignores_pallas_outside_ops(tmp_path):
    # the interpret gate is an ops/ contract; a (hypothetical)
    # pallas_call elsewhere is someone else's review problem, and the
    # rule must not misfire on unrelated serving code
    src = probe(
        tmp_path,
        "def f():\n    return pl.pallas_call(kernel)(x)\n",
        rel=ENGINE_REL,
    )
    assert not hits(KernelHygieneRule(), src)


def test_handoff_rule_flags_adhoc_adoption(tmp_path):
    src = probe(
        tmp_path,
        """
        def sneak_pages(self, n):
            pages = self.engine.allocator.adopt(n)
            self.engine.allocator._refs[pages[0]] = 2
            run = self.engine.allocator._free[:n]
            return pages + run
        """,
    )
    found = hits(HandoffAdoptionRule(), src)
    assert len(found) == 3
    assert any("adopt" in f.message for f in found)


def test_handoff_rule_ignores_self_private_fields(tmp_path):
    # the allocator's own methods touch _refs/_free through self —
    # that IS the install path, not a bypass
    src = probe(
        tmp_path,
        """
        def alloc(self, n):
            out, self._free = self._free[:n], self._free[n:]
            for p in out:
                self._refs[p] = 1
            return out
        """,
    )
    assert not hits(HandoffAdoptionRule(), src)


def test_handoff_rule_vacuous_on_install_path(tmp_path):
    # same offender code, impersonating the exempt files: the rule
    # must not apply there (they ARE the entry point), and the
    # vacuity guard proves the offender fires elsewhere
    code = """
    def install(self, engine, n):
        return engine.allocator.adopt(n)
    """
    for rel in (
        "dlrover_tpu/serving/paged_kv.py",
        "dlrover_tpu/serving/handoff.py",
    ):
        src = probe(tmp_path, code, rel=rel)
        assert not hits(HandoffAdoptionRule(), src), rel
    src = probe(tmp_path, code, rel=SERVING_REL)
    assert len(hits(HandoffAdoptionRule(), src)) == 1


# ---------------------------------------------------------------------------
# ELASTIC-001: resharding only through designated entry points


def test_elastic_rule_flags_adhoc_reshard(tmp_path):
    # an engine method outside the designated owners moving arrays
    # onto a new sharding inline — the footgun a live resize must
    # route through serving/elastic.py instead
    src = probe(
        tmp_path,
        """
        import jax

        class Engine:
            def step(self):
                self.params = jax.device_put(self.params, self.sh)
                self.mesh = serving_mesh(2, n_kv_heads=2)
        """,
        rel=ENGINE_REL,
    )
    found = hits(ElasticReshardRule(), src)
    assert len(found) == 2
    assert all("elastic" in f.message for f in found)


def test_elastic_rule_allows_designated_owners(tmp_path):
    src = probe(
        tmp_path,
        """
        import jax

        class Engine:
            def __init__(self, tp):
                self.mesh = serving_mesh(tp, n_kv_heads=2)

            def _shard_params(self, params):
                return jax.device_put(params, self.sh)

            def _replicate(self, x):
                return jax.device_put(x, self.rep)
        """,
        rel=ENGINE_REL,
    )
    assert not hits(ElasticReshardRule(), src)


def test_elastic_rule_vacuous_on_elastic_module(tmp_path):
    # the same offender inside serving/elastic.py is the DESIGNED
    # reshard path: exempt there, flagged anywhere else (vacuity
    # guard on the exemption)
    code = """
    import jax

    def resize(engine, tp):
        engine.mesh = serving_mesh(tp, n_kv_heads=2)
        engine.params = jax.device_put(engine.params, engine.sh)
    """
    src = probe(
        tmp_path, code, rel="dlrover_tpu/serving/elastic.py"
    )
    assert not hits(ElasticReshardRule(), src)
    src = probe(tmp_path, code, rel=SERVING_REL)
    assert len(hits(ElasticReshardRule(), src)) == 2


def test_elastic_rule_unlisted_serving_file_allows_nothing(tmp_path):
    # a serving file with no allowlist entry gets no owners at all:
    # every reshard primitive there is a finding
    src = probe(
        tmp_path,
        """
        def rebalance(pool):
            return shard_tree(pool.params, pool.mesh)
        """,
        rel="dlrover_tpu/serving/replica.py",
    )
    assert len(hits(ElasticReshardRule(), src)) == 1


def test_elastic_rule_ignores_outside_serving(tmp_path):
    # parallel/mesh.py and the ops layer build meshes by design —
    # the rule is a serving-layer invariant only
    src = probe(
        tmp_path,
        """
        import jax

        def make(tp):
            return jax.device_put(1.0, None), serving_mesh(tp)
        """,
        rel="dlrover_tpu/parallel/mesh.py",
    )
    assert not hits(ElasticReshardRule(), src)


# ---------------------------------------------------------------------------
# ADAPTER-001: adapter-bank allocation/eviction only in adapters.py


def test_adapter_rule_flags_adhoc_bank_mutation(tmp_path):
    # an engine method minting a fresh bank, scattering a slot
    # directly, and poking the cache's LRU/pin internals — each a
    # way to re-point a decoding slot at the wrong tenant's weights
    src = probe(
        tmp_path,
        """
        class Engine:
            def _admit(self, req):
                bank = init_adapter_bank(self.cfg, 8, 8, None)
                bank = _bank_slot_write(bank, req.update, 3)
                self._adapter_cache._resident.clear()
                self._adapter_cache._pins[req.adapter_id] = 0
                return bank
        """,
        rel=ENGINE_REL,
    )
    found = hits(AdapterBankRule(), src)
    assert len(found) == 4
    assert all("adapters.py" in f.message for f in found)


def test_adapter_rule_allows_cache_api(tmp_path):
    # the sanctioned surface: acquire/release/rebuild and reading
    # .bank — none of it is a finding
    src = probe(
        tmp_path,
        """
        class Engine:
            def submit(self, adapter_id):
                slot = self._adapter_cache.acquire(adapter_id)
                return self._adapter_cache.bank, slot

            def retire(self, req):
                self._adapter_cache.release(req.adapter_id)
        """,
        rel=ENGINE_REL,
    )
    assert not hits(AdapterBankRule(), src)


def test_adapter_rule_ignores_self_private_fields(tmp_path):
    # the cache's own methods touch _resident/_pins through self —
    # that IS the eviction path, not a bypass
    src = probe(
        tmp_path,
        """
        def _take_slot(self):
            for victim, slot in self._resident.items():
                if self._pins.get(victim, 0) == 0:
                    del self._resident[victim]
                    return slot
            raise RuntimeError
        """,
        rel="dlrover_tpu/serving/adapters.py",
    )
    assert not hits(AdapterBankRule(), src)


def test_adapter_rule_vacuous_on_adapters_module(tmp_path):
    # same offender code impersonating adapters.py: exempt there
    # (it IS the bank owner), flagged anywhere else in serving
    code = """
    def rebuild(cache, cfg):
        cache.bank = init_adapter_bank(cfg, 8, 8, None)
        return cache._upload(0, cache._take_slot())
    """
    src = probe(
        tmp_path, code, rel="dlrover_tpu/serving/adapters.py"
    )
    assert not hits(AdapterBankRule(), src)
    src = probe(tmp_path, code, rel=SERVING_REL)
    assert len(hits(AdapterBankRule(), src)) == 3


def test_adapter_rule_ignores_outside_serving(tmp_path):
    # models/tests build banks by design — serving-layer invariant
    src = probe(
        tmp_path,
        """
        def setup(cfg):
            return init_adapter_bank(cfg, 8, 8, None)
        """,
        rel="dlrover_tpu/models/lora.py",
    )
    assert not hits(AdapterBankRule(), src)


# ---------------------------------------------------------------------------
# ROUTE-001: fleet routing decisions only in replica.py + affinity.py


def test_route_rule_flags_adhoc_routing(tmp_path):
    # a gateway picking its own replica from the digest map — the
    # forked-policy footgun: two components routing the same prompt
    # differently halves the fleet hit rate, and the private-index
    # poke mints a route drop() can never retract
    src = probe(
        tmp_path,
        """
        def pick(pool, prompt):
            chain = prefix_digest_chain(prompt, 16)
            depths = pool.digest_map.match_depths(chain)
            order = affinity_order(pool.replicas(), depths, len, 0.5)
            pool.digest_map._by_digest["d"] = {"r1"}
            return order[0]
        """,
        rel="dlrover_tpu/serving/gateway.py",
    )
    found = hits(FleetRoutingRule(), src)
    assert len(found) == 4
    assert all("replica.py" in f.message for f in found)


def test_route_rule_allows_observation_surface(tmp_path):
    # the sanctioned read-only surface: routing_stats()/stats() and
    # submitting through the pool — none of it is a finding
    src = probe(
        tmp_path,
        """
        def health(pool):
            return pool.routing_stats(), pool.digest_map.stats()

        def serve(pool, prompt):
            return pool.submit(prompt)
        """,
        rel="dlrover_tpu/serving/gateway.py",
    )
    assert not hits(FleetRoutingRule(), src)


def test_route_rule_ignores_self_private_fields(tmp_path):
    # FleetDigestMap's own methods touch _by_digest/_by_replica
    # through self — that IS the map, not a bypass (mirrors the
    # real exemption: affinity.py is an exempt file anyway, so probe
    # the self-access case on an unlisted serving file)
    src = probe(
        tmp_path,
        """
        class Map:
            def update(self, rid, ds):
                self._by_replica[rid] = frozenset(ds)
                self._by_digest.setdefault("d", set()).add(rid)
        """,
        rel="dlrover_tpu/serving/gateway.py",
    )
    assert not hits(FleetRoutingRule(), src)


def test_route_rule_vacuous_on_owning_modules(tmp_path):
    # the same offender impersonating the two designated owners is
    # exempt there, flagged anywhere else in serving (vacuity guard
    # on the exemption)
    code = """
    def route(pool, prompt):
        chain = prefix_digest_chain(prompt, 16)
        return pool.digest_map.match_depths(chain)
    """
    for owner in (
        "dlrover_tpu/serving/replica.py",
        "dlrover_tpu/serving/affinity.py",
    ):
        src = probe(tmp_path, code, rel=owner)
        assert not hits(FleetRoutingRule(), src)
    src = probe(tmp_path, code, rel=SERVING_REL)
    assert len(hits(FleetRoutingRule(), src)) == 2


def test_route_rule_ignores_outside_serving(tmp_path):
    # tests/benches drive the affinity API directly by design —
    # the rule is a serving-layer invariant only
    src = probe(
        tmp_path,
        """
        def bench(pool, prompt):
            chain = prefix_digest_chain(prompt, 16)
            return affinity_order(pool.replicas(), {}, len, 0.5)
        """,
        rel="dlrover_tpu/master/kv_store.py",
    )
    assert not hits(FleetRoutingRule(), src)


# ---------------------------------------------------------------------------
# TIER-001: admission preemption only in scheduler.py + paged_kv.py


def test_tier_rule_flags_adhoc_preemption(tmp_path):
    # an engine (or pool) evicting a running request for admission on
    # its own — bypasses the scheduler's snapshot-before-cancel
    # ordering, so the victim's resume loses byte parity; both the
    # bare and attribute call spellings must be caught
    src = probe(
        tmp_path,
        """
        def make_room(self, sched):
            sched._preempt_for_admission_locked()
            preempt_for_admission(self.victim)
        """,
        rel="dlrover_tpu/serving/engine.py",
    )
    found = hits(TierPreemptionRule(), src)
    assert len(found) == 2
    assert all("scheduler.py" in f.message for f in found)


def test_tier_rule_allows_memory_pressure_swap(tmp_path):
    # the engine's own page-pressure preempt-and-swap is the separate
    # legal survival path (PR 6) — not an admission decision, never a
    # finding; neither is observing tier counters
    src = probe(
        tmp_path,
        """
        def step(self):
            slot = self._pick_preempt_slot()
            self._preempt_slot(slot)
            return self.metrics.tier_preempted_total
        """,
        rel="dlrover_tpu/serving/engine.py",
    )
    assert not hits(TierPreemptionRule(), src)


def test_tier_rule_vacuous_on_owning_modules(tmp_path):
    # the same offender impersonating the designated owners is exempt
    # there, flagged anywhere else in serving (vacuity guard on the
    # exemption)
    code = """
    def pump(self):
        if self.blocked():
            self._preempt_for_admission_locked()
    """
    for owner in (
        "dlrover_tpu/serving/scheduler.py",
        "dlrover_tpu/serving/paged_kv.py",
    ):
        src = probe(tmp_path, code, rel=owner)
        assert not hits(TierPreemptionRule(), src)
    src = probe(tmp_path, code, rel=SERVING_REL)
    assert len(hits(TierPreemptionRule(), src)) == 1


def test_tier_rule_ignores_outside_serving(tmp_path):
    # tests drive the preemption API directly by design — the rule is
    # a serving-layer invariant only
    src = probe(
        tmp_path,
        """
        def force_preempt(sched):
            sched._preempt_for_admission_locked()
        """,
        rel="tests/test_serving_tiers.py",
    )
    assert not hits(TierPreemptionRule(), src)


# ---------------------------------------------------------------------------
# PREFILL-001: partial write frontier mutates only in engine
# admission/step and decode.py prefill programs


def test_prefill_rule_flags_outside_writers(tmp_path):
    # every write spelling: host-mirror subscript store, device-dict
    # key store, and the d.update(frontier=...) keyword — a scheduler
    # (or any non-engine serving module) touching any of them is a
    # CRITICAL finding
    src = probe(
        tmp_path,
        """
        def rebalance(self, slot):
            self.engine._frontier[slot] = 0
            self.engine._dev["frontier"] = zeros
            self.engine._dev.update(frontier=zeros)
        """,
        rel="dlrover_tpu/serving/scheduler.py",
    )
    rule = PrefillFrontierRule()
    found = hits(rule, src)
    assert len(found) == 3
    assert rule.severity == CRITICAL  # rides the bench preflight gate
    assert all("request_progress" in f.message for f in found)


def test_prefill_rule_allows_engine_writers(tmp_path):
    # the engine allowlist: admission installs, the interleaved
    # dispatcher advances, the release path clears
    src = probe(
        tmp_path,
        """
        def _admit(self, slot, req):
            self._frontier[slot] = start

        def _dispatch_interleaved(self):
            d.update(frontier=frontier)

        def _clear_prefill(self, slot):
            self._frontier[slot] = 0

        def _run_pf(cache, params, frontier, pslot):
            frontier = frontier.at[pslot].set(pstart)

        def _run_pf_paged(pool, table, params, frontier, pslot):
            frontier = frontier.at[pslot].set(pstart)
        """,
        rel="dlrover_tpu/serving/engine.py",
    )
    assert not hits(PrefillFrontierRule(), src)


def test_prefill_rule_vacuity_of_engine_allowlist(tmp_path):
    # the allowlisted owner names are exempt ONLY inside engine.py —
    # the same function impersonating another serving module is
    # flagged, so the exemption can never silently widen
    code = """
    def _dispatch_interleaved(self):
        self._frontier[slot] = start
    """
    src = probe(
        tmp_path, code, rel="dlrover_tpu/serving/engine.py"
    )
    assert not hits(PrefillFrontierRule(), src)
    src = probe(tmp_path, code, rel=SERVING_REL)
    assert len(hits(PrefillFrontierRule(), src)) == 1
    # an engine function OFF the allowlist is flagged too
    src = probe(
        tmp_path,
        """
        def _harvest(self):
            self._frontier[slot] = fetched
        """,
        rel="dlrover_tpu/serving/engine.py",
    )
    assert len(hits(PrefillFrontierRule(), src)) == 1
    # adapters are an operand of the two fused programs, not a second
    # pair of them: a `_lora` twin coming back is off the allowlist
    src = probe(
        tmp_path,
        """
        def _run_pf_lora(cache, params, frontier, pslot, abank, aidx):
            frontier = frontier.at[pslot].set(pstart)
        """,
        rel="dlrover_tpu/serving/engine.py",
    )
    assert len(hits(PrefillFrontierRule(), src)) == 1


def test_prefill_rule_ignores_reads_and_decode(tmp_path):
    # reads (progress ranking, stats) and call names are never
    # writes; decode.py's chunk-resume primitives are legal writers
    # wholesale
    src = probe(
        tmp_path,
        """
        def _slot_progress(self, slot):
            self._cow_frontier(slot, p)
            return int(self._frontier[slot]) - plen
        """,
        rel="dlrover_tpu/serving/scheduler.py",
    )
    assert not hits(PrefillFrontierRule(), src)
    src = probe(
        tmp_path,
        """
        def prefill_chunk_into_slot(cfg, params, chunk, cache, slot):
            frontier = frontier.at[slot].set(start)
        """,
        rel="dlrover_tpu/models/decode.py",
    )
    assert not hits(PrefillFrontierRule(), src)


def test_prefill_rule_not_vacuous_on_real_engine():
    # the walker must see the real engine's frontier writes (the
    # rule has something to protect) and the allowlist must cover
    # every one of them (the tree stays clean)
    root = pathlib.Path(analysis.__file__).resolve().parents[2]
    src = SourceFile.parse(
        root / "dlrover_tpu" / "serving" / "engine.py",
        rel="dlrover_tpu/serving/engine.py",
    )
    sites = frontier_write_sites(src.tree)
    assert len(sites) >= 4, "real engine frontier writes not seen"
    owners = {owner for _, _, owner in sites}
    assert "_admit" in owners and "_dispatch_interleaved" in owners
    assert not hits(PrefillFrontierRule(), src)


# ---------------------------------------------------------------------------
# HBM-001: HBM<->host transfer primitives only in designated movers


def test_hbm_rule_flags_stray_transfers(tmp_path):
    # a serving file with no allowlist entry starting its own D2H
    # copies and device_put-ing KV back — the unaccounted PCIe
    # traffic the tier's byte budget exists to prevent
    src = probe(
        tmp_path,
        """
        import jax

        def leak(arr, host, sh):
            arr.copy_to_host_async()
            start = getattr(arr, "copy_to_host_async", None)
            return jax.device_put(host, sh)
        """,
        rel=SERVING_REL,
    )
    found = hits(HbmTransferRule(), src)
    assert len(found) == 3
    assert all("kv_tier" in f.message for f in found)


def test_hbm_rule_allows_designated_movers(tmp_path):
    # engine: the async D2H starter + placement helpers
    src = probe(
        tmp_path,
        """
        import jax

        class Engine:
            def _start_host_copy(self, arrays):
                for a in arrays:
                    start = getattr(a, "copy_to_host_async", None)
                    if start is not None:
                        start()

            def _shard_bank(self, bank):
                return {
                    k: jax.device_put(v, self.sh)
                    for k, v in bank.items()
                }

            def _replicate(self, x):
                return jax.device_put(x, self.rep)
        """,
        rel=ENGINE_REL,
    )
    assert not hits(HbmTransferRule(), src)
    # handoff: adoption places shipped KV onto the target sharding
    src = probe(
        tmp_path,
        """
        import jax

        def adopt_into_slot(engine, pkg):
            return jax.device_put(pkg.data, engine.sh)
        """,
        rel="dlrover_tpu/serving/handoff.py",
    )
    assert not hits(HbmTransferRule(), src)


def test_hbm_rule_vacuity_of_kv_tier_allowlist(tmp_path):
    # the tier's snapshot/upload helpers are legal; the SAME
    # primitives in an unlisted kv_tier.py function are findings —
    # the module is not exempt wholesale
    code = """
    import jax

    def snapshot_row(pool, row, w):
        piece = pool["k"][row]
        start = getattr(piece, "copy_to_host_async", None)
        if start is not None:
            start()
        return piece

    def upload_row(pool, ent, row):
        return jax.device_put(ent.data, pool["k"].sharding)

    def sneaky(arr, host, sh):
        arr.copy_to_host_async()
        return jax.device_put(host, sh)
    """
    src = probe(
        tmp_path, code, rel="dlrover_tpu/serving/kv_tier.py"
    )
    found = hits(HbmTransferRule(), src)
    assert len(found) == 2
    assert all("sneaky" in f.message for f in found)


def test_hbm_rule_ignores_outside_serving(tmp_path):
    # models/ and parallel/ move arrays by design — the rule is a
    # serving-layer invariant only
    src = probe(
        tmp_path,
        """
        import jax

        def place(x, sh):
            x.copy_to_host_async()
            return jax.device_put(x, sh)
        """,
        rel="dlrover_tpu/parallel/sharding.py",
    )
    assert not hits(HbmTransferRule(), src)


def test_hbm_rule_not_vacuous_on_real_tree():
    # the walker must see the real transfer sites (the rule has
    # something to protect) and the allowlists must cover every one
    # of them (the tree stays clean)
    root = pathlib.Path(analysis.__file__).resolve().parents[2]
    serving = root / "dlrover_tpu" / "serving"
    owners = {}
    for name in ("engine.py", "handoff.py", "kv_tier.py"):
        src = SourceFile.parse(
            serving / name, rel=f"dlrover_tpu/serving/{name}"
        )
        sites = hbm_transfer_sites(src.tree)
        owners[name] = {o for _, _, o in sites}
        assert sites, f"no transfer sites seen in {name}"
        assert not hits(HbmTransferRule(), src)
    assert "_start_host_copy" in owners["engine.py"]
    assert "adopt_into_slot" in owners["handoff.py"]
    assert {
        "snapshot_row", "snapshot_pages", "upload_row", "upload_pages"
    } <= owners["kv_tier.py"]


# ---------------------------------------------------------------------------
# pragma semantics


def test_pragma_suppresses_with_reason(tmp_path):
    src = probe(
        tmp_path,
        """
        import time
        def beat():
            return time.time()  # graftlint: allow(CLOCK-001) reason=wall-clock telemetry
        """,
    )
    findings = run_rules([ClockDisciplineRule()], files=[src])
    assert len(findings) == 1
    assert findings[0].suppressed
    assert findings[0].suppression_reason == "wall-clock telemetry"
    assert not unsuppressed(findings)


def test_pragma_on_comment_line_covers_next_line(tmp_path):
    src = probe(
        tmp_path,
        """
        import time
        def beat():
            # graftlint: allow(CLOCK-001) reason=telemetry ts
            return time.time()
        """,
    )
    assert not unsuppressed(
        run_rules([ClockDisciplineRule()], files=[src])
    )


def test_pragma_without_reason_is_critical(tmp_path):
    src = probe(
        tmp_path,
        """
        import time
        def beat():
            return time.time()  # graftlint: allow(CLOCK-001)
        """,
    )
    findings = run_rules([ClockDisciplineRule()], files=[src])
    meta = [f for f in findings if f.rule_id == "GRAFT-000"]
    assert len(meta) == 1
    assert meta[0].severity == CRITICAL
    assert not meta[0].suppressed


def test_pragma_for_other_rule_does_not_suppress(tmp_path):
    src = probe(
        tmp_path,
        """
        import time
        def beat():
            return time.time()  # graftlint: allow(EXC-001) reason=mismatched id
        """,
    )
    findings = run_rules([ClockDisciplineRule()], files=[src])
    assert [f.rule_id for f in unsuppressed(findings)] == [
        "CLOCK-001"
    ]


# ---------------------------------------------------------------------------
# INTEG-001: KV integrity checksum discipline


def test_integ_rule_flags_stray_checksum_in_serving(tmp_path):
    # every spelling of the primitives counts: the health helpers,
    # bare blake2b, and hashlib.blake2b
    code = """
    import hashlib
    from dlrover_tpu.serving.health import kv_checksum, verify_checksum
    from hashlib import blake2b

    def sneaky_stamp(data):
        return kv_checksum(data)

    def sneaky_verify(data, d):
        return verify_checksum(data, d)

    def raw_digest(data):
        h = hashlib.blake2b(digest_size=16)
        return blake2b(h.hexdigest().encode())
    """
    src = probe(tmp_path, code)
    found = hits(IntegrityChecksumRule(), src)
    assert len(found) == 4
    assert all(f.severity == "CRITICAL" for f in found)


def test_integ_rule_vacuity_of_allowlists(tmp_path):
    # the designated sites are legal; the SAME calls in an unlisted
    # function of the SAME files are findings — neither kv_tier.py
    # nor handoff.py is exempt wholesale
    tier_code = """
    from dlrover_tpu.serving.health import kv_checksum, verify_checksum

    def _finalize(self, ent):
        ent.checksum = kv_checksum(ent.data)

    def _verify_locked(self, ent):
        return verify_checksum(ent.data, ent.checksum)

    def sneaky(self, ent):
        return kv_checksum(ent.data)
    """
    src = probe(
        tmp_path, tier_code, rel="dlrover_tpu/serving/kv_tier.py"
    )
    found = hits(IntegrityChecksumRule(), src)
    assert len(found) == 1
    assert "sneaky" in found[0].message

    handoff_code = """
    from dlrover_tpu.serving.health import kv_checksum, verify_checksum

    def export_run(engine, idx, transport="device"):
        return kv_checksum({})

    def adopt_into_slot(engine, slot, pkg):
        return verify_checksum(pkg.data, pkg.checksum)

    def on_prefill_done(self, scheduler, ticket, pkg):
        return verify_checksum(pkg.data, pkg.checksum)

    def resneak(pkg):
        return verify_checksum(pkg.data, pkg.checksum)
    """
    src = probe(
        tmp_path, handoff_code, rel="dlrover_tpu/serving/handoff.py",
        name="handoff_probe.py",
    )
    found = hits(IntegrityChecksumRule(), src)
    assert len(found) == 1
    assert "resneak" in found[0].message


def test_integ_rule_health_module_exempt_wholesale(tmp_path):
    src = probe(
        tmp_path,
        """
        import hashlib

        def kv_checksum(data):
            return hashlib.blake2b(b"x").hexdigest()
        """,
        rel="dlrover_tpu/serving/health.py",
    )
    assert not hits(IntegrityChecksumRule(), src)


def test_integ_rule_ignores_outside_serving(tmp_path):
    # affinity-style digests outside serving/ (e.g. master/) are not
    # this rule's business
    src = probe(
        tmp_path,
        """
        import hashlib

        def content_key(b):
            return hashlib.blake2b(b).hexdigest()
        """,
        rel="dlrover_tpu/master/kv_store.py",
    )
    assert not hits(IntegrityChecksumRule(), src)


def test_integ_rule_not_vacuous_on_real_tree():
    # the walker must see the real stamp/verify sites (the rule has
    # something to protect) and the allowlists must cover every one
    # of them (the tree stays clean)
    root = pathlib.Path(analysis.__file__).resolve().parents[2]
    serving = root / "dlrover_tpu" / "serving"
    owners = {}
    for name in ("kv_tier.py", "handoff.py", "affinity.py"):
        src = SourceFile.parse(
            serving / name, rel=f"dlrover_tpu/serving/{name}"
        )
        sites = integrity_checksum_sites(src.tree)
        owners[name] = {o for _, _, o in sites}
        assert sites, f"no checksum sites seen in {name}"
        assert not hits(IntegrityChecksumRule(), src)
    assert {"_finalize", "_verify_locked"} <= owners["kv_tier.py"]
    assert {
        "export_run", "adopt_into_slot", "on_prefill_done"
    } <= owners["handoff.py"]
    assert "_block_digest" in owners["affinity.py"]


# ---------------------------------------------------------------------------
# QUANT-001: weight-quantization call-site discipline


def test_quant_rule_flags_stray_quantize_in_serving(tmp_path):
    # every spelling of every primitive counts: bare imported names,
    # module attributes, and the stochastic variant
    code = """
    from dlrover_tpu.ops import quantization
    from dlrover_tpu.ops.quantization import (
        dequantize_int8,
        quantize_int8,
        stochastic_round_int8,
    )

    def per_step_requant(w):
        return quantize_int8(w, 64)

    def rematerialize(q, s):
        return dequantize_int8(q, s, q.shape, 0)

    def noisy(w, key):
        return quantization.stochastic_round_int8(w, key, 64)
    """
    src = probe(tmp_path, code)
    found = hits(WeightQuantSiteRule(), src)
    assert len(found) == 3
    assert all(f.severity == "CRITICAL" for f in found)
    assert any("per_step_requant" in f.message for f in found)


def test_quant_rule_vacuity_of_allowlist(tmp_path):
    # _quantize_params in engine.py is the ONE designated site; the
    # SAME calls in any other engine function are findings — the
    # file is not exempt wholesale
    code = """
    from dlrover_tpu.ops.quantization import (
        quantize_int8,
        stochastic_round_int8,
    )

    def _quantize_params(self, params):
        return quantize_int8(params, 64)

    def _decode_step_fn(self, w, key):
        return stochastic_round_int8(w, key, 64)
    """
    src = probe(tmp_path, code, rel=ENGINE_REL)
    found = hits(WeightQuantSiteRule(), src)
    assert len(found) == 1
    assert "_decode_step_fn" in found[0].message


def test_quant_rule_decode_file_allows_nothing(tmp_path):
    # models/decode.py is in scope but allows nothing: the forward
    # paths consume QuantizedWeight via matmul_any's fused dequant
    src = probe(
        tmp_path,
        """
        from dlrover_tpu.ops.quantization import dequantize_int8

        def _forward_cached(q, s):
            return dequantize_int8(q, s, q.shape, 0)
        """,
        rel="dlrover_tpu/models/decode.py",
        name="decode_probe.py",
    )
    found = hits(WeightQuantSiteRule(), src)
    assert len(found) == 1
    assert "_forward_cached" in found[0].message


def test_quant_rule_ignores_outside_scope(tmp_path):
    # ops/quantization.py (the primitives' home) and the KV-cache
    # quant path in training-side code are not this rule's business
    src = probe(
        tmp_path,
        """
        def quantize_any(x, block=128):
            return quantize_int8(x, block)
        """,
        rel="dlrover_tpu/ops/quantization.py",
        name="ops_probe.py",
    )
    assert not hits(WeightQuantSiteRule(), src)


def test_quant_rule_not_vacuous_on_real_tree():
    # the walker must see the real install sites in engine.py (the
    # rule has something to protect), _quantize_params must own every
    # one of them, and the real files must stay clean
    root = pathlib.Path(analysis.__file__).resolve().parents[2]
    eng = SourceFile.parse(
        root / "dlrover_tpu" / "serving" / "engine.py",
        rel="dlrover_tpu/serving/engine.py",
    )
    sites = weight_quant_sites(eng.tree)
    assert sites, "no quantization sites seen in engine.py"
    assert {o for _, _, o in sites} == {"_quantize_params"}
    assert not hits(WeightQuantSiteRule(), eng)
    dec = SourceFile.parse(
        root / "dlrover_tpu" / "models" / "decode.py",
        rel="dlrover_tpu/models/decode.py",
    )
    assert not weight_quant_sites(dec.tree)
    assert not hits(WeightQuantSiteRule(), dec)


# ---------------------------------------------------------------------------
# registry / CLI


def test_registry_ids_unique_and_selectable():
    ids = [r.id for r in REGISTRY]
    assert len(ids) == len(set(ids))
    assert [r.id for r in get_rules(["CLOCK-001"])] == ["CLOCK-001"]
    with pytest.raises(KeyError):
        get_rules(["NOPE-999"])
    for rule in REGISTRY:
        assert rule.rationale and rule.title


def _cli(*argv):
    return subprocess.run(
        [sys.executable, "-m", "dlrover_tpu.analysis", *argv],
        capture_output=True,
        text=True,
        timeout=120,
    )


def test_cli_json_exits_zero_on_clean_tree():
    res = _cli("--json")
    assert res.returncode == 0, res.stdout + res.stderr
    payload = json.loads(res.stdout)
    assert payload["ok"] is True
    assert payload["findings"] == []
    assert payload["suppressed"], "expected the tree's pragma'd sites"
    assert all(f["suppression_reason"] for f in payload["suppressed"])


def test_cli_flags_offender_file(tmp_path):
    bad = tmp_path / "dlrover_tpu" / "serving" / "bad.py"
    bad.parent.mkdir(parents=True)
    bad.write_text("import time\nTS = time.time()\n")
    res = _cli("--rules", "CLOCK-001", str(bad))
    assert res.returncode == 1
    assert "CLOCK-001" in res.stdout


def test_cli_rejects_unknown_rule():
    assert _cli("--rules", "NOPE-999").returncode == 2


def test_cli_list_names_every_rule():
    res = _cli("--list")
    assert res.returncode == 0
    for rule in REGISTRY:
        assert rule.id in res.stdout
