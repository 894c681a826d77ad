"""Multi-adapter LoRA serving (serving/adapters.py + the batched
per-slot delta path in models/decode.py).

The central contract is BYTE PARITY per request: a batch mixing
adapters ad1/ad2/base through ONE forward must emit, for every
request, exactly the tokens a dedicated engine over merge()d weights
emits for that request alone. The sweep covers dense/paged layouts,
greedy and sampled decoding, sync and async dispatch, and tp=1 vs
tp=2 (the stacked B banks shard along the tp output-column split, so
the delta never adds a collective).

Also covered: registry validation (typo'd targets, mixed ranks,
shape drift), the LRU device cache's pinned-while-referenced
eviction (a decoding request's bank slot can never be recycled under
it), AdapterCacheFull backpressure at engine and scheduler level,
per-tenant admission quotas, base-traffic program-cache-key identity
(adapters off must compile and serve exactly the pre-adapter
programs), and live elastic resize with resident adapters.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.models import llama, lora
from dlrover_tpu.serving.adapters import (
    AdapterCacheFull,
    AdapterRegistry,
    DeviceAdapterCache,
)
from dlrover_tpu.serving.engine import ContinuousBatcher
from dlrover_tpu.serving.scheduler import (
    AdmissionError,
    RequestScheduler,
    SloConfig,
)

pytestmark = pytest.mark.adapters

multi_device = pytest.mark.skipif(
    len(jax.devices()) < 2,
    reason="tp>1 needs >=2 (forced host) devices",
)


@pytest.fixture(scope="module")
def model():
    cfg = dataclasses.replace(
        llama.LlamaConfig.tiny(), dtype=jnp.float32
    )
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _make_adapter(cfg, params, seed, rank=4, alpha=8.0):
    """(adapter_state_dict, merged_full_params): B is randomized so
    the delta is nonzero (inject zeros B by design)."""
    lc = lora.LoraConfig(rank=rank, alpha=alpha)
    lc_cfg, p = lora.inject(
        cfg, params, lc, jax.random.PRNGKey(seed)
    )
    layers = dict(p["layers"])
    for k in list(layers):
        if k.endswith(lora.LORA_B):
            layers[k] = (
                jax.random.normal(
                    jax.random.PRNGKey(seed + 100),
                    layers[k].shape,
                    jnp.float32,
                )
                * 0.05
            )
    p = dict(p)
    p["layers"] = layers
    # merge() reads alpha from the config inject() returned
    return lora.adapter_state_dict(p), lora.merge(lc_cfg, p)


@pytest.fixture(scope="module")
def adapters(model):
    """Registry with two heterogeneous adapters + per-id merged
    oracle params."""
    cfg, params = model
    sd1, merged1 = _make_adapter(cfg, params, 1, rank=4, alpha=8.0)
    sd2, merged2 = _make_adapter(cfg, params, 2, rank=2, alpha=4.0)
    reg = AdapterRegistry(cfg, max_rank=8)
    reg.register("ad1", sd1, alpha=8.0)
    reg.register("ad2", sd2, alpha=4.0)
    return reg, {"ad1": merged1, "ad2": merged2, None: params}


def _prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 250, size=n).tolist() for n in lengths]


def _tokens(outs):
    return [list(map(int, o)) for o in outs]


# ---------------------------------------------------------------------------
# registry validation


class TestRegistry:
    def test_register_lookup_roundtrip(self, model):
        cfg, params = model
        sd, _ = _make_adapter(cfg, params, 7)
        reg = AdapterRegistry(cfg, max_rank=8)
        v1 = reg.register("a", sd, alpha=8.0)
        assert "a" in reg and len(reg) == 1
        assert reg.ids() == ["a"]
        # re-registration bumps the version (device caches re-upload)
        v2 = reg.register("a", sd, alpha=8.0)
        assert v2 > v1
        reg.unregister("a")
        assert "a" not in reg
        with pytest.raises(KeyError, match="unknown adapter"):
            reg.get("a")

    def test_unservable_target_rejected(self, model):
        cfg, params = model
        lc = lora.LoraConfig(rank=2, alpha=4.0, targets=("w_gate",))
        _, p = lora.inject(cfg, params, lc, jax.random.PRNGKey(0))
        reg = AdapterRegistry(cfg)
        with pytest.raises(ValueError, match="not servable"):
            reg.register("mlp", lora.adapter_state_dict(p))

    def test_half_pair_rejected(self, model):
        cfg, params = model
        sd, _ = _make_adapter(cfg, params, 3)
        sd = {
            "layers": {
                k: v
                for k, v in sd["layers"].items()
                if not k.startswith("wq" + lora.LORA_B)
            }
        }
        reg = AdapterRegistry(cfg)
        with pytest.raises(ValueError, match="missing half"):
            reg.register("halved", sd)

    def test_mixed_ranks_rejected(self, model):
        cfg, params = model
        sd, _ = _make_adapter(cfg, params, 4, rank=4)
        layers = dict(sd["layers"])
        a = np.asarray(layers["wq" + lora.LORA_A])
        layers["wq" + lora.LORA_A] = a[:, :, :2]
        b = np.asarray(layers["wq" + lora.LORA_B])
        layers["wq" + lora.LORA_B] = b[:, :2, :]
        reg = AdapterRegistry(cfg)
        with pytest.raises(ValueError, match="mixed ranks"):
            reg.register("mixed", {"layers": layers})

    def test_rank_above_bank_max_rejected(self, model):
        cfg, params = model
        sd, _ = _make_adapter(cfg, params, 5, rank=4)
        reg = AdapterRegistry(cfg, max_rank=2)
        with pytest.raises(ValueError, match="max_rank"):
            reg.register("fat", sd)

    def test_shape_drift_rejected(self, model):
        cfg, params = model
        sd, _ = _make_adapter(cfg, params, 6)
        layers = dict(sd["layers"])
        a = np.asarray(layers["wk" + lora.LORA_A])
        layers["wk" + lora.LORA_A] = a[:, :-1, :]  # wrong d_in
        reg = AdapterRegistry(cfg)
        with pytest.raises(ValueError, match="must be"):
            reg.register("bent", {"layers": layers})


# ---------------------------------------------------------------------------
# batched-delta vs merged-weight byte parity


def _mixed_run(cfg, params, reg, assignments, **kw):
    kw.setdefault("n_slots", 2)
    kw.setdefault("max_len", 64)
    kw.setdefault("max_new_tokens", 8)
    kw.setdefault("eos_id", None)
    kw.setdefault("adapter_registry", reg)
    kw.setdefault("adapter_cache_slots", 2)
    eng = ContinuousBatcher(cfg, params, **kw)
    for prompt, aid in assignments:
        eng.submit(prompt, adapter_id=aid)
    outs = _tokens(eng.generate_all([]))
    return outs, eng


def _oracle_run(cfg, merged, prompt, **kw):
    kw.setdefault("n_slots", 2)
    kw.setdefault("max_len", 64)
    kw.setdefault("max_new_tokens", 8)
    kw.setdefault("eos_id", None)
    eng = ContinuousBatcher(cfg, merged, **kw)
    return _tokens(eng.generate_all([prompt]))[0]


class TestBatchedParity:
    """Mixed-adapter batches match the per-request merged-weight
    oracle token-for-token."""

    @pytest.mark.parametrize("layout", ["dense", "paged"])
    @pytest.mark.parametrize(
        "sampling",
        [{}, {"temperature": 0.8, "top_k": 5}],
        ids=["greedy", "sampled"],
    )
    @pytest.mark.parametrize(
        "async_depth", [0, 1], ids=["sync", "async"]
    )
    def test_mixed_batch_matches_merged_oracle(
        self, model, adapters, layout, sampling, async_depth
    ):
        cfg, params = model
        reg, merged = adapters
        prompts = _prompts((5, 9, 7, 12), seed=3)
        aids = ["ad1", None, "ad2", "ad1"]
        # sampled runs pin per-request keys so the oracle engine can
        # replay the identical stream from slot 0
        keys = [
            np.asarray(jax.random.PRNGKey(17 + i))
            for i in range(len(prompts))
        ]
        kw = dict(sampling, kv_layout=layout, async_depth=async_depth)
        eng = ContinuousBatcher(
            cfg, params, n_slots=2, max_len=64, max_new_tokens=8,
            eos_id=None, adapter_registry=reg, adapter_cache_slots=2,
            **kw,
        )
        for prompt, aid, key in zip(prompts, aids, keys):
            eng.submit(prompt, adapter_id=aid, prng_key=key)
        outs = _tokens(eng.generate_all([]))
        stats = eng.adapter_stats()
        assert stats["uploads"] >= 2  # both adapters hit the device
        for i, (prompt, aid, key) in enumerate(
            zip(prompts, aids, keys)
        ):
            oracle = ContinuousBatcher(
                cfg, merged[aid], n_slots=2, max_len=64,
                max_new_tokens=8, eos_id=None, **kw,
            )
            oracle.submit(prompt, prng_key=key)
            ref = _tokens(oracle.generate_all([]))[0]
            assert outs[i] == ref, (
                f"req {i} (adapter={aid}, layout={layout}, "
                f"sampling={sampling}, async={async_depth}): "
                f"{outs[i]} != {ref}"
            )

    @multi_device
    @pytest.mark.parametrize("layout", ["dense", "paged"])
    def test_tp2_matches_tp1(self, model, adapters, layout):
        """The sharded bank (B split along tp output columns) changes
        nothing: tp=2 mixed-adapter output == tp=1 output."""
        cfg, params = model
        reg, _ = adapters
        prompts = _prompts((5, 9, 7), seed=4)
        aids = ["ad1", "ad2", None]
        base, _ = _mixed_run(
            cfg, params, reg, list(zip(prompts, aids)),
            kv_layout=layout,
        )
        tp2, eng = _mixed_run(
            cfg, params, reg, list(zip(prompts, aids)),
            kv_layout=layout, mesh_spec=2,
        )
        assert tp2 == base
        assert eng.mesh_shape == {"tp": 2}

    @pytest.mark.parametrize("layout", ["dense", "paged"])
    def test_base_traffic_matches_adapterless_engine(
        self, model, adapters, layout
    ):
        """adapter_id=None rows ride the all-zero slot 0: output is
        byte-identical to an engine with no registry at all."""
        cfg, params = model
        reg, _ = adapters
        prompts = _prompts((5, 9), seed=5)
        with_reg, _ = _mixed_run(
            cfg, params, reg, [(p, None) for p in prompts],
            kv_layout=layout,
        )
        without, _ = _mixed_run(
            cfg, params, None, [(p, None) for p in prompts],
            adapter_registry=None, kv_layout=layout,
        )
        assert with_reg == without


# ---------------------------------------------------------------------------
# program-cache key identity (adapters off == pre-adapter engine)


class TestProgramKeys:
    def test_adapterless_keys_carry_no_adapter_tag(self, model):
        cfg, params = model
        eng = ContinuousBatcher(
            cfg, params, n_slots=2, max_len=32, eos_id=None
        )
        assert eng._adapter_tag() == ()
        for _, key in eng._bound_keys:
            assert "adapters" not in key
        # and the device state carries no adapter index vector
        assert "adapt" not in eng._dev

    def test_adaptered_keys_differ_only_by_tag(self, model, adapters):
        cfg, params = model
        reg, _ = adapters
        plain = ContinuousBatcher(
            cfg, params, n_slots=2, max_len=32, eos_id=None
        )
        lora_eng = ContinuousBatcher(
            cfg, params, n_slots=2, max_len=32, eos_id=None,
            adapter_registry=reg, adapter_cache_slots=3,
        )
        tag = lora_eng._adapter_tag()
        assert tag == ("adapters", 3, 8)
        plain_keys = [k for _, k in plain._bound_keys]
        lora_keys = [k for _, k in lora_eng._bound_keys]
        assert [k + tag for k in plain_keys] == lora_keys
        assert "adapt" in lora_eng._dev

    @pytest.mark.parametrize("layout", ["dense", "paged"])
    def test_adapters_are_operands_of_the_one_chunk_program(
        self, model, adapters, layout
    ):
        """An adapter-enabled engine binds the SAME chunk program an
        adapterless one does: lowered without the adapter operands it
        is that program to the byte, and with them (every slot on
        index 0, the zero adapter) it takes the bank's leaves and the
        index vector as its only new inputs and returns the same
        outputs."""
        cfg, params = model
        reg, _ = adapters
        kw = dict(n_slots=2, max_len=32, eos_id=None, kv_layout=layout)
        plain = ContinuousBatcher(cfg, params, **kw)
        lora_eng = ContinuousBatcher(
            cfg, params, adapter_registry=reg, adapter_cache_slots=3,
            **kw,
        )
        assert plain._adapter_args() == {}

        def lowered(eng, **operands):
            d = eng._dev
            kv = (
                (eng.page_pool, eng._table) if layout == "paged"
                else (eng.cache,)
            )
            return eng._run_chunk.lower(
                *kv, eng.params, d["tok"], d["pos"], d["done"],
                d["limit"], d["keys"], 4, **operands,
            )

        def n_inputs(low):
            return len(jax.tree_util.tree_leaves(low.args_info))

        base = lowered(plain)
        assert lowered(lora_eng).as_text() == base.as_text()
        operands = lora_eng._adapter_args()
        assert sorted(operands) == ["abank", "aidx"]
        assert not np.asarray(operands["aidx"]).any()
        with_ops = lowered(lora_eng, **operands)
        assert n_inputs(with_ops) == n_inputs(base) + len(
            jax.tree_util.tree_leaves(operands)
        )
        assert with_ops.out_info == base.out_info
        assert with_ops.as_text() != base.as_text()


# ---------------------------------------------------------------------------
# device cache: LRU, pins, backpressure


class TestDeviceCache:
    def test_lru_eviction_skips_pinned(self, model, adapters):
        cfg, params = model
        reg, _ = adapters
        sd3, _ = _make_adapter(cfg, params, 9, rank=2, alpha=4.0)
        reg.register("ad3", sd3, alpha=4.0)
        try:
            cache = DeviceAdapterCache(cfg, reg, cache_slots=2)
            s1 = cache.acquire("ad1")  # pinned
            s2 = cache.acquire("ad2")  # pinned
            with pytest.raises(AdapterCacheFull):
                cache.acquire("ad3")  # both slots pinned
            cache.release("ad2")
            s3 = cache.acquire("ad3")  # evicts ad2, NOT pinned ad1
            assert s3 == s2
            assert cache.slot_of("ad1") == s1
            assert cache.slot_of("ad2") is None
            assert cache.stats()["evictions"] == 1
            # re-acquiring the victim re-uploads into some free slot
            cache.release("ad1")
            cache.release("ad3")
            cache.acquire("ad2")
            assert cache.stats()["uploads"] == 4
        finally:
            reg.unregister("ad3")

    def test_engine_backpressure_then_recovery(self, model, adapters):
        """With one bank slot, the second adapter is rejected while
        the first decodes, and admits cleanly after it retires."""
        cfg, params = model
        reg, _ = adapters
        eng = ContinuousBatcher(
            cfg, params, n_slots=2, max_len=64, max_new_tokens=4,
            eos_id=None, adapter_registry=reg, adapter_cache_slots=1,
        )
        eng.submit(_prompts((5,))[0], adapter_id="ad1")
        with pytest.raises(AdapterCacheFull):
            eng.submit(_prompts((6,))[0], adapter_id="ad2")
        # the rejected submit left no ledger entry behind
        assert eng.queue_len() == 1
        eng.generate_all([])
        idx = eng.submit(_prompts((6,))[0], adapter_id="ad2")
        eng.generate_all([])
        assert idx == 1

    def test_scheduler_requeues_on_full_bank(self, model, adapters):
        """The scheduler absorbs AdapterCacheFull: the request waits
        in the EDF heap and completes once a pin frees — no failure
        surfaces to the client."""
        cfg, params = model
        reg, _ = adapters
        eng = ContinuousBatcher(
            cfg, params, n_slots=2, max_len=64, max_new_tokens=4,
            eos_id=None, adapter_registry=reg, adapter_cache_slots=1,
        )
        sched = RequestScheduler(eng)
        reqs = [
            sched.submit(p, adapter_id=aid)
            for p, aid in zip(
                _prompts((5, 6, 7), seed=6), ["ad1", "ad2", "ad1"]
            )
        ]
        sched.run_to_completion()
        assert all(len(r.tokens) == 4 for r in reqs)
        assert eng.adapter_stats()["evictions"] >= 1

    def test_unknown_adapter_raises_before_ledger(
        self, model, adapters
    ):
        cfg, params = model
        reg, _ = adapters
        eng = ContinuousBatcher(
            cfg, params, n_slots=2, max_len=64, eos_id=None,
            adapter_registry=reg,
        )
        with pytest.raises(KeyError, match="unknown adapter"):
            eng.submit([1, 2, 3], adapter_id="nope")
        assert eng.queue_len() == 0

    def test_adapter_id_without_registry_rejected(self, model):
        cfg, params = model
        eng = ContinuousBatcher(
            cfg, params, n_slots=2, max_len=64, eos_id=None
        )
        with pytest.raises(ValueError, match="adapter_registry"):
            eng.submit([1, 2, 3], adapter_id="ad1")

    def test_gpt_config_rejected(self):
        from dlrover_tpu.models.decode import _check_adapters
        from dlrover_tpu.models.gpt import GptConfig

        with pytest.raises(ValueError, match="fused qkv"):
            _check_adapters(GptConfig.tiny(), object())
        _check_adapters(GptConfig.tiny(), None)  # adapters-off ok


# ---------------------------------------------------------------------------
# scheduler policy: quotas + validation


class TestSchedulerPolicy:
    def test_per_tenant_quota_leaves_room_for_others(
        self, model, adapters
    ):
        cfg, params = model
        reg, _ = adapters
        eng = ContinuousBatcher(
            cfg, params, n_slots=2, max_len=64, max_new_tokens=2,
            eos_id=None, adapter_registry=reg, adapter_cache_slots=2,
        )
        sched = RequestScheduler(
            eng, slo=SloConfig(max_active_per_adapter=2)
        )
        prompts = _prompts((4, 5, 6, 7), seed=7)
        sched.submit(prompts[0], adapter_id="ad1")
        sched.submit(prompts[1], adapter_id="ad1")
        with pytest.raises(AdmissionError, match="quota"):
            sched.submit(prompts[2], adapter_id="ad1")
        # the other tenant and base traffic are unaffected
        r_other = sched.submit(prompts[2], adapter_id="ad2")
        r_base = sched.submit(prompts[3])
        sched.run_to_completion()
        assert len(r_other.tokens) == 2 and len(r_base.tokens) == 2
        # quota freed after completion
        sched.submit(prompts[0], adapter_id="ad1")
        sched.run_to_completion()

    def test_unknown_adapter_is_admission_error(
        self, model, adapters
    ):
        cfg, params = model
        reg, _ = adapters
        eng = ContinuousBatcher(
            cfg, params, n_slots=2, max_len=64, eos_id=None,
            adapter_registry=reg,
        )
        sched = RequestScheduler(eng)
        with pytest.raises(AdmissionError, match="unknown adapter"):
            sched.submit([1, 2, 3], adapter_id="ghost")
        before = sched.metrics.requests_total
        assert sched.queue_depth() == 0
        assert before == 0


# ---------------------------------------------------------------------------
# elastic resize with resident adapters


class TestElasticWithAdapters:
    @multi_device
    @pytest.mark.parametrize("layout", ["dense", "paged"])
    def test_live_shrink_replays_adaptered_requests(
        self, model, adapters, layout
    ):
        """Mid-decode tp=2 -> tp=1 shrink: the bank is re-minted
        under the new placement, residents re-upload into their
        existing slots, and the preempted mixed-adapter batch replays
        to exactly the no-resize output."""
        cfg, params = model
        reg, _ = adapters
        prompts = _prompts((5, 8), seed=8)
        aids = ["ad1", "ad2"]
        kw = dict(
            n_slots=2, max_len=64, max_new_tokens=8, eos_id=None,
            chunk=2, kv_layout=layout, adapter_registry=reg,
            adapter_cache_slots=2,
        )
        oracle, _ = _mixed_run(
            cfg, params, reg, list(zip(prompts, aids)), **kw
        )
        eng = ContinuousBatcher(cfg, params, mesh_spec=2, **kw)
        for p, aid in zip(prompts, aids):
            eng.submit(p, adapter_id=aid)
        eng.step()  # some tokens decoded at tp=2
        report = eng.resize(1)
        assert report.direction == "shrink"
        assert report.replayed == 2
        # residents survived the resize in their original slots
        assert sorted(eng._adapter_cache.resident_ids()) == [
            "ad1", "ad2",
        ]
        outs = _tokens(eng.generate_all([]))
        assert outs == oracle

    def test_reset_clears_pins_and_mirrors(self, model, adapters):
        cfg, params = model
        reg, _ = adapters
        eng = ContinuousBatcher(
            cfg, params, n_slots=2, max_len=64, max_new_tokens=4,
            eos_id=None, adapter_registry=reg, adapter_cache_slots=2,
        )
        eng.submit(_prompts((5,))[0], adapter_id="ad1")
        eng.step()
        assert eng._adapter_cache.pinned_count() == 1
        eng.reset()
        assert eng._adapter_cache.pinned_count() == 0
        assert not eng.adapt.any()
        # engine serves cleanly after the rebuild
        eng.submit(_prompts((6,))[0], adapter_id="ad2")
        eng.generate_all([])


# ---------------------------------------------------------------------------
# telemetry surfaces


class TestTelemetry:
    def test_stats_and_residency(self, model, adapters):
        cfg, params = model
        reg, _ = adapters
        eng = ContinuousBatcher(
            cfg, params, n_slots=2, max_len=64, max_new_tokens=2,
            eos_id=None, adapter_registry=reg, adapter_cache_slots=2,
        )
        eng.submit(_prompts((5,))[0], adapter_id="ad1")
        eng.submit(_prompts((6,))[0], adapter_id="ad1")
        eng.generate_all([])
        s = eng.adapter_stats()
        assert s["registered"] == 2.0
        assert s["hits"] >= 1.0 and s["misses"] == 1.0
        assert eng.adapter_residency() == ["ad1"]
        assert eng.adapter_active() == {}

    def test_adapterless_engine_reports_empty(self, model):
        cfg, params = model
        eng = ContinuousBatcher(
            cfg, params, n_slots=2, max_len=32, eos_id=None
        )
        assert eng.adapter_stats() == {}
        assert eng.adapter_residency() == []
        assert eng.adapter_active() == {}
