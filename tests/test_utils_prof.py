"""Profiling + numeric-health + stats-collection tests."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from dlrover_tpu.master.stats import (
    JobMetricCollector,
    LocalStatsReporter,
    ModelMetrics,
)
from dlrover_tpu.master.strategy_generator import SimpleStrategyGenerator
from dlrover_tpu.utils.numeric import (
    LossSpikeDetector,
    NumericChecker,
    assert_finite,
    find_nonfinite,
)
from dlrover_tpu.utils.prof import cost_analysis


class TestCostAnalysis:
    def test_matmul_flops(self):
        a = jnp.ones((64, 64), jnp.float32)

        def f(x):
            return x @ x

        costs = cost_analysis(f, a)
        # 2*n^3 flops for a square matmul
        assert costs["flops"] >= 2 * 64**3 * 0.9


class TestLossSpike:
    def test_detects_spike_and_dumps(self, tmp_path):
        det = LossSpikeDetector(
            window=50, sigma=4.0, min_warm=10, dump_dir=str(tmp_path)
        )
        rng = np.random.RandomState(0)
        for i in range(30):
            assert not det.observe(i, 1.0 + rng.randn() * 0.01)
        assert det.observe(30, 50.0)
        assert det.observe(31, float("nan"))
        lines = open(tmp_path / "loss_spikes.jsonl").read().splitlines()
        assert len(lines) == 2
        assert json.loads(lines[0])["step"] == 30

    def test_spike_does_not_poison_stats(self):
        det = LossSpikeDetector(window=50, sigma=4.0, min_warm=10)
        for i in range(20):
            det.observe(i, 1.0)
        det.observe(20, 100.0)
        # next normal loss is still normal
        assert not det.observe(21, 1.01)


class TestNumeric:
    def test_find_nonfinite(self):
        tree = {
            "ok": jnp.ones((3,)),
            "bad": jnp.array([1.0, float("inf")]),
        }
        bad = find_nonfinite(tree)
        assert bad == ["bad"]
        try:
            assert_finite(tree)
            raise AssertionError("should have raised")
        except FloatingPointError:
            pass

    def test_checker_compare(self):
        c = NumericChecker(atol=1e-6, rtol=1e-6)
        x = jnp.arange(6.0)
        c.record("layer0", x)
        assert c.compare("layer0", x)["match"]
        rep = c.compare("layer0", x + 1e-3)
        assert not rep["match"]
        assert rep["max_abs"] > 1e-4


class TestStatsCollection:
    def test_collect_and_report(self, tmp_path):
        rep = LocalStatsReporter(str(tmp_path))
        col = JobMetricCollector(
            "job1", reporters=[rep], report_interval=0.0
        )
        col.collect_model_info(num_params=1000, batch_size=8)
        col.collect_node_resource(0, cpu_percent=50, mem_gb=4)
        col.collect_node_resource(1, cpu_percent=70, mem_gb=4)
        col.maybe_report_runtime(global_step=100, samples_per_sec=12.5)
        runtime = [
            json.loads(ln)
            for ln in open(tmp_path / "runtime.jsonl")
        ]
        assert runtime[0]["num_nodes"] == 2
        assert runtime[0]["samples_per_sec"] == 12.5
        model = [json.loads(ln) for ln in open(tmp_path / "model.jsonl")]
        assert model[0]["num_params"] == 1000
        # duplicate model info is not re-reported
        col.collect_model_info(num_params=1000, batch_size=8)
        assert (
            len(open(tmp_path / "model.jsonl").read().splitlines()) == 1
        )


class TestStrategyGenerator:
    def test_parallel_suggestion_shards_when_too_big(self):
        g = SimpleStrategyGenerator(
            num_devices=8, hbm_gb_per_device=16.0
        )
        small = g.suggest_parallel(num_params=100_000_000)
        assert small.fsdp == 1 and small.data == 8
        big = g.suggest_parallel(num_params=13_000_000_000)
        assert big.fsdp > 1
        assert big.data * big.fsdp == 8

    def test_dataloader_suggestion(self):
        g = SimpleStrategyGenerator(8, host_cpu_count=16)
        cfg = g.suggest_dataloader(sample_bytes=4096, global_batch_size=64)
        assert 1 <= cfg.num_workers <= 8
        assert cfg.prefetch >= 1


class TestProgramStats:
    """utils/program_stats.py — the XLA equivalent of the reference's
    TF graph profile extractor (elastic_agent/tensorflow/
    profile_extractor.py) — and its flow into the master's metric
    collector over the ModelInfo RPC."""

    def _stats(self):
        import jax
        import jax.numpy as jnp

        from dlrover_tpu.utils.program_stats import profile_step_fn

        def f(w, x):
            return jnp.tanh(x @ w).sum()

        w = jnp.ones((128, 128))
        x = jnp.ones((32, 128))
        return profile_step_fn(jax.grad(f), w, x)

    def test_extracts_flops_and_ops(self):
        s = self._stats()
        # grad of x@w: forward 2*32*128*128 + backward 2x
        assert s.flops > 1e6
        assert s.op_count > 5
        assert "dot" in s.op_histogram or s.fusion_count > 0
        assert s.arithmetic_intensity > 0

    def test_params_stats(self):
        import jax.numpy as jnp

        from dlrover_tpu.utils.program_stats import params_stats

        out = params_stats({"a": jnp.ones((10, 10)),
                            "b": jnp.ones((5,))})
        assert out["variable_count"] == 2
        assert out["total_variable_bytes"] == 400 + 20
        assert out["max_variable_bytes"] == 400

    def test_model_info_rpc_feeds_collector(self):
        from dlrover_tpu.common import messages as msg
        from dlrover_tpu.common.comm import Envelope
        from dlrover_tpu.master.servicer import MasterServicer

        s = self._stats()
        servicer = MasterServicer()
        servicer.report(
            Envelope(payload=msg.ModelInfo(
                node_id=0,
                num_params=1234,
                flops_per_step=1e12,
                batch_size_per_host=8,
                seq_len=2048,
                program_stats=s.to_json(),
            ))
        )
        model = servicer.metric_collector._model
        assert model is not None
        assert model.num_params == 1234
        assert model.program["flops"] == s.flops
        assert model.program["op_count"] == s.op_count

    def test_op_histogram_tuple_ops(self):
        """Multi-output fusions and tuple collectives — the type itself
        is parenthesized; the op must still be counted (r3 review)."""
        from dlrover_tpu.utils.program_stats import _op_histogram

        hlo = "\n".join([
            "  %p0 = f32[128,128]{1,0} parameter(0)",
            "  %fusion = (f32[128,128]{1,0}, f32[128]{0}) fusion(%p0),"
            " kind=kLoop, calls=%fused_computation",
            "  %ar = (bf16[64]{0}, bf16[64]{0}) all-reduce(%a, %b),"
            " replica_groups={{0,1}}, to_apply=%add",
            "  ROOT %t = (f32[2]{0}) tuple(%x)",
            "  %cp = f32[8]{0} collective-permute(%p0),"
            " source_target_pairs={{0,1}}",
        ])
        hist = _op_histogram(hlo)
        assert hist["fusion"] == 1
        assert hist["all-reduce"] == 1
        assert hist["collective-permute"] == 1
        assert hist["parameter"] == 1
