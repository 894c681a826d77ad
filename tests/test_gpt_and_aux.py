"""GPT-2 model family, sparse PS executor failover, ICI monitor."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh

from dlrover_tpu.models import gpt
from dlrover_tpu.trainer.sparse_executor import SparseTrainingExecutor
from dlrover_tpu.utils.ici_monitor import IciMonitor


class TestGpt:
    def test_tiny_trains(self):
        cfg = gpt.GptConfig.tiny()
        params = gpt.init_params(cfg, jax.random.PRNGKey(0))
        opt = optax.adamw(3e-3)
        opt_state = opt.init(params)
        tokens = jax.random.randint(
            jax.random.PRNGKey(1), (4, 32), 0, cfg.vocab_size
        )

        @jax.jit
        def step(params, opt_state):
            (loss, m), g = jax.value_and_grad(
                lambda p: gpt.loss_fn(cfg, p, {"tokens": tokens}),
                has_aux=True,
            )(params)
            up, opt_state = opt.update(g, opt_state, params)
            return optax.apply_updates(params, up), opt_state, loss

        first = None
        for i in range(30):
            params, opt_state, loss = step(params, opt_state)
            if first is None:
                first = float(loss)
        assert float(loss) < first * 0.5

    def test_sharded_apply_on_mesh(self):
        cfg = gpt.GptConfig.tiny()
        mesh = Mesh(
            np.array(jax.devices()[:8]).reshape(4, 2),
            ("data", "tensor"),
        )
        params = gpt.init_params(cfg, jax.random.PRNGKey(0))
        tokens = jnp.zeros((4, 16), jnp.int32)
        with mesh:
            logits = jax.jit(
                lambda p, t: gpt.apply(cfg, p, t, mesh=mesh)
            )(params, tokens)
        assert logits.shape == (4, 16, cfg.vocab_size)
        assert logits.dtype == jnp.float32

    def test_size_presets(self):
        assert gpt.num_params(gpt.GptConfig.gpt2()) > 100e6
        assert gpt.num_params(gpt.GptConfig.gpt2_xl()) > 1.4e9


class TestSparseExecutor:
    class _FakeLayer:
        def __init__(self):
            self.state = {"w": 1}
            self.loads = 0

        def state_dict(self):
            return dict(self.state)

        def load_state_dict(self, s):
            self.state = dict(s)
            self.loads += 1

    class _FakeClient:
        def __init__(self):
            self.version = 1
            self.steps = []
            self.acks = []

        def get_cluster_version(self, _type="global"):
            return self.version

        def update_cluster_version(self, v, t="local"):
            self.acks.append((v, t))

        def report_global_step(self, s, host_compute_ms=0.0):
            self.steps.append((s, host_compute_ms))

    def test_failover_on_version_change(self, tmp_path):
        layer = self._FakeLayer()
        mc = self._FakeClient()
        seen_rebuilds = []
        ex = SparseTrainingExecutor(
            train_step=lambda b: {"loss": float(b)},
            embedding_layers={"emb": layer},
            master_client=mc,
            ckpt_dir=str(tmp_path),
            version_poll_steps=5,
            report_steps=5,
        )
        ex.on_rebuild(lambda v: seen_rebuilds.append(v))

        def batches():
            for i in range(30):
                if i == 7:
                    mc.version = 2  # PS membership changed mid-stream
                yield i

        metrics = ex.train(batches())
        assert metrics["loss"] == 29.0
        assert ex.rebuild_count == 1
        assert seen_rebuilds == [2]
        assert layer.loads == 1          # restored after rebuild
        assert (2, "local") in mc.acks   # acked to master
        assert ex.global_step == 30 and len(mc.steps) == 6
        # host-compute ms rides every report (straggler signal) and
        # the window RESETS after each report: deterministic check —
        # step 30 is a report boundary, so a missing reset leaves the
        # whole run's accumulated time in the window (timing-ratio
        # assertions were load-flaky on a busy 1-core box)
        ms = [m for _, m in mc.steps]
        assert all(m > 0 for m in ms), ms
        assert ex._host_ms_window == 0.0, (
            "window not reset after report"
        )

    def test_no_master_runs_standalone(self):
        ex = SparseTrainingExecutor(
            train_step=lambda b: {"loss": 0.0}
        )
        out = ex.train(range(3))
        assert ex.global_step == 3 and out == {"loss": 0.0}


@pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs 8-device mesh"
)
class TestIciMonitor:
    def test_probe_and_baseline(self):
        mesh = Mesh(
            np.array(jax.devices()[:8]).reshape(4, 2),
            ("data", "tensor"),
        )
        mon = IciMonitor(mesh, mbytes=0.5)
        stats = mon.probe()
        assert set(stats) == {"data", "tensor"}
        assert all(s.gbps > 0 for s in stats.values())
        mon.probe()
        mon.probe()
        assert mon.baseline("data") > 0
        # CPU wall-clock jitters too much to assert no degradation here;
        # the detection logic is covered deterministically below

    def test_degradation_detection_logic(self):
        mesh = Mesh(np.array(jax.devices()[:8]), ("data",))
        mon = IciMonitor(mesh)
        mon._history["data"] = [10.0, 10.0, 10.0, 2.0]
        assert mon.degraded_axes() == ["data"]


def test_num_params_exact():
    # exact-count contract (the llama counterpart has the same test):
    # init_params' leaf sizes must sum to num_params, incl. the r4
    # attention biases
    import jax

    from dlrover_tpu.models import gpt

    cfg = gpt.GptConfig(
        vocab_size=96, dim=48, n_layers=2, n_heads=4, max_seq_len=32
    )
    params = gpt.init_params(cfg, jax.random.PRNGKey(0))
    actual = sum(
        x.size for x in jax.tree_util.tree_leaves(params)
    )
    assert actual == gpt.num_params(cfg), (
        actual,
        gpt.num_params(cfg),
    )
