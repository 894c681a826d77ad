"""shard_mapped Pallas attention kernels (tp>1 fused-kernel dispatch).

The parity contracts, exercised in interpret mode on the conftest's 8
forced host devices via DLROVER_TPU_FORCE_KERNELS=1:

- EXACT bytes: the shard_mapped kernel vs the tp=1 kernel. Attention
  is embarrassingly parallel over heads and the kernel's scale/blocks
  depend only on the unsharded seq/head_dim axes, so chunking the
  head axis over shards changes nothing about any head's arithmetic.
- allclose only: kernel vs XLA reference. The online softmax computes
  (p@v)/l where the reference computes softmax(s)@v — same math,
  different op order, ~1e-7 apart in f32.
- token-level: a forced-kernel engine emits the same token ids as the
  reference engine (greedy and sampled), and forced tp=2 matches
  forced tp=1 exactly.

Engine-level tests use a dim=128 config (head_dim=32) because the
kernel gates refuse head_dim < 32 — tiny()'s head_dim=16 would make a
"kernel path" test silently run the reference.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.models import llama
from dlrover_tpu.ops import flash_attention as fa
from dlrover_tpu.ops import paged_attention as pa
from dlrover_tpu.ops.attention import (
    dot_product_attention,
    reference_attention,
)
from dlrover_tpu.parallel.mesh import serving_head_specs, serving_mesh
from dlrover_tpu.serving.engine import ContinuousBatcher

pytestmark = pytest.mark.kernels

multi_device = pytest.mark.skipif(
    len(jax.devices()) < 2,
    reason="tp>1 needs >=2 (forced host) devices",
)


@pytest.fixture
def forced(monkeypatch):
    monkeypatch.setenv("DLROVER_TPU_FORCE_KERNELS", "1")


@pytest.fixture(scope="module")
def mesh2():
    if len(jax.devices()) < 2:
        pytest.skip("needs >=2 devices")
    return serving_mesh(2, n_kv_heads=2)


def _flash_qkv(seed=0, b=2, s=256, h=4, kv=2, d=64):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, kv, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, kv, d)), jnp.float32)
    return q, k, v


def _paged_case(seed=0, b=2, h=4, kv=2, d=64, n_pages=9, ps=16, p=4,
                quant=False):
    rng = np.random.default_rng(seed)
    if quant:
        pool = {
            "k": jnp.asarray(
                rng.integers(-127, 127, (n_pages, ps, kv, d)), jnp.int8
            ),
            "v": jnp.asarray(
                rng.integers(-127, 127, (n_pages, ps, kv, d)), jnp.int8
            ),
            "k_scale": jnp.asarray(
                rng.random((n_pages, ps, kv, 1)) * 0.02, jnp.bfloat16
            ),
            "v_scale": jnp.asarray(
                rng.random((n_pages, ps, kv, 1)) * 0.02, jnp.bfloat16
            ),
        }
    else:
        pool = {
            "k": jnp.asarray(
                rng.standard_normal((n_pages, ps, kv, d)), jnp.float32
            ),
            "v": jnp.asarray(
                rng.standard_normal((n_pages, ps, kv, d)), jnp.float32
            ),
        }
    table = jnp.asarray(rng.integers(1, n_pages, (b, p)), jnp.int32)
    lengths = jnp.asarray(
        rng.integers(1, p * ps, size=b), jnp.int32
    )
    q = jnp.asarray(rng.standard_normal((b, h, d)), jnp.float32)
    return q, pool, table, lengths


def _bytes_equal(a, b):
    return bool((np.asarray(a) == np.asarray(b)).all())


# ---------------------------------------------------------------------------
# op-level parity: shard_mapped kernel vs tp=1 kernel vs reference


@multi_device
class TestShardedFlashParity:
    def test_sharded_matches_tp1_bytes(self, forced, mesh2):
        q, k, v = _flash_qkv(seed=1)
        tp1 = fa.flash_attention(q, k, v, causal=True)
        sharded = fa.sharded_flash_attention(q, k, v, mesh2, causal=True)
        assert _bytes_equal(tp1, sharded)

    def test_kernel_allclose_reference(self, forced, mesh2):
        q, k, v = _flash_qkv(seed=2)
        sharded = fa.sharded_flash_attention(q, k, v, mesh2, causal=True)
        ref = reference_attention(q, k, v, causal=True)
        np.testing.assert_allclose(
            np.asarray(sharded), np.asarray(ref), atol=2e-6, rtol=2e-6
        )

    def test_dpa_auto_tp2_takes_sharded_kernel(
        self, forced, mesh2, monkeypatch
    ):
        q, k, v = _flash_qkv(seed=3)
        routed = []
        real = fa.sharded_flash_attention
        monkeypatch.setattr(
            fa,
            "sharded_flash_attention",
            lambda *a, **kw: routed.append(1) or real(*a, **kw),
        )
        out = dot_product_attention(
            q, k, v, causal=True, impl="auto", tp=2, mesh=mesh2
        )
        assert routed, "auto+tp2+mesh must dispatch the sharded kernel"
        assert _bytes_equal(out, fa.flash_attention(q, k, v, causal=True))

    def test_dpa_tp2_without_mesh_stays_reference(self, forced):
        # tp>1 declared but no mesh to shard_map over: must fall back
        # to the reference, never the (wrong-layout) tp=1 kernel
        q, k, v = _flash_qkv(seed=4)
        out = dot_product_attention(
            q, k, v, causal=True, impl="auto", tp=2
        )
        assert _bytes_equal(
            out, reference_attention(q, k, v, causal=True)
        )


@multi_device
class TestShardedPagedParity:
    @pytest.mark.parametrize("quant", [False, True])
    def test_sharded_matches_tp1_bytes(self, forced, mesh2, quant):
        q, pool, table, lengths = _paged_case(seed=5, quant=quant)
        tp1 = pa.paged_attention(q, pool, table, lengths, impl="kernel")
        sharded = pa.paged_attention(
            q, pool, table, lengths, impl="kernel", mesh=mesh2
        )
        assert _bytes_equal(tp1, sharded)

    def test_a_shard_walks_the_blocks_of_tp1(self, forced, mesh2):
        """head_dim 128 in f32: 128 KiB of ONE head's rows are 256
        cells a block, for the two heads of tp = 1 and for a shard's
        one alike, and a walk of 23 pages crosses a block's boundary.
        Sized from all the heads a kernel sees, tp = 1 would walk 128
        cells a block and the shard 256: other partial sums, other
        bytes."""
        q, pool, table, lengths = _paged_case(
            seed=9, d=128, n_pages=49, p=24
        )
        lengths = jnp.asarray([23 * 16 - 3, 16 * 16 + 1], jnp.int32)
        shard = {n: a[:, :, :1] for n, a in pool.items()}
        assert pa._pages_per_block(pool, table) == 16
        assert pa._pages_per_block(shard, table) == 16
        tp1 = pa.paged_attention(q, pool, table, lengths, impl="kernel")
        sharded = pa.paged_attention(
            q, pool, table, lengths, impl="kernel", mesh=mesh2
        )
        assert _bytes_equal(tp1, sharded)
        by_head = jnp.concatenate([
            pa.paged_attention(
                q[:, 2 * g:2 * g + 2],
                {n: a[:, :, g:g + 1] for n, a in pool.items()},
                table, lengths, impl="kernel",
            )
            for g in range(2)
        ], axis=1)
        assert _bytes_equal(tp1, by_head)

    def test_kernel_allclose_reference(self, forced, mesh2):
        q, pool, table, lengths = _paged_case(seed=6)
        sharded = pa.paged_attention(
            q, pool, table, lengths, impl="kernel", mesh=mesh2
        )
        ref = pa.paged_attention(
            q, pool, table, lengths, impl="reference"
        )
        np.testing.assert_allclose(
            np.asarray(sharded), np.asarray(ref), atol=2e-6, rtol=2e-6
        )

    def test_auto_tp2_routes_sharded(self, forced, mesh2, monkeypatch):
        q, pool, table, lengths = _paged_case(seed=7)
        routed = []
        real = pa._sharded_kernel
        monkeypatch.setattr(
            pa,
            "_sharded_kernel",
            lambda *a, **kw: routed.append(1) or real(*a, **kw),
        )
        pa.paged_attention(q, pool, table, lengths, mesh=mesh2)
        assert routed, "auto+mesh(tp=2) must dispatch the sharded kernel"

    def test_sharded_under_jit_matches_eager(self, forced, mesh2):
        # the engine programs call this under trace; jit must not
        # change a byte
        q, pool, table, lengths = _paged_case(seed=8)
        eager = pa.paged_attention(
            q, pool, table, lengths, impl="kernel", mesh=mesh2
        )
        jitted = jax.jit(
            lambda q, p, t, l: pa.paged_attention(
                q, p, t, l, impl="kernel", mesh=mesh2
            )
        )(q, pool, table, lengths)
        assert _bytes_equal(eager, jitted)


class TestDispatchGates:
    def _case(self):
        q = jax.ShapeDtypeStruct((2, 4, 64), jnp.float32)
        pages = {
            "k": jax.ShapeDtypeStruct((8, 16, 2, 64), jnp.float32),
            "v": jax.ShapeDtypeStruct((8, 16, 2, 64), jnp.float32),
        }
        table = np.zeros((2, 4), np.int32)
        return q, pages, table

    def test_unforced_cpu_never_kernels(self, monkeypatch):
        monkeypatch.delenv("DLROVER_TPU_FORCE_KERNELS", raising=False)
        q, pages, table = self._case()
        assert not pa.use_kernel(q, pages, table)
        assert not pa.use_kernel(q, pages, table, tp=2)

    def test_forced_enables_tp2_kernel(self, forced):
        q, pages, table = self._case()
        assert pa.use_kernel(q, pages, table, tp=2)
        # indivisible per-shard heads still refuse, forced or not
        assert not pa.use_kernel(q, pages, table, tp=4)

    def test_head_specs_shard_only_head_axes(self, mesh2):
        specs = serving_head_specs(mesh2)
        assert tuple(specs["qkv"]) == (None, None, "tp", None)
        assert tuple(specs["q1"]) == (None, "tp", None)
        assert tuple(specs["pool"]) == (None, None, None, "tp", None)
        assert tuple(specs["replicated"]) == ()


# ---------------------------------------------------------------------------
# engine-level: kernel_path probe, program-cache isolation, token parity


@pytest.fixture(scope="module")
def kmodel():
    # head_dim=32 (dim=128 / 4 heads): the smallest width the kernel
    # gates accept, so the forced engine genuinely traces the kernel.
    # attn_impl="auto" because tiny() defaults to the "reference"
    # oracle pin, which (correctly) refuses the kernel path outright.
    cfg = dataclasses.replace(
        llama.LlamaConfig.tiny(dim=128, attn_impl="auto"),
        dtype=jnp.float32,
    )
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 250, size=n).tolist() for n in lengths]


def _engine(cfg, params, **kw):
    kw.setdefault("n_slots", 2)
    kw.setdefault("max_len", 64)
    kw.setdefault("max_new_tokens", 6)
    kw.setdefault("chunk", 4)
    kw.setdefault("eos_id", None)
    kw.setdefault("kv_layout", "paged")
    return ContinuousBatcher(cfg, params, **kw)


def _run(cfg, params, prompts, **kw):
    eng = _engine(cfg, params, **kw)
    return [list(map(int, o)) for o in eng.generate_all(prompts)]


class TestEngineKernelPath:
    def test_unforced_paged_engine_reports_reference(
        self, kmodel, monkeypatch
    ):
        monkeypatch.delenv("DLROVER_TPU_FORCE_KERNELS", raising=False)
        cfg, params = kmodel
        assert _engine(cfg, params).kernel_path == "reference"

    def test_forced_paged_engine_reports_kernel(self, kmodel, forced):
        cfg, params = kmodel
        assert _engine(cfg, params).kernel_path == "kernel"

    @multi_device
    def test_forced_tp2_paged_engine_reports_kernel(
        self, kmodel, forced
    ):
        cfg, params = kmodel
        eng = _engine(cfg, params, mesh_spec=2)
        assert eng.kernel_path == "kernel"
        assert eng.mesh_tp == 2

    def test_forced_dense_engine_stays_reference(self, kmodel, forced):
        # dense decode attends over the slot bank (positions-masked
        # gather), never the paged kernel — the probe must not lie
        cfg, params = kmodel
        eng = _engine(cfg, params, kv_layout="dense")
        assert eng.kernel_path == "reference"

    def test_reference_impl_pin_overrides_force(self, kmodel, forced):
        # cfg.attn_impl="reference" is the byte-parity oracle: it must
        # pin the gathered-view formulation even when kernels are
        # forced (and even on a real TPU)
        cfg, params = kmodel
        rcfg = dataclasses.replace(cfg, attn_impl="reference")
        assert _engine(rcfg, params).kernel_path == "reference"

    def test_narrow_heads_refuse_kernel_even_forced(
        self, model_tiny, forced
    ):
        # tiny()'s head_dim=16 fails the >=32 lane gate: forcing the
        # env must not force unsupported shapes onto the kernel
        cfg, params = model_tiny
        assert _engine(cfg, params).kernel_path == "reference"

    def test_forced_and_reference_engines_get_distinct_programs(
        self, kmodel, forced, monkeypatch
    ):
        # the program caches key on the forced-kernel tag: an engine
        # traced with the kernel body must never be served to an
        # unforced engine with the same (cfg, mesh, ...) key
        cfg, params = kmodel
        eng_forced = _engine(cfg, params)
        monkeypatch.delenv("DLROVER_TPU_FORCE_KERNELS")
        eng_ref = _engine(cfg, params)
        assert eng_forced._run_chunk is not eng_ref._run_chunk
        assert eng_ref.kernel_path == "reference"


@pytest.fixture(scope="module")
def model_tiny():
    cfg = dataclasses.replace(
        llama.LlamaConfig.tiny(), dtype=jnp.float32
    )
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


@multi_device
class TestEngineTokenParity:
    def test_greedy_kernel_matches_reference_and_tp1(
        self, kmodel, monkeypatch
    ):
        cfg, params = kmodel
        prompts = _prompts((5, 11, 3), seed=10)
        monkeypatch.delenv("DLROVER_TPU_FORCE_KERNELS", raising=False)
        base = _run(cfg, params, prompts)
        monkeypatch.setenv("DLROVER_TPU_FORCE_KERNELS", "1")
        assert _run(cfg, params, prompts) == base
        assert _run(cfg, params, prompts, mesh_spec=2) == base

    def test_sampled_kernel_matches_reference(
        self, kmodel, monkeypatch
    ):
        cfg, params = kmodel
        prompts = _prompts((5, 9), seed=11)
        kw = dict(temperature=0.8, top_k=20, seed=7)
        monkeypatch.delenv("DLROVER_TPU_FORCE_KERNELS", raising=False)
        base = _run(cfg, params, prompts, **kw)
        monkeypatch.setenv("DLROVER_TPU_FORCE_KERNELS", "1")
        assert _run(cfg, params, prompts, mesh_spec=2, **kw) == base


# ---------------------------------------------------------------------------
# metrics: the kernel-path counter


class TestKernelPathMetrics:
    def test_counter_renders_both_labels(self):
        from dlrover_tpu.serving.metrics import ServingMetrics

        m = ServingMetrics()
        text = m.render()
        assert 'serving_kernel_path_steps_total{path="kernel"} 0' in text
        assert (
            'serving_kernel_path_steps_total{path="reference"} 0' in text
        )
        m.update_kernel_path("kernel", 5)
        assert m.kernel_path_steps == {"kernel": 5, "reference": 0}
        assert (
            'serving_kernel_path_steps_total{path="kernel"} 5'
            in m.render()
        )

    def test_counter_is_monotonic_and_validates_path(self):
        from dlrover_tpu.serving.metrics import ServingMetrics

        m = ServingMetrics()
        m.update_kernel_path("reference", 9)
        m.update_kernel_path("reference", 4)  # lagging copy: no rollback
        m.update_kernel_path("warp-drive", 99)  # unknown label: dropped
        assert m.kernel_path_steps == {"kernel": 0, "reference": 9}
