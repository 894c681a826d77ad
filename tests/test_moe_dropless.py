"""Routing that drops no token (models/moe.dropless_moe) against the
dense every-expert form: each expert computed for each token and
weighted by the routing weights, zero off the top k."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.models import moe

D, E, M, K = 128, 16, 256, 4


def _weights(seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    router = jax.random.normal(ks[0], (D, E)) / np.sqrt(D)
    wg = jax.random.normal(ks[1], (E, D, M)) / np.sqrt(D)
    wu = jax.random.normal(ks[2], (E, D, M)) / np.sqrt(D)
    wd = jax.random.normal(ks[3], (E, M, D)) / np.sqrt(M)
    return router, wg, wu, wd


def _dense(h, router, wg, wu, wd, k):
    p = jax.nn.softmax(h @ router, -1)
    w, idx = jax.lax.top_k(p, k)
    w = w / w.sum(-1, keepdims=True)
    full = jnp.zeros_like(p).at[jnp.arange(h.shape[0])[:, None], idx].set(w)
    y = jax.nn.silu(jnp.einsum("td,edm->etm", h, wg))
    y = y * jnp.einsum("td,edm->etm", h, wu)
    return jnp.einsum("te,etd->td", full, jnp.einsum("etm,emd->etd", y, wd))


@pytest.mark.parametrize("t", [1, 64, 1000])
@pytest.mark.parametrize("uneven", [False, True])
def test_dropless_equals_dense_every_expert(t, uneven):
    router, wg, wu, wd = _weights()
    h = jax.random.normal(jax.random.PRNGKey(t), (t, D))
    if uneven:
        # one expert's column reads a direction every token carries:
        # it is in every token's top k (a quarter of the pairs at
        # k = 4, half of them with k = 2 below), where the capacity
        # path at factor 1.25 keeps 1.25 / 16 of the tokens an expert
        direction = jnp.ones((D,)) / np.sqrt(D)
        router = router.at[:, 3].set(direction * 3.0)
        h = h + 4.0 * direction
    with jax.default_matmul_precision("highest"):
        y, counts = jax.jit(
            lambda h: moe.dropless_moe(h, router, wg, wu, wd, K))(h)
        want = _dense(h, router, wg, wu, wd, K)
    assert int(counts.sum()) == t * K            # no pair dropped
    if uneven:
        assert int(counts[3]) == t               # every token's pair
        assert int(counts[3]) > moe.capacity(
            moe.MoeConfig(n_experts=E, top_k=K), t) or t == 1
    np.testing.assert_allclose(y, want, atol=2e-5 * float(jnp.abs(want).max()) + 1e-6)


def test_one_expert_gets_half_the_pairs():
    router, wg, wu, wd = _weights()
    direction = jnp.ones((D,)) / np.sqrt(D)
    router = router.at[:, 5].set(direction * 3.0)
    h = jax.random.normal(jax.random.PRNGKey(9), (1000, D)) + 4.0 * direction
    with jax.default_matmul_precision("highest"):
        y, counts = moe.dropless_moe(h, router, wg, wu, wd, 2)
        want = _dense(h, router, wg, wu, wd, 2)
    assert int(counts[5]) == 1000 and int(counts.sum()) == 2000
    np.testing.assert_allclose(y, want, atol=2e-5 * float(jnp.abs(want).max()))


@pytest.mark.parametrize("t", [1, 64, 300])
def test_grouped_kernel_against_ragged_dot(t, monkeypatch):
    """The Pallas kernels (interpret mode) on the experts' stack over
    layers, addressed at one layer, against lax.ragged_dot on that
    layer's slice: the same routed layout through both."""
    from dlrover_tpu.ops import flash_attention as fa
    from dlrover_tpu.ops import grouped_matmul as gmm

    router, wg, wu, wd = _weights(seed=2)
    stack = lambda w: jnp.stack([jnp.zeros_like(w), w])
    h = jax.random.normal(jax.random.PRNGKey(t), (t, D))
    seen = []
    monkeypatch.setattr(gmm, "use_kernel", lambda x, w: seen.append(0) or False)
    with jax.default_matmul_precision("highest"):
        want, c2 = moe.dropless_moe(h, router, wg, wu, wd, K)
        monkeypatch.undo()
        monkeypatch.setattr(fa, "force_kernels", lambda: True)
        assert gmm.use_kernel(jnp.zeros((16, D)), wg)
        got, c1 = moe.dropless_moe(
            h, router, stack(wg), stack(wu), stack(wd), K, layer=1)
    assert seen
    np.testing.assert_array_equal(c1, c2)
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_tile_rows_stay_between_a_sublane_tile_and_the_mxu():
    assert moe._tile_rows(64 * 8, 64) == 16
    assert moe._tile_rows(1024 * 8, 64) == 64
    assert moe._tile_rows(3584 * 8, 64) == 128
