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


def _layout(counts, d, seed=0, bound=None):
    """The padded layout of `counts` pairs an expert: (x [rows, d]
    with zeros on the padding rows, padded run lengths, the rows that
    hold a pair, each one's expert)."""
    from dlrover_tpu.ops import grouped_matmul as gmm

    counts = np.asarray(counts)
    pairs = int(counts.sum()) if bound is None else bound
    rows = moe.dropless_rows(pairs, len(counts))
    group_rows = -(-counts // gmm.SUB_ROWS) * gmm.SUB_ROWS
    starts = np.cumsum(group_rows) - group_rows
    held = np.concatenate(
        [s + np.arange(c) for s, c in zip(starts, counts)]).astype(int)
    expert = np.repeat(np.arange(len(counts)), counts)
    x = np.zeros((rows, d), np.float32)
    x[held] = np.random.default_rng(seed).standard_normal((len(held), d))
    return jnp.asarray(x), jnp.asarray(group_rows, jnp.int32), held, expert


def _pair_by_pair(x, wg, wu, wd, held, expert):
    """Each held row through its own expert's SwiGLU, one at a time
    in float64: no layout, no grouping."""
    x, wg, wu, wd = (np.asarray(a, np.float64) for a in (x, wg, wu, wd))
    out = np.zeros((len(held), wd.shape[-1]))
    for i, (r, e) in enumerate(zip(held, expert)):
        g = x[r] @ wg[e]
        out[i] = (g / (1 + np.exp(-g)) * (x[r] @ wu[e])) @ wd[e]
    return out


def _check_walk(counts, bound=None, layer=None):
    from dlrover_tpu.ops import grouped_matmul as gmm

    _, wg, wu, wd = _weights(seed=4)
    e = len(counts)
    wg, wu, wd = wg[:e], wu[:e], wd[:e]
    x, group_rows, held, expert = _layout(counts, D, bound=bound)
    stacked = (wg, wu, wd)
    if layer is not None:
        stacked = tuple(
            jnp.stack([jnp.zeros_like(w)] * layer + [w]) for w in stacked)
    with jax.default_matmul_precision("highest"):
        got = gmm.expert_mlp_kernel(x, *stacked, group_rows, layer=layer)
        ragged = gmm.expert_mlp_ragged(x, wg, wu, wd, group_rows)
    got, ragged = np.asarray(got)[held], np.asarray(ragged)[held]
    np.testing.assert_allclose(got, ragged, atol=1e-5)
    np.testing.assert_allclose(
        got, _pair_by_pair(x, wg, wu, wd, held, expert), atol=2e-5)
    # what the caller may gather is finite, whatever lies elsewhere
    assert np.isfinite(got).all()


@pytest.mark.parametrize("n", [1, 15, 16, 17, 127, 128, 129, 300])
def test_the_walk_stops_at_a_runs_length(n):
    """Runs of n rows around a sub-tile's and a chunk's edges, beside
    a short run and an expert with none: the kernels (interpret mode)
    against `lax.ragged_dot` and against each pair on its own."""
    _check_walk([3, n, 0, n, 1])


@pytest.mark.parametrize("counts", [
    [0, 5, 40, 0, 17, 0, 3, 0],      # no rows: first, last, between
    [0, 0, 0, 0, 0, 0, 0, 9],        # only the last expert holds any
    [9, 0, 0, 0, 0, 0, 0, 0],        # only the first
    [0, 0, 0, 300, 0, 0, 0, 0],      # every pair on one expert
    [130, 129, 128, 127, 17, 16, 15, 1],
], ids=["holes", "last", "first", "one", "edges"])
def test_the_walk_survives_any_deal(counts):
    _check_walk(counts)


def test_the_walk_over_a_share_whose_pairs_came_in_part():
    """A held share's rows are bounded for every pair landing here;
    40 of 640 came: the walk ends with the runs."""
    _check_walk([0, 5, 0, 17, 18, 0, 0, 0], bound=640, layer=1)


def test_a_share_whose_pairs_all_lie_elsewhere(monkeypatch):
    """No run at all: the kernels do nothing and end; through
    `dropless_moe` the routed part is exactly zero."""
    from dlrover_tpu.ops import flash_attention as fa
    from dlrover_tpu.ops import grouped_matmul as gmm

    _, wg, wu, wd = _weights(seed=4)
    x, group_rows, _, _ = _layout([0] * 8, D, bound=64)
    gmm.expert_mlp_kernel(
        x, wg[:8], wu[:8], wd[:8], group_rows).block_until_ready()
    monkeypatch.setattr(fa, "force_kernels", lambda: True)
    router, wg, wu, wd = _weights(seed=2)
    # the held half's columns read against the direction every token
    # carries: no token chooses any of them
    direction = jnp.ones((D,)) / np.sqrt(D)
    router = router.at[:, 8:].set(-direction[:, None] * 3.0)
    h = jax.random.normal(jax.random.PRNGKey(3), (40, D)) + 4.0 * direction
    assert gmm.use_kernel(jnp.zeros((16, D)), wg)
    y, counts = moe.dropless_moe(
        h, router, wg[8:], wu[8:], wd[8:], moe.Routing(top_k=K, held=(8, 8)))
    assert int(counts.sum()) == 0 and not np.asarray(y).any()


@pytest.mark.parametrize("counts", [
    [0, 5, 40, 0, 17, 0, 3, 0], [0, 0, 0, 0, 0, 0, 0, 9], [33] * 8,
], ids=["holes", "last", "even"])
def test_the_walk_in_blocks_of_columns(counts, monkeypatch):
    """A matrix over the block's bytes goes in blocks of columns, the
    outer grid axis: after one block's last run comes the next
    block's first, with experts that hold nothing on either side."""
    from dlrover_tpu.ops import grouped_matmul as gmm

    monkeypatch.setattr(gmm, "_BLOCK_BYTES", D * 128 * 4)
    assert gmm._column_block(D, M, 4) == 128       # gate, up: two blocks
    assert gmm._column_block(M, D, 4) == D         # down: whole
    _check_walk(counts)
    monkeypatch.setattr(gmm, "_BLOCK_BYTES", D * 64 * 4)
    assert gmm._column_block(M, D, 4) == D         # no narrower than lanes
    _check_walk(counts, layer=1)


@pytest.mark.parametrize("counts", [
    [0, 5, 40, 0, 17, 0, 3, 0], [130, 129, 128, 127, 17, 16, 15, 1],
], ids=["holes", "edges"])
def test_the_walk_in_slabs_of_columns(counts, monkeypatch):
    """A product spans a slab of its block's columns where the block
    is over the slab's bytes (what a product spans is code, eight
    static shapes of it): a trip a slab, the same rows."""
    from dlrover_tpu.ops import grouped_matmul as gmm

    # the served widths: Mellum2's, GigaChat3.1's blocks of columns
    assert gmm._slab_columns(2304, 896, 2) == 128
    assert gmm._slab_columns(896, 2304, 2) == 1152
    assert gmm._slab_columns(7168, 512, 2) == 128
    assert gmm._slab_columns(2048, 1792, 2) == 256
    assert gmm._slab_columns(D, M, 4) == M            # here: whole
    monkeypatch.setattr(gmm, "_SLAB_BYTES", D * 128 * 4)
    assert gmm._slab_columns(D, M, 4) == 128          # gate, up: two slabs
    assert gmm._slab_columns(M, D, 4) == D            # down: whole
    _check_walk(counts)


@pytest.mark.parametrize("bucket", [1024, 1536, 2048, 2560, 3072, 3584])
def test_a_prefills_rows_are_its_pairs_and_a_tenth(bucket):
    """Mellum2's deal (8 of 64 experts a token): the rows multiplied
    for a prefill bucket, each run padded to 16, are at most 1.10
    times the pairs, and the static bound `dropless_rows` at most 15
    rows an expert more than the pairs (`engine.admit`'s `moe_rows`)."""
    from dlrover_tpu.ops import grouped_matmul as gmm

    pairs, experts = bucket * 8, 64
    rows = moe.dropless_rows(pairs, experts)
    assert rows % gmm.SUB_ROWS == 0
    assert pairs <= rows <= pairs + 15 * experts
    rng = np.random.default_rng(bucket)
    for skew in (None, 1.0):
        p = np.full(experts, 1 / experts) if skew is None else rng.dirichlet(
            np.full(experts, skew))
        counts = rng.multinomial(pairs, p)
        multiplied = int((-(-counts // 16) * 16).sum())
        assert multiplied <= rows and multiplied <= 1.10 * pairs
    # a decode batch, and a deal with fewer pairs than experts
    assert moe.dropless_rows(64 * 8, 64) == 512 + 15 * 64
    assert moe.dropless_rows(8, 64) == 8 * 16
