"""One chip's share of an expert layer (`moe.dropless_moe` with
`Routing.held`) tied to the model: the shares' parts add up to the
uncut reference's layer, the router's bias moves choices and never
weights, the group-limited choice against a brute-force reference, and
the softmax router as it was."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import _gigachat3_tiny as tiny  # noqa: E402
from dlrover_tpu.models import moe  # noqa: E402
from dlrover_tpu.ops import flash_attention as fa  # noqa: E402
from reference_models import gigachat3 as ref  # noqa: E402

T, D, M, E = 48, 64, 32, 32
ROUTING = dict(top_k=4, scoring="sigmoid", n_group=4, topk_group=2,
               scaling=2.5)


@pytest.fixture(scope="module")
def layer():
    """(model, one expert layer's published leaves, normed tokens)."""
    model = tiny.model_dict()
    lp = {k: v[0] for k, v in tiny.params(model, seed=5)["layers"].items()}
    tokens = jax.random.normal(jax.random.PRNGKey(9), (T, D))
    return model, lp, tokens


def _share(lp, m, first, count, **over):
    routing = moe.Routing(**dict(ROUTING, held=(first, count), **over))
    return moe.dropless_moe(
        m, lp["router"], lp["we_gate"][first:first + count],
        lp["we_up"][first:first + count], lp["we_down"][first:first + count],
        routing, bias=lp["router_bias"],
    )


@pytest.mark.parametrize("kernels", [False, True], ids=["ragged", "kernels"])
def test_the_shares_and_the_shared_expert_once_add_up_to_the_layer(
    layer, kernels, monkeypatch
):
    """Four chips hold 8 of the 32 experts each: their routed parts
    plus the shared expert counted ONCE are the uncut reference's
    layer; a share alone is the reference given that share; the held
    pairs are counted per expert."""
    if kernels:
        monkeypatch.setattr(fa, "force_kernels", lambda: True)
    model, lp, m = layer
    with jax.default_matmul_precision("highest"):
        whole = ref.moe_layer(model, lp, m)
        shared = ref.swiglu(m, lp["ws_gate"], lp["ws_up"], lp["ws_down"])
        total, counts = shared, []
        for first in range(0, E, 8):
            part, c = _share(lp, m, first, 8)
            alone = ref.moe_layer(
                model, dict(lp, **{
                    k: lp[k][first:first + 8]
                    for k in ("we_gate", "we_up", "we_down")}),
                m, held=(first, 8), shared=False)
            assert float(jnp.abs(part - alone).max()) < 1e-5
            total = total + part
            counts.append(np.asarray(c))
    assert float(jnp.abs(total - whole).max()) < 1e-5
    counts = np.concatenate(counts)
    # every token's 4 pairs landed on exactly one chip
    assert counts.sum() == T * 4
    chosen = np.asarray(
        ref.routing_weights(model, m, lp["router"], lp["router_bias"]) > 0)
    np.testing.assert_array_equal(counts, chosen.sum(0))


def test_an_uncut_layer_is_the_share_of_everything(layer):
    model, lp, m = layer
    with jax.default_matmul_precision("highest"):
        whole = ref.moe_layer(model, lp, m, shared=False)
        got, counts = _share(lp, m, 0, E)
    assert float(jnp.abs(got - whole).max()) < 1e-5
    assert int(counts.sum()) == T * 4


def test_the_bias_moves_choices_and_never_weights(layer):
    """With the bias the choice changes for some tokens; where it does
    not, the weights are those of the scores alone (a bias that leaked
    into the weights would change them everywhere)."""
    _, lp, m = layer
    routing = moe.Routing(**ROUTING)
    logits = m @ lp["router"]
    def by_expert(weights, chosen):
        order = np.argsort(np.asarray(chosen), axis=1)
        return (np.take_along_axis(np.asarray(weights), order, 1),
                np.take_along_axis(np.asarray(chosen), order, 1))

    w0, c0 = by_expert(*moe.route(logits, routing, None))
    w1, c1 = by_expert(*moe.route(logits, routing, lp["router_bias"]))
    same = (c0 == c1).all(axis=1)
    assert 0 < same.sum() < T
    np.testing.assert_allclose(w0[same], w1[same], rtol=1e-6)
    w1, c1 = moe.route(logits, routing, lp["router_bias"])
    # weights: 2.5 * s / sum of the chosen s, whatever chose them
    s = np.asarray(jax.nn.sigmoid(logits))
    picked = np.take_along_axis(s, np.asarray(c1), axis=1)
    np.testing.assert_allclose(
        np.asarray(w1), 2.5 * picked / picked.sum(1, keepdims=True),
        rtol=1e-6)
    # a huge bias on one expert of a kept group makes it everyone's
    # first choice and leaves its weight the score's
    bias = jnp.zeros((E,)).at[3].set(100.0)
    w2, c2 = moe.route(logits, routing, bias)
    assert (np.asarray(c2)[:, 0] == 3).all()
    assert float(w2.max()) <= 2.5


def _brute_choice(scores, n_group, topk_group, k):
    """The group-limited choice, a token at a time; ties to the lower
    index."""
    out = []
    for row in np.asarray(scores, np.float64):
        groups = row.reshape(n_group, -1)
        gscore = np.sort(groups, axis=1)[:, -2:].sum(1)
        best = sorted(range(n_group), key=lambda g: (-gscore[g], g))
        kept = set(best[:topk_group])
        cand = [e for e in range(row.size)
                if e // groups.shape[1] in kept]
        out.append(sorted(cand, key=lambda e: (-row[e], e))[:k])
    return np.asarray(out)


def test_group_limited_choice_against_brute_force(layer):
    _, lp, m = layer
    routing = moe.Routing(**ROUTING)
    logits = m @ lp["router"]
    _, chosen = moe.route(logits, routing, lp["router_bias"])
    scores = np.asarray(jax.nn.sigmoid(logits) + lp["router_bias"])
    np.testing.assert_array_equal(
        np.asarray(chosen), _brute_choice(scores, 4, 2, 4))
    # every choice lies in two groups
    assert (np.unique(np.asarray(chosen) // 8, axis=1).shape[1] <= 4)
    assert all(len(set(r // 8)) <= 2 for r in np.asarray(chosen))


def test_a_tie_goes_to_the_lower_index():
    """All logits equal: every group scores alike, groups 0 and 1
    stay, and the four first experts of group 0 are chosen."""
    routing = moe.Routing(**ROUTING)
    logits = jnp.zeros((3, E))
    weights, chosen = moe.route(logits, routing, jnp.zeros((E,)))
    np.testing.assert_array_equal(
        np.asarray(chosen), np.tile(np.arange(4), (3, 1)))
    np.testing.assert_array_equal(
        np.asarray(chosen), _brute_choice(np.full((3, E), 0.5), 4, 2, 4))
    np.testing.assert_allclose(np.asarray(weights), 2.5 / 4)
    # a tie between an expert of a kept group and one outside it is
    # no tie: the one outside is not in the choice
    logits = jnp.zeros((1, E)).at[0, 8:16].set(1.0).at[0, 24:].set(1.0)
    _, chosen = moe.route(logits, routing, jnp.zeros((E,)))
    assert set(np.asarray(chosen)[0] // 8) <= {1, 3}


def test_pairs_held_elsewhere_never_enter_the_sort(layer):
    """The held pairs are counted, the rest are not; the rows' bound
    is for the worst deal and the live tiles are counted."""
    _, lp, m = layer
    y, counts = _share(lp, m, 8, 8)
    assert counts.shape == (8,) and counts.dtype == jnp.int32
    assert 0 < int(counts.sum()) < T * 4
    # no expert held of the chosen: the routed part is exactly zero
    y, counts = moe.dropless_moe(
        m, lp["router"], lp["we_gate"][8:16], lp["we_up"][8:16],
        lp["we_down"][8:16], moe.Routing(**dict(ROUTING, held=(8, 8))),
        bias=jnp.zeros((E,)).at[8:16].set(-100.0),
    )
    assert int(counts.sum()) == 0 and not np.asarray(y).any()


def test_softmax_routing_is_as_it_was(layer):
    """`dropless_moe(..., top_k)` and the config's Routing of a
    softmax model trace the same program, and its result is the
    softmax-then-top-k mixture, byte for byte the one of the plain
    formula the layer had."""
    _, lp, m = layer
    args = (m, lp["router"], lp["we_gate"], lp["we_up"], lp["we_down"])
    by_int = jax.make_jaxpr(lambda *a: moe.dropless_moe(*a, 4))(*args)
    by_routing = jax.make_jaxpr(
        lambda *a: moe.dropless_moe(*a, moe.Routing(top_k=4, held=(0, E)))
    )(*args)
    assert str(by_int) == str(by_routing)
    assert "scatter" in str(by_int) and "clamp" not in str(by_int)
    y, counts = moe.dropless_moe(*args, 4)
    probs = jax.nn.softmax(jnp.dot(
        m, lp["router"], precision=jax.lax.Precision.HIGHEST), axis=-1)
    w, chosen = jax.lax.top_k(probs, 4)
    w = w / w.sum(-1, keepdims=True)
    want = jnp.zeros_like(m)
    for j in range(4):
        e = chosen[:, j]
        h = jax.nn.silu(jnp.einsum("td,tdm->tm", m, lp["we_gate"][e]))
        h = h * jnp.einsum("td,tdm->tm", m, lp["we_up"][e])
        want = want + w[:, j:j + 1] * jnp.einsum(
            "tm,tmd->td", h, lp["we_down"][e])
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(want), atol=2e-5, rtol=2e-5)
    assert int(counts.sum()) == T * 4


@pytest.mark.parametrize("first", [0, 8, 16, 24])
def test_a_share_through_the_kernels_is_the_share(first, monkeypatch):
    """At widths the grouped kernels take (lanes of 128): one chip's 8
    of 32 sigmoid-routed experts through the kernels (interpret mode),
    whose walk ends with the pairs that came, against the same share
    through `lax.ragged_dot`, and the four shares against the uncut
    layer."""
    from dlrover_tpu.ops import grouped_matmul as gmm

    d, m, t = 128, 128, 40
    ks = jax.random.split(jax.random.PRNGKey(11), 6)
    router = jax.random.normal(ks[0], (d, E)) / np.sqrt(d)
    wg = jax.random.normal(ks[1], (E, d, m)) / np.sqrt(d)
    wu = jax.random.normal(ks[2], (E, d, m)) / np.sqrt(d)
    wd = jax.random.normal(ks[3], (E, m, d)) / np.sqrt(m)
    bias = 0.1 * jax.random.normal(ks[4], (E,))
    h = jax.random.normal(ks[5], (t, d))

    def share(at, count):
        return moe.dropless_moe(
            h, router, wg[at:at + count], wu[at:at + count],
            wd[at:at + count],
            moe.Routing(**dict(ROUTING, held=(at, count))), bias=bias)

    with jax.default_matmul_precision("highest"):
        want, c_want = share(first, 8)
        whole, _ = share(0, E)
        parts = sum(share(at, 8)[0] for at in range(0, E, 8))
        monkeypatch.setattr(fa, "force_kernels", lambda: True)
        assert gmm.use_kernel(jnp.zeros((16, d)), wg)
        got, c_got = share(first, 8)
    np.testing.assert_array_equal(np.asarray(c_got), np.asarray(c_want))
    assert 0 < int(c_got.sum()) < t * 4
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    np.testing.assert_allclose(np.asarray(parts), np.asarray(whole), atol=1e-5)
