"""Paged-attention decode kernel vs the dense-bank reference
formulation (pallas interpret mode on CPU), plus the shape gate and
the gather view. docs/DEVIATIONS.md §10."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.ops import paged_attention as pa

pytestmark = pytest.mark.paged


def _pool(rng, n_pages, page_size, kv, hd, quant=False):
    k = jnp.asarray(
        rng.standard_normal((n_pages, page_size, kv, hd)), jnp.float32
    )
    v = jnp.asarray(
        rng.standard_normal((n_pages, page_size, kv, hd)), jnp.float32
    )
    if not quant:
        return {"k": k, "v": v}
    ks = jnp.abs(k).max(axis=-1, keepdims=True) / 127.0
    vs = jnp.abs(v).max(axis=-1, keepdims=True) / 127.0
    return {
        "k": jnp.round(k / ks).astype(jnp.int8),
        "v": jnp.round(v / vs).astype(jnp.int8),
        "k_scale": ks.astype(jnp.bfloat16),
        "v_scale": vs.astype(jnp.bfloat16),
    }


@pytest.mark.parametrize(
    "b,h,kv,hd,page_size,n_pages,per_row",
    [
        (3, 4, 2, 32, 16, 9, 4),    # GQA, partial pages
        (2, 8, 8, 64, 8, 17, 8),    # MHA, minimum page size
        (1, 4, 4, 128, 16, 5, 2),   # single row, wide head
    ],
)
def test_kernel_matches_reference_fp32(
    b, h, kv, hd, page_size, n_pages, per_row
):
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((b, h, hd)), jnp.float32)
    pages = _pool(rng, n_pages, page_size, kv, hd)
    table = jnp.asarray(
        rng.integers(1, n_pages, size=(b, per_row)), jnp.int32
    )
    lengths = jnp.asarray(
        rng.integers(1, per_row * page_size + 1, size=b), jnp.int32
    )
    ref = pa.paged_attention(q, pages, table, lengths, impl="reference")
    ker = pa.paged_attention(q, pages, table, lengths, impl="kernel")
    np.testing.assert_allclose(
        np.asarray(ker), np.asarray(ref), atol=2e-5, rtol=2e-5
    )


def test_kernel_matches_reference_int8():
    """Fused in-kernel dequant == dequant-then-attend reference."""
    rng = np.random.default_rng(1)
    b, h, kv, hd, page_size, n_pages, per_row = 3, 4, 2, 32, 16, 9, 4
    q = jnp.asarray(rng.standard_normal((b, h, hd)), jnp.float32)
    pages = _pool(rng, n_pages, page_size, kv, hd, quant=True)
    table = jnp.asarray(
        rng.integers(1, n_pages, size=(b, per_row)), jnp.int32
    )
    lengths = jnp.asarray([5, 33, 64], jnp.int32)
    ref = pa.paged_attention(q, pages, table, lengths, impl="reference")
    ker = pa.paged_attention(q, pages, table, lengths, impl="kernel")
    np.testing.assert_allclose(
        np.asarray(ker), np.asarray(ref), atol=3e-2, rtol=3e-2
    )


def test_reference_ignores_dead_pages():
    """Cells past a row's length must not leak into the output, no
    matter what garbage the pages hold (trash-page contract: retired
    slots' rewrites land in pages live rows never read)."""
    rng = np.random.default_rng(2)
    b, h, kv, hd, page_size, per_row = 2, 4, 2, 32, 8, 4
    q = jnp.asarray(rng.standard_normal((b, h, hd)), jnp.float32)
    pages = _pool(rng, 9, page_size, kv, hd)
    # disjoint tables (the engine's refcounting guarantees a live
    # row's cells are never another row's dead cells)
    table = jnp.asarray(
        rng.permutation(np.arange(1, 9)).reshape(b, per_row), jnp.int32
    )
    lengths = jnp.asarray([3, 17], jnp.int32)
    base = pa.paged_attention(q, pages, table, lengths, impl="reference")
    # nuke every cell past each row's length with huge garbage
    k = np.asarray(pages["k"]).copy()
    v = np.asarray(pages["v"]).copy()
    tab = np.asarray(table)
    for row in range(b):
        ln = int(lengths[row])
        for pi in range(per_row):
            for off in range(page_size):
                if pi * page_size + off >= ln:
                    k[tab[row, pi], off] = 1e9
                    v[tab[row, pi], off] = -1e9
    poisoned = {"k": jnp.asarray(k), "v": jnp.asarray(v)}
    out = pa.paged_attention(
        q, poisoned, table, lengths, impl="reference"
    )
    np.testing.assert_array_equal(np.asarray(out), np.asarray(base))


def test_gather_pages_layout():
    rng = np.random.default_rng(3)
    pages = _pool(rng, 6, 4, 2, 32)
    table = jnp.asarray([[2, 5, 1], [3, 3, 0]], jnp.int32)
    view = pa.gather_pages(pages, table)
    assert view["k"].shape == (2, 12, 2, 32)
    np.testing.assert_array_equal(
        np.asarray(view["k"][0, 4:8]), np.asarray(pages["k"][5])
    )
    # a table may repeat a page (shared prefix): both views read it
    np.testing.assert_array_equal(
        np.asarray(view["v"][1, 0:4]), np.asarray(view["v"][1, 4:8])
    )


def test_supports_gate():
    rng = np.random.default_rng(4)
    q = jnp.asarray(rng.standard_normal((2, 4, 32)), jnp.float32)
    pages = _pool(rng, 5, 16, 2, 32)
    table = jnp.zeros((2, 3), jnp.int32)
    assert pa.supports(q, pages, table)
    # page_size below the 8-sublane floor
    assert not pa.supports(q, _pool(rng, 5, 4, 2, 32), table)
    # head_dim below the lane floor
    q_bad = jnp.asarray(rng.standard_normal((2, 4, 24)), jnp.float32)
    assert not pa.supports(q_bad, _pool(rng, 5, 16, 2, 24), table)
    # table batch mismatch
    assert not pa.supports(q, pages, jnp.zeros((3, 3), jnp.int32))
    # kernel never auto-selected on CPU (byte-parity contract)
    assert not pa.use_kernel(q, pages, table)


def test_unknown_impl_rejected():
    rng = np.random.default_rng(5)
    q = jnp.asarray(rng.standard_normal((1, 4, 32)), jnp.float32)
    pages = _pool(rng, 3, 8, 2, 32)
    with pytest.raises(ValueError, match="unknown impl"):
        pa.paged_attention(
            q, pages, jnp.zeros((1, 2), jnp.int32),
            jnp.ones((1,), jnp.int32), impl="nope",
        )


def _stacked_case(quant):
    """A stacked pool [3, n_pages, page_size, KV, hd] of distinct
    layers (bf16, or int8 with bf16 scales), with a bf16 query, a
    table and lengths that end mid-page."""
    rng = np.random.default_rng(6)
    dtype = jnp.bfloat16
    b, h, kv, hd, page_size, n_pages, per_row = 3, 4, 2, 32, 16, 9, 4
    layers = [
        _pool(rng, n_pages, page_size, kv, hd, quant=quant)
        for _ in range(3)
    ]
    pool = {
        name: jnp.stack([lp[name] for lp in layers])
        for name in layers[0]
    }
    if not quant:
        pool = {name: arr.astype(dtype) for name, arr in pool.items()}
    q = jnp.asarray(rng.standard_normal((b, h, hd)), dtype)
    table = jnp.asarray(
        rng.integers(1, n_pages, size=(b, per_row)), jnp.int32
    )
    lengths = jnp.asarray([1, 37, per_row * page_size], jnp.int32)
    return q, pool, table, lengths


@pytest.mark.parametrize("layer", [0, 1, 2])
@pytest.mark.parametrize("impl", ["kernel", "reference"])
@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
def test_stacked_pool_at_layer_equals_that_layer_alone(
    quant, impl, layer
):
    """The forward hands the kernel the WHOLE pool and a traced layer
    index; that must be, bit for bit, the kernel on `pool[layer]`."""
    q, pool, table, lengths = _stacked_case(quant)
    alone = pa.paged_attention(
        q, {name: arr[layer] for name, arr in pool.items()},
        table, lengths, impl=impl,
    )
    stacked = jax.jit(
        lambda q, pool, table, lengths, l: pa.paged_attention(
            q, pool, table, lengths, impl=impl, layer=l
        )
    )(q, pool, table, lengths, jnp.int32(layer))
    assert stacked.dtype == alone.dtype
    np.testing.assert_array_equal(
        np.asarray(stacked, np.float32), np.asarray(alone, np.float32)
    )


@pytest.mark.parametrize("layer", [0, 1, 2])
@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
def test_write_at_layer_leaves_other_layers_bytes(quant, layer):
    """`_write_pages_and_attend` scatters a step's rows into layer
    `layer` of the stacked pool: every leaf of every other layer keeps
    its bytes, the written cells hold the step's K/V (quantized as the
    dense path quantizes), and the rest of that layer is untouched."""
    from dlrover_tpu.models import decode

    q, pool, table, lengths = _stacked_case(quant)
    b, _, hd = q.shape
    kv = pool["k"].shape[3]
    rng = np.random.default_rng(7)
    k_new = jnp.asarray(rng.standard_normal((b, 1, kv, hd)), q.dtype)
    v_new = jnp.asarray(rng.standard_normal((b, 1, kv, hd)), q.dtype)
    # three distinct pages, so no two rows write one cell
    table = table.at[:, 0].set(jnp.asarray([1, 2, 3], jnp.int32))
    positions = jnp.asarray([[0], [5], [15]], jnp.int32)
    _, out = jax.jit(
        lambda pool, l: decode._write_pages_and_attend(
            q[:, None], k_new, v_new, pool, l, table, positions, hd
        )
    )(pool, jnp.int32(layer))
    if quant:
        kq, ks = decode._kv_quantize(k_new)
        vq, vs = decode._kv_quantize(v_new)
        wrote = {"k": kq, "v": vq, "k_scale": ks, "v_scale": vs}
    else:
        wrote = {"k": k_new, "v": v_new}
    assert set(out) == set(pool)
    for name, before in pool.items():
        after = np.asarray(out[name].astype(jnp.float32))
        expect = np.asarray(before.astype(jnp.float32)).copy()
        for row in range(b):
            expect[layer, row + 1, int(positions[row, 0])] = np.asarray(
                wrote[name][row, 0].astype(before.dtype).astype(
                    jnp.float32
                )
            )
        assert out[name].dtype == before.dtype
        np.testing.assert_array_equal(after, expect, err_msg=name)
