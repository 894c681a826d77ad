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


# ---------------------------------------------------------------------------
# the block walk: a block of pages a step, no further than the length
# ---------------------------------------------------------------------------

PS = 16


def _walk_case(kv, n_rep, hd, per_row, lengths, dtype=jnp.float32,
               quant=False, layers=None, window=None, seed=11):
    """A pool of b * per_row + 1 pages (page 0 is nobody's), disjoint
    tables in a shuffled order, `lengths` a slot."""
    rng = np.random.default_rng(seed)
    b = len(lengths)
    n_pages = b * per_row + 1
    pool = _pool(rng, n_pages, PS, kv, hd, quant=quant)
    if not quant:
        pool = {n: a.astype(dtype) for n, a in pool.items()}
    if layers:
        pool = {
            n: jnp.stack([a * (1 + i) if a.dtype != jnp.int8 else a
                          for i in range(layers)])
            for n, a in pool.items()
        }
    table = jnp.asarray(
        rng.permutation(np.arange(1, n_pages)).reshape(b, per_row),
        jnp.int32,
    )
    q = jnp.asarray(
        rng.standard_normal((b, kv * n_rep, hd)),
        jnp.bfloat16 if quant else dtype,
    )
    return q, pool, table, jnp.asarray(lengths, jnp.int32), window


def _both(q, pool, table, lengths, window, layer=None):
    ref = pa.paged_attention(
        q, pool, table, lengths, impl="reference", layer=layer,
        window=window,
    )
    ker = pa.paged_attention(
        q, pool, table, lengths, impl="kernel", layer=layer,
        window=window,
    )
    return np.asarray(ker, np.float32), np.asarray(ref, np.float32)


def _block_cells(hd, dtype, per_row):
    pages = {"k": jax.ShapeDtypeStruct((1, PS, 1, hd), dtype)}
    table = jax.ShapeDtypeStruct((1, per_row), jnp.int32)
    return pa._pages_per_block(pages, table) * PS


def test_block_is_sized_from_one_head_and_the_dtype():
    """128 KiB of one KV head's rows: 256 cells of f32, 512 of bf16
    and of int8 (unpacked to two bytes before the products) at
    head_dim 128, whatever the head count; never more than the
    table."""
    assert _block_cells(128, jnp.float32, 80) == 256
    assert _block_cells(128, jnp.bfloat16, 80) == 512
    assert _block_cells(128, jnp.int8, 80) == 512
    assert _block_cells(128, jnp.bfloat16, 4) == 4 * PS
    # a head_dim under a lane tile still takes a tile's lanes in VMEM
    assert _block_cells(64, jnp.float32, 80) == 256


@pytest.mark.parametrize(
    "length", [0, 1, 127, 128, 129, 255, 256, 257, 20 * PS],
    ids=lambda n: f"len{n}",
)
def test_lengths_around_a_blocks_boundary(length):
    """f32 at head_dim 128 walks 256 cells a block, in runs of 8
    pages: empty, one cell, one under, at and one over a run's and a
    block's boundary, and the whole table of 20 pages (a last block
    of 4 pages)."""
    assert _block_cells(128, jnp.float32, 20) == 256
    case = _walk_case(2, 2, 128, 20, [length, 200])
    ker, ref = _both(*case)
    if length == 0:
        # the reference's softmax over no column is NaN; the kernel
        # writes zeros, and the row beside it is untouched by it
        assert not ker[0].any()
        ker, ref = ker[1:], ref[1:]
    np.testing.assert_allclose(ker, ref, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("n_rep", [1, 4, 8], ids=lambda n: f"rep{n}")
@pytest.mark.parametrize("kv", [1, 4, 8], ids=lambda n: f"kv{n}")
def test_group_sizes_and_head_counts(kv, n_rep):
    """n_rep = 1 is the same products with one row; one KV head goes
    without its axis; lengths end in the second block's middle."""
    case = _walk_case(kv, n_rep, 128, 12, [130, 77, 192])
    ker, ref = _both(*case)
    np.testing.assert_allclose(ker, ref, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("layer", [None, 1], ids=["one-layer", "stacked"])
@pytest.mark.parametrize(
    "kv,quant", [(2, False), (4, False), (4, True), (8, True), (1, True)],
    ids=["bf16-kv2", "bf16-kv4", "int8-kv4", "int8-kv8", "int8-kv1"],
)
def test_packed_pools_over_two_blocks(kv, quant, layer):
    """bf16 packs two heads a sublane word, int8 four: each head's
    rows come out of the words as the reference's gather has them,
    over a walk of two blocks of 512 cells, stacked and not."""
    per_row = 40
    case = _walk_case(
        kv, 2, 128, per_row, [per_row * PS - 40, 17, per_row * PS],
        dtype=jnp.bfloat16, quant=quant,
        layers=3 if layer is not None else None,
    )
    ker, ref = _both(*case, layer=layer)
    np.testing.assert_allclose(ker, ref, atol=3e-2, rtol=3e-2)
    if not quant:
        # bf16 in, f32 sums: the kernel and the gathered view differ
        # by the output's rounding and the order of the sums
        assert np.abs(ker - ref).max() < 2e-2


@pytest.mark.parametrize("quant", [False, True], ids=["f32", "int8"])
def test_pages_past_the_length_are_not_read(quant):
    """Every page past a slot's length, and every page of nobody,
    holds NaN (an int8 pool: in its scales); the output is finite and
    the reference's on the clean pool."""
    q, pool, table, lengths, _ = _walk_case(
        2, 2, 128, 12, [5, 130, 177], quant=quant
    )
    ker_clean, ref = _both(q, pool, table, lengths, None)
    live = np.zeros(pool["k"].shape[0], bool)
    for row, n in enumerate(np.asarray(lengths)):
        live[np.asarray(table)[row, : -(-int(n) // PS)]] = True
    names = ("k_scale", "v_scale") if quant else ("k", "v")
    poisoned = dict(pool)
    for name in names:
        arr = np.asarray(pool[name].astype(jnp.float32)).copy()
        arr[~live] = np.nan
        poisoned[name] = jnp.asarray(arr, pool[name].dtype)
    ker = np.asarray(
        pa.paged_attention(q, poisoned, table, lengths, impl="kernel"),
        np.float32,
    )
    assert np.isfinite(ker).all()
    np.testing.assert_array_equal(ker, ker_clean)
    np.testing.assert_allclose(
        ker, ref, atol=3e-2 if quant else 2e-5, rtol=3e-2 if quant else 2e-5
    )


@pytest.mark.parametrize(
    "lengths",
    [[2 * 304 + 150, 2 * 304 + 270], [304 + 5, 3 * 304 + 299], [40, 250]],
    ids=["wrapped-twice", "straddles-the-end", "not-yet-wrapped"],
)
def test_window_over_a_ring_that_wrapped(lengths):
    """window = 256 over a ring of 19 pages (304 cells), f32 blocks of
    16 pages: the walk is 17 pages (two blocks), starts mid-ring
    after two wraps and its first block straddles the ring's end; a
    length under the window walks from page 0."""
    window, ring = 256, 19
    assert _block_cells(128, jnp.float32, ring) == 256
    q, pool, table, lens, _ = _walk_case(2, 2, 128, ring, lengths)
    ker, ref = _both(q, pool, table, lens, window)
    np.testing.assert_allclose(ker, ref, atol=2e-5, rtol=2e-5)
    # the window did something: the full attention differs
    full = pa.paged_attention(
        q, pool, table, jnp.minimum(lens, ring * PS), impl="reference"
    )
    if max(lengths) > window:
        assert np.abs(np.asarray(full) - ref).max() > 1e-3


def test_window_pages_behind_the_window_are_not_read():
    """The ring's entries that hold no position of the window any
    more (freed and handed to someone else) are NaN."""
    window, ring = 256, 19
    lengths = [2 * 304 + 150, 304 + 5]
    q, pool, table, lens, _ = _walk_case(2, 2, 128, ring, lengths)
    ker_clean, ref = _both(q, pool, table, lens, window)
    k = np.asarray(pool["k"]).copy()
    v = np.asarray(pool["v"]).copy()
    tab = np.asarray(table)
    for row, n in enumerate(lengths):
        walked = {
            p % ring for p in range((n - window) // PS, -(-n // PS))
        }
        for entry in set(range(ring)) - walked:
            k[tab[row, entry]] = np.nan
            v[tab[row, entry]] = np.nan
    ker = np.asarray(pa.paged_attention(
        q, {"k": jnp.asarray(k), "v": jnp.asarray(v)}, table, lens,
        impl="kernel", window=window,
    ))
    assert np.isfinite(ker).all()
    np.testing.assert_array_equal(ker, ker_clean)


def test_supports_states_the_head_dim_and_word_rules(monkeypatch):
    """head_dim in whole lane tiles (Mosaic's rule: the interpreter
    has none); a sub-word pool's heads in whole words, or one head;
    the buffers inside the VMEM budget."""
    from dlrover_tpu.ops import flash_attention as fa

    table = jnp.zeros((2, 8), jnp.int32)

    def case(h, kv, hd, dtype):
        q = jax.ShapeDtypeStruct((2, h, hd), jnp.bfloat16)
        cell = jax.ShapeDtypeStruct((9, PS, kv, hd), dtype)
        return q, {"k": cell, "v": cell}, table

    assert pa.supports(*case(8, 2, 128, jnp.bfloat16))
    assert pa.supports(*case(8, 1, 128, jnp.bfloat16))
    assert pa.supports(*case(8, 4, 256, jnp.int8))
    assert pa.supports(*case(6, 3, 128, jnp.float32))
    assert not pa.supports(*case(6, 3, 128, jnp.bfloat16))
    assert not pa.supports(*case(8, 2, 128, jnp.int8))
    assert not pa.supports(*case(512, 512, 128, jnp.bfloat16))
    assert pa.supports(*case(8, 2, 64, jnp.bfloat16))
    monkeypatch.setattr(fa, "_interpret", lambda: False)
    assert pa.supports(*case(8, 2, 128, jnp.bfloat16))
    assert not pa.supports(*case(8, 2, 64, jnp.bfloat16))
    assert not pa.supports(*case(8, 2, 192, jnp.bfloat16))
