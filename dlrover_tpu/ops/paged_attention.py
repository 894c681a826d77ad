"""Pallas TPU paged-attention decode kernel (vLLM PagedAttention, TPU
re-design).

The serving engine's paged KV layout (serving/engine.py kv_layout=
"paged") stores K/V in a global page pool stacked over layers,
`[L, n_pages, page_size, KV, hd]`; each batch row owns a page TABLE
`[P]` of physical page ids covering logical positions
[i*page_size, (i+1)*page_size), shared by every layer. Decode
attention must gather a row's pages of ONE layer and attend a single
query over them. The pool comes in stacked, with the layer's index
(`layer`, traced: the forward's layer loop carries the whole pool and
never slices a layer out); a per-layer pool `[n_pages, page_size, KV,
hd]` with no `layer` is the same thing with L = 1. This module
provides both halves:

- `paged_attention(..., impl="reference")`: gather the pages into a
  dense [B, M, KV, hd] view and run EXACTLY the grouped-einsum masked
  softmax that models/decode.py's `_cached_attention` runs on the
  dense slot bank (same shapes, same ops, same reduction widths).
  This is the byte-parity workhorse: the paged engine is bit-identical
  to the dense oracle because the attention FORMULATION is identical
  — pages only change where the bytes live, never what is computed.
  Masked columns contribute exact-zero probability whatever garbage a
  trash/stale page holds, so the gather may read anything dead.
- `paged_attention(..., impl="kernel")`: a Pallas kernel in the
  flash_attention.py online-softmax style that never materializes the
  dense view. The layer index, the page table and the lengths ride in
  as SCALAR-PREFETCH operands (pltpu.PrefetchScalarGridSpec) and the
  pool stays in HBM: one grid step per slot copies that slot's pages
  of that layer itself, a BLOCK of pages at a time (one DMA a page,
  the next block's started before this block's arithmetic, two
  buffers), from the first page the window still reaches to the page
  of the last position and no further. Per KV head the scores and the
  value sums are matrix products on the MXU (the group's query rows
  against the block's cells), the online softmax stays f32. int8
  pools are multiplied by their scales in VMEM, head by head, before
  the products: the cache reads stay int8 in HBM. interpret=True on
  CPU keeps tier-1 runnable.
- `impl="auto"`: the kernel on a TPU when `supports()` passes, else
  the reference (a decision the engine logs once and reports as
  `kernel_path`). CPU tier-1 therefore runs the reference —
  which is what makes the engine parity sweep deterministic — unless
  DLROVER_TPU_FORCE_KERNELS=1 (the shard_map parity tests / bench)
  forces the interpret-mode kernel. Under a serving mesh (tp > 1)
  the kernel dispatches shard_mapped over the "tp" axis: each shard
  streams the pages of its own KV-head slice (no collectives).

The single-query shape gate reuses ops/flash_attention.supports()
(fixed to accept q_len == 1 decode shapes) for the head_dim range
and the GQA grouping; what the walk itself asks of the pool (head_dim
in whole 128-lane tiles on a TPU, sub-word heads in whole sublane
words) is `supports()`'s own.
"""

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dlrover_tpu.ops import flash_attention as fa

NEG_INF = -1e30


def supports(q, pages: Dict, table, tp: int = 1) -> bool:
    """Whether the Pallas kernel handles these shapes. `q` is the
    [B, H, hd] single-token query, `pages` the pool dict (per-layer
    or stacked: the gate reads the last three dims), `table` the
    [B, P] page table. Reuses flash_attention's q_len==1 gate for the
    head_dim range and the GQA grouping, then checks what the walk
    itself needs of the pool.

    `tp` is the serving tensor-parallel degree: the gate judges the
    PER-SHARD head counts (heads / tp), because that is what the
    kernel would see under GSPMD head sharding — a global count that
    doesn't divide over tp fails outright."""
    b, h, d = q.shape
    page_size, kv = pages["k"].shape[-3:-1]
    shard = fa.per_shard_heads(h, kv, tp)
    if shard is None:
        return False
    h, kv = shard
    q_probe = jax.ShapeDtypeStruct((b, 1, h, d), q.dtype)
    k_probe = jax.ShapeDtypeStruct((b, 1, kv, d), q.dtype)
    if not fa.supports(q_probe, k_probe, block_q=1, block_k=1):
        return False
    # Mosaic copies no page whose head_dim is not whole 128-lane tiles
    if d % _LANES and not fa._interpret():
        return False
    # a sub-word pool packs 2 (bf16) or 4 (int8) neighbouring heads a
    # sublane word: whole words, or the one head that goes without
    # its axis
    if kv > 1 and kv % (4 // pages["k"].dtype.itemsize):
        return False
    # a page is a block's unit and a major dim of it, so any size
    # lowers — but below 8 cells a copy moves too little
    if page_size < 8:
        return False
    if table.ndim != 2 or table.shape[0] != b:
        return False
    return _vmem_bytes(pages, table, kv) <= _VMEM_BUDGET


def use_kernel(q, pages: Dict, table, tp: int = 1) -> bool:
    """Static (trace-time) dispatch decision for the engine: the
    kernel on a real TPU backend (or under the
    DLROVER_TPU_FORCE_KERNELS=1 interpret-mode escape hatch the
    shard_map parity tests and the bench use) — CPU otherwise takes
    the reference, the byte-parity formulation, which keeps the
    engine parity sweeps deterministic. tp > 1 dispatches the
    SHARD_MAPPED kernel: each shard runs the same Pallas program on
    its per-shard heads (`supports()` judges the per-shard shapes),
    so multi-chip replicas keep the fused int8-dequant page streaming
    instead of regathering into the einsum reference."""
    if jax.default_backend() != "tpu" and not fa.force_kernels():
        return False
    return supports(q, pages, table, tp=tp)


# ---------------------------------------------------------------------------
# reference: gather + the dense-bank attention formulation
# ---------------------------------------------------------------------------


def _stacked(pages: Dict, layer):
    """(stacked pool, layer index as int32[1]). A per-layer pool
    (`layer` None) is the stacked form with L = 1 and layer 0:
    `arr[None]` is a bitcast, not a copy."""
    if layer is None:
        pages = {name: arr[None] for name, arr in pages.items()}
        layer = 0
    return pages, jnp.asarray(layer, jnp.int32).reshape(1)


def gather_pages(pages: Dict, table, layer=None) -> Dict:
    """Materialize the dense [B, M, KV, ...] view of each row's pages
    (M = P * page_size), of layer `layer` where the pool is stacked —
    in ONE gather (`arr[layer, table]`), so no layer is sliced out
    first. A pure read: XLA lowers it to a gather, no pool mutation.
    Rows of `table` pointing at the trash page (or at stale pages)
    surface garbage that the position mask must hide — which it does,
    exactly (masked softmax columns are 0.0)."""
    pages, layer = _stacked(pages, layer)
    out = {}
    for name, arr in pages.items():
        g = arr[layer[0], table]  # [B, P, page_size, KV, ...]
        out[name] = g.reshape((g.shape[0], -1) + g.shape[3:])
    return out


def ring_view(table, lengths, window: int, page_size: int):
    """The pages of a window layer's RING table in logical order:
    (first logical page that still holds a position inside the
    window [B], page ids [B, R] of logical pages first .. first+R-1).
    Logical page p of a slot lives at ring entry p % R."""
    ring = table.shape[1]
    first = jnp.maximum(lengths - window, 0) // page_size
    logical = first[:, None] + jnp.arange(ring, dtype=jnp.int32)[None, :]
    return first, jnp.take_along_axis(table, logical % ring, axis=1)


def _reference(q, pages, table, lengths, scale, layer=None, window=None):
    if window is not None:
        return _reference_window(
            q, pages, table, lengths, scale, layer, window
        )
    """The dense-bank formulation on the gathered view — kept
    OP-FOR-OP identical to models/decode.py::_cached_attention (same
    grouped einsum, same mask, same softmax axis) so the paged engine
    can be byte-compared against the dense oracle. q: [B, H, hd],
    single decode query per row at position lengths-1 (`lengths`
    [B], or [B, H] where a row's heads see different lengths: a
    diffusion block's rows beside a carried block's)."""
    view = gather_pages(pages, table, layer)
    k_cache, v_cache = view["k"], view["v"]
    if "k_scale" in view:
        k_cache = (
            k_cache.astype(q.dtype) * view["k_scale"].astype(q.dtype)
        )
        v_cache = (
            v_cache.astype(q.dtype) * view["v_scale"].astype(q.dtype)
        )
    b, h, hd = q.shape
    m = k_cache.shape[1]
    kv = k_cache.shape[2]
    n_rep = h // kv
    qg = q.reshape(b, 1, kv, n_rep, hd)
    scores = jnp.einsum(
        "bskrd,bmkd->bkrsm", qg, k_cache,
        preferred_element_type=jnp.float32,
    ) * scale
    cols = jnp.arange(m)[None, None, None, None, :]
    if lengths.ndim == 2:
        rows = (lengths - 1).reshape(b, kv, n_rep, 1, 1)
    else:
        rows = (lengths - 1)[:, None, None, None, None]
    scores = jnp.where(cols <= rows, scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkrsm,bmkd->bskrd", p, v_cache)
    return out.reshape(b, h, hd)


def _reference_window(q, pages, table, lengths, scale, layer, window):
    """The window layer's formulation over the ring: gather the ring's
    pages in logical order and mask the cells outside
    (length - window, length). Same einsums as `_reference`."""
    page_size = pages["k"].shape[-3]
    first, ordered = ring_view(table, lengths, window, page_size)
    view = gather_pages(pages, ordered, layer)
    k_cache, v_cache = view["k"], view["v"]
    b, h, hd = q.shape
    m = k_cache.shape[1]
    kv = k_cache.shape[2]
    qg = q.reshape(b, 1, kv, h // kv, hd)
    scores = jnp.einsum(
        "bskrd,bmkd->bkrsm", qg, k_cache,
        preferred_element_type=jnp.float32,
    ) * scale
    cols = (first * page_size)[:, None] + jnp.arange(m)[None, :]
    live = (cols < lengths[:, None]) & (
        cols >= (lengths - window)[:, None]
    )
    scores = jnp.where(live[:, None, None, None, :], scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkrsm,bmkd->bskrd", p, v_cache)
    return out.reshape(b, h, hd)


# ---------------------------------------------------------------------------
# Pallas kernel
# ---------------------------------------------------------------------------

# What ONE KV head's K (or V) rows of one block may take of a block
# buffer: the block's cells follow from it (512 of bf16 at head_dim
# 128), so a tensor-parallel shard walks the blocks of tp = 1 whatever
# its head count (byte parity).
_HEAD_BLOCK_BYTES = 128 * 1024
# Two buffers each of K and V blocks, every KV head of them: 4 MiB at
# 8 KV heads of bf16, inside the 16 MiB of scoped VMEM a v5e kernel
# has by default. Wider pools (32 heads: 16 MiB) have the scoped limit
# raised to what they need, up to this much of the chip's 128 MiB.
_VMEM_BUDGET = 32 * 1024 * 1024
_VMEM_SCOPED_DEFAULT = 16 * 1024 * 1024
# Beside the buffers: a block's unpacked operands, the scores, the
# compiler's own temporaries (an int8 MHA block spills 10 MiB).
_VMEM_HEADROOM = 12 * 1024 * 1024
_LANES, _SUBLANES = 128, 8
# Pages whose copies start, and are waited for, as one straight run.
_RUN_PAGES = 8


def _pad(n: int, to: int) -> int:
    return -(-n // to) * to


def _pages_per_block(pages: Dict, table) -> int:
    """Pages one block of the walk holds: as many as keep one KV
    head's rows inside `_HEAD_BLOCK_BYTES` (int8 counted as the two
    bytes a cell is unpacked to before the products), at most the
    table."""
    page_size, _, hd = pages["k"].shape[-3:]
    page = page_size * _pad(hd, _LANES) * max(
        2, pages["k"].dtype.itemsize
    )
    return min(max(1, _HEAD_BLOCK_BYTES // page), table.shape[1])


def _vmem_bytes(pages: Dict, table, kv: int) -> int:
    """The buffers of a kernel over `kv` KV heads, padded as VMEM
    tiles them: two blocks of K and two of V (heads on sublanes,
    head_dim on lanes) and, for an int8 pool, the scales of a slot's
    whole table, K's and V's, twice (heads on lanes)."""
    page_size, _, hd = pages["k"].shape[-3:]
    cell = _pad(kv, _SUBLANES) * _pad(hd, _LANES) * pages["k"].dtype.itemsize
    total = _pages_per_block(pages, table) * page_size * cell
    if "k_scale" in pages:
        total += (
            table.shape[1] * page_size * _pad(kv, _LANES)
            * pages["k_scale"].dtype.itemsize
        )
    return 2 * 2 * total


def _head_rows(buf):
    """g -> KV head g's [cells, hd] rows, f32, out of one block as
    the pool lays it out (`buf` [cells, KV, hd]: heads on sublanes).
    A sub-word dtype packs neighbouring heads into one 32-bit sublane
    word, and a plain `buf[:, g, :]` lowers to a load, a rotate and a
    select per CELL; so the rows are read as words with a sublane
    stride (one load per 8 cells, whichever the head) and a word's
    heads split with shifts. Mosaic's strided load wants whole lane
    tiles and the words whole: another head_dim, or a head count that
    does not fill a word (a one-head shard of a bf16 pool), takes the
    plain way."""
    if len(buf.shape) == 2:  # one head, its axis dropped (`_kernel`)
        return lambda g: buf[...].astype(jnp.float32)
    cells, kv, hd = buf.shape
    pack = 4 // buf.dtype.itemsize
    if hd % _LANES or kv % pack:
        return lambda g: buf[:, g, :].astype(jnp.float32)
    per_cell = kv // pack
    words = buf.bitcast(jnp.uint32) if pack > 1 else buf
    words = words.reshape(cells * per_cell, hd)
    loaded = {}

    def rows(g):
        word, part = divmod(g, pack)
        if word not in loaded:
            loaded.clear()  # heads come in order: one word live
            loaded[word] = words[pl.ds(word, cells, stride=per_cell), :]
        w = loaded[word]
        if pack == 1:
            return w.astype(jnp.float32)
        if pack == 4:
            # int8: byte `part` up to the top, back down with its sign
            w = pltpu.bitcast(w << (24 - 8 * part), jnp.int32) >> 24
            return w.astype(jnp.float32)
        # bf16 is the top half of an f32
        w = w & jnp.uint32(0xFFFF0000) if part else w << 16
        return pltpu.bitcast(w, jnp.float32)

    return rows


def _paged_kernel(layer_ref, table_ref, len_ref,  # scalar prefetch
                  q_ref, k_hbm, v_hbm, scales, o_ref,
                  k_buf, v_buf, sems, turn,
                  m_scr, l_scr, acc_scr, s_scr, pv_scr,
                  *, scale, page_size, per_block, window=None,
                  latent=None, carried=None):
    """Grid (B,): one invocation attends query row b, every KV head of
    it, over the pages its length covers, `per_block` pages at a time.

    `carried` (static: (rows, cells)) gives the first `rows` query
    rows of every KV head's group a length `cells` shorter: a
    diffusion block carried beside the next one, in the same walk.

    `latent` (static: the latent's rank) is the LATENT variant of the
    same walk: the pool's one leaf holds a row a token that is both
    key and value (`v_hbm` and `v_buf` are empty), a block of rows is
    copied once, every head's scores are one product `[H, W] x [W,
    cells]` against it and the value sums one product `[H, cells] x
    [cells, latent]` against its first `latent` columns.

    K and V stay in HBM (`k_hbm`, `v_hbm`: the stacked pool's
    leaves); a slot's pages are not contiguous, so the body copies
    them itself, one DMA a page and leaf into buffer `cur` of two,
    and starts the next block's copies — the next SLOT's first block
    after a slot's last — before it waits for this block's (`turn`
    carries the buffer's parity over grid steps). The walk runs from
    the first page that still holds a position inside the window
    (page 0 without one) to the page of position length - 1: pages
    past the length are never read, a slot of length 0 reads nothing
    and writes zeros.

    Per KV head (`_head_rows`), scores are the group's [n_rep, hd]
    query rows against [hd, cells] and the value sum the probabilities
    [n_rep, cells] against [cells, hd], both on the MXU with f32
    accumulation, operands in the query's dtype (an int8 head's rows
    are first multiplied by their scales, in VMEM: HBM traffic stays
    int8; `scales` is (K's, V's) of the slot's walk, [1, cells of
    the walk, KV], or empty). Between the two the heads' scores stand
    together in `s_scr` [H, cells], so that the online softmax (max,
    sum, rescale: f32, in VMEM scratch) is ONE chain of whole
    registers a block and not one chain of quarter-filled ones a
    head. Arithmetic never mixes KV heads, and a block's cells do not
    depend on how many heads the kernel sees (the tp byte-parity
    argument)."""
    b = pl.program_id(0)
    slots = pl.num_programs(0)
    kv, n_rep = q_ref.shape[1:3]
    ring = table_ref.shape[1]
    cells = per_block * page_size
    run_pages = min(_RUN_PAGES, per_block)
    leaves = ((k_hbm, k_buf),)
    if latent is None:
        leaves += ((v_hbm, v_buf),)

    def walk(slot):
        """(first logical page, pages) of the slot's walk."""
        first = 0
        if window is not None:
            first = jnp.maximum(len_ref[slot] - window, 0) // page_size
        last = (len_ref[slot] + page_size - 1) // page_size
        return first, jnp.minimum(last - first, ring)

    def copies(slot, block, buf, start):
        """Start, or wait for, the copies of one block of `slot`
        into buffer `buf`: only the pages the walk covers, in runs of
        `run_pages` while they last and then page by page."""
        first, pages = walk(slot)
        at = block * per_block
        count = jnp.clip(pages - at, 0, per_block)
        entry0 = first + at
        if window is not None:
            entry0 = entry0 % ring  # logical page p at entry p % R

        def page(i, carry=None):
            entry = entry0 + i
            if window is not None:
                entry = jnp.where(entry >= ring, entry - ring, entry)
            src = (layer_ref[0], table_ref[slot, entry])
            for hbm, vmem in leaves:
                copy = pltpu.make_async_copy(
                    hbm.at[src], vmem.at[buf, i], sems.at[buf]
                )
                copy.start() if start else copy.wait()
            return carry

        def run(r, carry):
            if start:
                # unrolled: the scalar core overlaps the pages' table
                # reads and address arithmetic
                for i in range(run_pages):
                    page(r * run_pages + i)
                return carry
            # a semaphore counts bytes: one wait a leaf for the run's
            # pages (the source only gives the size)
            for _, vmem in leaves:
                dst = vmem.at[buf, pl.ds(r * run_pages, run_pages)]
                pltpu.make_async_copy(dst, dst, sems.at[buf]).wait()
            return carry

        runs = count // run_pages
        jax.lax.fori_loop(0, runs, run, 0)
        jax.lax.fori_loop(runs * run_pages, count, page, 0)

    @pl.when(b == 0)
    def _first():
        # a block's unfilled tail is masked, but 0 x NaN is NaN: the
        # buffers only ever hold zeros or cells of the pool
        for _, vmem in leaves:
            vmem[...] = jnp.zeros_like(vmem)
        turn[0] = 0

    first, pages = walk(b)
    blocks = (pages + per_block - 1) // per_block
    length = reach = len_ref[b]
    if carried is not None:
        # the cells a query row sees, [H, 1]
        row = jax.lax.broadcasted_iota(jnp.int32, (kv * n_rep, 1), 0)
        reach = length - jnp.where(
            row % n_rep < carried[0], carried[1], 0)
    buf0 = turn[0]
    # nobody has started the very first block; and a slot with no
    # block starts the next slot's first in its stead
    ahead = jnp.where((b == 0) & (blocks > 0), b, b + 1)

    @pl.when(((b == 0) | (blocks == 0)) & (ahead < slots))
    def _ahead():
        copies(ahead, 0, buf0, True)

    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)

    def block(j, carry):
        cur = (buf0 + j) % 2
        # the next block's copies, or the next SLOT's first block's
        # after this slot's last, before this block's are waited for
        more = j + 1 < blocks

        @pl.when(more | (b + 1 < slots))
        def _next():
            copies(
                jnp.where(more, b, b + 1), jnp.where(more, j + 1, 0),
                1 - cur, True,
            )

        copies(b, j, cur, False)
        at = (first + j * per_block) * page_size
        pos = at + jax.lax.broadcasted_iota(jnp.int32, (1, cells), 1)
        live = pos < reach
        if window is not None:
            # the oldest page's cells that have left the window
            live = live & (pos >= length - window)
        dtype = q_ref.dtype
        if latent is not None:
            # one block of rows, read once: keys as they lie, values
            # their first `latent` columns (whole lane tiles)
            rows = k_buf.at[cur].reshape(cells, k_buf.shape[-1])[...]
            k_rows = v_rows = None
        else:
            k_rows, v_rows = (
                _head_rows(x.at[cur].reshape((cells,) + x.shape[3:]))
                for x in (k_buf, v_buf)
            )
        k_scale = v_scale = None
        if scales:
            # an int8 block's scales [cells, KV], heads on lanes; a
            # dead cell's is dropped, so that whatever it holds meets
            # a zero
            dead = at + jax.lax.broadcasted_iota(
                jnp.int32, (cells, 1), 0) >= length
            k_scale, v_scale = (
                jnp.where(
                    dead, 0.0,
                    x[0, pl.ds(j * cells, cells), :].astype(jnp.float32),
                )
                for x in scales
            )

        def operand(rows, cell_scale, g):
            x = rows(g)
            if cell_scale is not None:
                # exact in f32, rounded once to the query's dtype
                x = x * cell_scale[:, g:g + 1]
            return x.astype(dtype)

        for g in range(kv):
            s_scr[g * n_rep:(g + 1) * n_rep] = jax.lax.dot_general(
                q_ref[0, g],
                rows if latent is not None
                else operand(k_rows, k_scale, g),
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        s = jnp.where(live, s_scr[...] * scale, NEG_INF)   # [H, cells]
        m_prev = m_scr[:, :1]                               # [H, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = alpha * l_scr[:, :1] + jnp.sum(p, axis=1, keepdims=True)
        s_scr[...] = p
        for g in range(kv):
            # probabilities rounded to the query's dtype before the
            # value product, as `_reference` rounds them
            pv_scr[g * n_rep:(g + 1) * n_rep] = jnp.dot(
                s_scr[g * n_rep:(g + 1) * n_rep].astype(dtype),
                rows[:, :latent] if latent is not None
                else operand(v_rows, v_scale, g),
                preferred_element_type=jnp.float32,
            )
        acc_scr[...] = acc_scr[...] * alpha + pv_scr[...]
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)
        return carry

    jax.lax.fori_loop(0, blocks, block, 0)
    turn[0] = (buf0 + blocks) % 2
    l = l_scr[:, :1]
    o_ref[0] = (acc_scr[...] / jnp.where(l == 0.0, 1.0, l)).astype(
        o_ref.dtype
    )


def block_rows(q, kv: int):
    """A diffusion block's queries `[B, S, H, hd]` as the walk takes
    them, `[B, S * H, hd]`: with one length a slot, the S queries of a
    K/V head's group are S times the group's query rows (K/V head g
    first, then the position, then the head inside the group)."""
    b, s, h, hd = q.shape
    q = q.reshape(b, s, kv, h // kv, hd)
    return q.transpose(0, 2, 1, 3, 4).reshape(b, s * h, hd)


def _block_rows_back(o, s: int, kv: int):
    """`block_rows`' inverse on the walk's result."""
    b, sh, hd = o.shape
    o = o.reshape(b, kv, s, sh // (s * kv), hd)
    return o.transpose(0, 2, 1, 3, 4).reshape(b, s, sh // s, hd)


@functools.partial(
    jax.jit, static_argnames=("scale", "window", "block", "carried"))
def _kernel(q, pages, layer, table, lengths, scale, window=None,
            block=0, carried=0):
    """q [B, H, hd] → [B, H, hd] over layer `layer` (int32[1]) of the
    stacked pool. `block` > 0: the rows are a diffusion block's
    (`block_rows`), of which the first `carried` of every KV head's
    see `block` fewer cells. (Jitted so that the engine's chunk
    programs, one a chunk length, and a period's layers of one kind
    trace and lower the body once between them: set-up time.) The
    layer index, the page table and lengths ride as scalar-prefetch
    operands; the pool's leaves are handed over whole and stay in HBM
    (`pl.ANY`), so the body's copies dereference (layer[0],
    table[b, p]) and stream the PHYSICAL pages of that layer out of
    the pool — never a sliced or gathered copy. q travels group-major ([B, KV, n_rep,
    hd]): one KV head's query rows are one leading index. An int8
    pool's scales are the one thing gathered (`_walk_scales`: Mosaic
    copies no page whose last dim is not whole lane tiles, and theirs
    is 1)."""
    b, h, hd = q.shape
    page_size, kv = pages["k"].shape[2:4]
    n_rep = h // kv
    per_block = _pages_per_block(pages, table)
    need = _vmem_bytes(pages, table, kv)
    if need > _VMEM_BUDGET:
        raise ValueError(
            f"paged kernel: {need} bytes of block buffers for {kv} KV "
            f"heads, over {_VMEM_BUDGET}: supports() refuses this"
        )
    cells_kv = [pages["k"], pages["v"]]
    if kv == 1:
        # Mosaic pads a lone sub-word head to a whole word and then
        # cannot copy a page of it: the pool goes without the axis
        cells_kv = [x.reshape(x.shape[:3] + (hd,)) for x in cells_kv]
    scales = ()
    if "k_scale" in pages:
        scales = _walk_scales(
            pages, layer, table, lengths, window, per_block
        )
    q_spec = pl.BlockSpec(
        (1, kv, n_rep, hd), lambda bi, lay, tab, lens: (bi, 0, 0, 0)
    )
    kernel = functools.partial(
        _paged_kernel, scale=scale, page_size=page_size,
        per_block=per_block, window=window,
        carried=(carried, block) if carried else None,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b,),
        in_specs=[
            q_spec,
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            tuple(
                pl.BlockSpec(
                    (1,) + x.shape[1:],
                    lambda bi, lay, tab, lens: (bi, 0, 0),
                )
                for x in scales
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, h, hd), lambda bi, lay, tab, lens: (bi, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((2, per_block) + x.shape[2:], x.dtype)
            for x in cells_kv
        ] + [
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((h, _LANES), jnp.float32),        # max
            pltpu.VMEM((h, _LANES), jnp.float32),        # sum
            pltpu.VMEM((h, hd), jnp.float32),            # accumulator
            pltpu.VMEM((h, per_block * page_size), jnp.float32),
            pltpu.VMEM((h, hd), jnp.float32),            # a block's p v
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            # a step starts the next slot's first copies and hands
            # over the buffer's parity: in order, on one core
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=max(
                _VMEM_SCOPED_DEFAULT, need + _VMEM_HEADROOM
            ),
        ),
        interpret=fa._interpret(),
        name="paged_attention_decode" + (
            "_block" if block else "" if window is None else "_window"
        ),
    )(
        layer, table.astype(jnp.int32), lengths.astype(jnp.int32),
        q.reshape(b, kv, n_rep, hd), *cells_kv, scales,
    )


# ---------------------------------------------------------------------------
# the latent variant: one row a token that is both key and value
# ---------------------------------------------------------------------------

# What a block of latent rows may take of a block buffer: 512 cells of
# bf16 at 640 numbers a row. One shared row serves every head, so the
# block is sized from the row and not from a head's share of it.
_LATENT_BLOCK_BYTES = 640 * 1024


def _latent_pages_per_block(pool, table) -> int:
    page_size, width = pool.shape[-2:]
    page = page_size * width * max(2, pool.dtype.itemsize)
    return min(max(1, _LATENT_BLOCK_BYTES // page), table.shape[1])


def supports_latent(q, pages: Dict, table, rank: int) -> bool:
    """Whether the latent variant of the kernel handles these shapes:
    `q` [B, H, W] the absorbed queries, `pages` {"ckv": [.., n_pages,
    page_size, W]}, `rank` the latent's width (the value columns).
    Mosaic copies a page whose rows are whole 128-lane tiles and
    slices the value columns at a tile's edge; the heads are the
    products' rows, in sublane tiles."""
    pool = pages["ckv"]
    b, h, w = q.shape
    page_size = pool.shape[-2]
    if pool.shape[-1] != w or not 0 < rank <= w:
        return False
    if not fa._interpret() and (w % _LANES or rank % _LANES):
        return False
    if h % _SUBLANES or page_size < 8:
        return False
    if pool.dtype.itemsize not in (2, 4) or q.dtype != pool.dtype:
        return False
    return table.ndim == 2 and table.shape[0] == b


def use_kernel_latent(q, pages: Dict, table, rank: int) -> bool:
    """`use_kernel` for the latent variant (one chip: a latent pool
    is not sharded over heads, it has none)."""
    if jax.default_backend() != "tpu" and not fa.force_kernels():
        return False
    return supports_latent(q, pages, table, rank)


def latent_scores_and_sums(qc, rows, q_positions, scale, rank: int):
    """The absorbed form of latent attention on a dense view: qc
    [B, S, H, W] (a head's query with its key matrix multiplied in)
    against rows [B, M, W], query at position p seeing cells j <= p;
    the probabilities against the rows' first `rank` columns ->
    [B, S, H, rank]. float32 scores and softmax, the probabilities
    rounded to the query's dtype before the sums (as `_reference`)."""
    scores = jnp.einsum(
        "bshw,bmw->bhsm", qc, rows, preferred_element_type=jnp.float32
    ) * scale
    cols = jnp.arange(rows.shape[1])[None, None, None, :]
    live = cols <= q_positions[:, None, :, None]
    p = jax.nn.softmax(
        jnp.where(live, scores, -jnp.inf), axis=-1
    ).astype(qc.dtype)
    return jnp.einsum("bhsm,bmc->bshc", p, rows[..., :rank])


@functools.partial(jax.jit, static_argnames=("scale", "rank"))
def _latent_kernel(q, pool, layer, table, lengths, scale, rank):
    """q [B, H, W] -> [B, H, rank] over layer `layer` (int32[1]) of
    the stacked latent pool `[L, n_pages, page_size, W]`: `_kernel`'s
    walk (scalar-prefetched layer, table and lengths; the pool whole
    in HBM; a block of pages copied a grid step, two buffers) with
    one leaf and one KV head that every query head shares."""
    b, h, w = q.shape
    page_size = pool.shape[2]
    per_block = _latent_pages_per_block(pool, table)
    index = lambda bi, lay, tab, lens: (bi, 0, 0, 0)  # noqa: E731
    kernel = functools.partial(
        _paged_kernel, scale=scale, page_size=page_size,
        per_block=per_block, latent=rank,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, 1, h, w), index),
            pl.BlockSpec(memory_space=pl.ANY),
            (),   # no second leaf
            (),   # no scales
        ],
        out_specs=pl.BlockSpec(
            (1, h, rank), lambda bi, lay, tab, lens: (bi, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((2, per_block, page_size, w), pool.dtype),
            (),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((h, _LANES), jnp.float32),        # max
            pltpu.VMEM((h, _LANES), jnp.float32),        # sum
            pltpu.VMEM((h, rank), jnp.float32),          # accumulator
            pltpu.VMEM((h, per_block * page_size), jnp.float32),
            pltpu.VMEM((h, rank), jnp.float32),          # a block's p v
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, rank), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_SCOPED_DEFAULT + _VMEM_HEADROOM,
        ),
        interpret=fa._interpret(),
        name="paged_attention_decode_latent",
    )(
        layer, table.astype(jnp.int32), lengths.astype(jnp.int32),
        q.reshape(b, 1, h, w), pool, (), (),
    )


def latent_paged_attention(
    q: jax.Array,           # [B, H, W]: one absorbed query a head
    pages: Dict[str, jax.Array],   # {"ckv": [L, n_pages, page_size, W]}
    table: jax.Array,       # [B, P]
    lengths: jax.Array,     # [B] valid cells per row (query at len-1)
    scale: float,
    rank: int,
    layer=None,
    impl: str = "auto",
) -> jax.Array:
    """Single-query latent attention over the paged latent pool ->
    the heads' sums of latents [B, H, rank]. `pages` is the stacked
    pool with `layer` the (traced) index, or one layer's with `layer`
    None. impl "reference": `latent_scores_and_sums` on the gathered
    view; "kernel": the latent variant of the Pallas walk; "auto":
    the kernel where `use_kernel_latent` passes."""
    if impl not in ("reference", "kernel", "auto"):
        raise ValueError(f"unknown impl {impl!r}")
    if impl == "reference" or (
        impl == "auto" and not use_kernel_latent(q, pages, table, rank)
    ):
        rows = gather_pages(pages, table, layer)["ckv"]
        return latent_scores_and_sums(
            q[:, None], rows, (lengths - 1)[:, None], scale, rank
        )[:, 0]
    pages, layer = _stacked(pages, layer)
    return _latent_kernel(
        q, pages["ckv"], layer, table, lengths, scale, rank
    )


def _walk_scales(pages, layer, table, lengths, window, per_block):
    """An int8 pool's (K, V) scales of each slot's walk, [B, cells,
    KV] with the cells in the walk's order (the ring's logical order
    under a window) and padded to whole blocks."""
    page_size = pages["k"].shape[2]
    if window is not None:
        _, table = ring_view(table, lengths, window, page_size)
    view = gather_pages(
        {n: pages[n] for n in ("k_scale", "v_scale")}, table, layer
    )
    short = -table.shape[1] % per_block * page_size
    return tuple(
        jnp.pad(view[n][..., 0], ((0, 0), (0, short), (0, 0)))
        for n in ("k_scale", "v_scale")
    )


def _sharded_kernel(q, pages, layer, table, lengths, scale, mesh,
                    window=None):
    """`_kernel` shard_mapped over the serving mesh's "tp" axis: q
    and the page pool split on their head axes, the layer index, the
    page table and lengths replicated (host-planned — every shard
    walks the same pages, reading only its own KV-head slice of
    them). Attention is per-KV-head local, so the body needs NO
    collectives, and a block's cells are sized from ONE head's rows
    (`_pages_per_block`), so every head's products and partial sums
    are those of tp=1: output is byte-identical to the tp=1 kernel
    chunked by head. Specs come from
    parallel/mesh.py:serving_head_specs, the one layout source."""
    from dlrover_tpu.parallel.mesh import serving_head_specs

    specs = serving_head_specs(mesh)
    rep = specs["replicated"]

    def body(q, pages, layer, table, lengths):
        return _kernel(q, pages, layer, table, lengths, scale, window)

    return fa.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            specs["q1"],
            {name: specs["pool"] for name in pages},
            rep,
            rep,
            rep,
        ),
        out_specs=specs["q1"],
    )(q, pages, layer, table, lengths)


def paged_attention(
    q: jax.Array,           # [B, H, hd] — one decode query per row
    pages: Dict[str, jax.Array],
    table: jax.Array,       # [B, P] physical page ids
    lengths: jax.Array,     # [B] valid cells per row (query at len-1)
    scale: Optional[float] = None,
    impl: str = "auto",
    mesh=None,
    layer=None,
    window: Optional[int] = None,
    block: int = 0,
) -> jax.Array:
    """Single-query attention over paged KV. `pages` is the stacked
    pool (`[L, n_pages, page_size, KV, ...]` leaves) with `layer` the
    (traced) index of the layer to attend over, or one layer's pool
    with `layer` None. impl: "reference" (the dense-bank byte-parity
    formulation over a gathered view), "kernel" (Pallas, pages
    streamed via scalar-prefetched table), or "auto" (kernel when
    `use_kernel` passes, else reference).

    `mesh` (optional serving mesh with a "tp" axis) makes the kernel
    path dispatch shard_mapped over the head axes; the reference path
    needs no wrapper — GSPMD partitions its gather+einsums per head
    on its own.

    `window` (static) makes this a WINDOW layer's attention: `table`
    is then the slot's ring (logical page p at entry p % R), the walk
    starts at the first page that still holds a position inside the
    last `window` positions, and that page's older cells are masked.
    `window=None` is the program as it was, operation for operation.

    `block` (static) > 0: `q` is `[B, block, H, hd]`, ONE diffusion
    block a row, whose queries all see `lengths` cells (the block's
    end), or `[B, 2 * block, H, hd]`: the block before it, CARRIED
    for its keys and values, and then the block. The carried block's
    queries see `lengths - block` cells (their own block's end): a
    second group of rows whose length is one block shorter. Both ride
    the same walk, once, as `S * H` heads over the pool's K/V heads
    (`block_rows`); on the chip the call is named
    `paged_attention_decode_block`, so that a trace tells it apart."""
    if block:
        s = q.shape[1]
        if window is not None or mesh is not None or s not in (
            block, 2 * block
        ):
            raise ValueError(
                "a block of queries has no window, no mesh and "
                f"{block} positions, or {2 * block} with a carried block"
            )
        kv = pages["k"].shape[-2]
        rows = block_rows(q, kv)
        if scale is None:
            scale = float(q.shape[-1]) ** -0.5
        if impl == "reference" or (
            impl == "auto" and not use_kernel(rows, pages, table)
        ):
            if s > block:
                # a length a query row, in `block_rows`' order
                short = block * (jnp.arange(s) < s - block)
                lengths = jnp.broadcast_to(
                    lengths[:, None, None, None]
                    - short[None, None, :, None],
                    (q.shape[0], kv, s, q.shape[2] // kv),
                ).reshape(rows.shape[:2])
            out = _reference(rows, pages, table, lengths, scale, layer)
        else:
            pages, layer = _stacked(pages, layer)
            out = _kernel(
                rows, pages, layer, table, lengths, scale, block=block,
                carried=(s - block) * (q.shape[2] // kv),
            )
        return _block_rows_back(out, s, kv)
    if scale is None:
        scale = float(q.shape[-1]) ** -0.5
    from dlrover_tpu.parallel.mesh import serving_mesh_tp

    tp = serving_mesh_tp(mesh)
    if impl not in ("reference", "kernel", "auto"):
        raise ValueError(f"unknown impl {impl!r}")
    if impl == "reference" or (
        impl == "auto" and not use_kernel(q, pages, table, tp=tp)
    ):
        return _reference(
            q, pages, table, lengths, scale, layer, window
        )
    pages, layer = _stacked(pages, layer)
    if tp > 1:
        return _sharded_kernel(
            q, pages, layer, table, lengths, scale, mesh, window
        )
    return _kernel(q, pages, layer, table, lengths, scale, window)
