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
  dense view: the layer index and the page table ride in as
  SCALAR-PREFETCH operands (pltpu.PrefetchScalarGridSpec), so the
  BlockSpec index map resolves (layer, page id) before the body runs
  and the pipeline streams pages HBM→VMEM directly. int8 pools
  dequantize inside the inner loop (fused into the score/accumulate
  dots — the cache reads stay int8 in HBM, halving decode's
  memory-bound byte traffic). interpret=True on CPU keeps tier-1
  runnable.
- `impl="auto"`: the kernel on a TPU when `supports()` passes, else
  the reference (a decision the engine logs once and reports as
  `kernel_path`). CPU tier-1 therefore runs the reference —
  which is what makes the engine parity sweep deterministic — unless
  DLROVER_TPU_FORCE_KERNELS=1 (the shard_map parity tests / bench)
  forces the interpret-mode kernel. Under a serving mesh (tp > 1)
  the kernel dispatches shard_mapped over the "tp" axis: each shard
  streams the pages of its own KV-head slice (no collectives).

The single-query shape gate reuses ops/flash_attention.supports()
(fixed to accept q_len == 1 decode shapes): head_dim lane/tile
constraints are identical between the two kernels.
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
    or stacked: the gate reads the last four dims), `table` the
    [B, P] page table. Reuses flash_attention's q_len==1 gate for the
    head_dim constraints, then checks the page axis.

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
    # flash's single-query gate owns the d / GQA lane constraints
    # (probed with the per-shard head counts); the key-side
    # "sequence" a page kernel streams is one page long
    q_probe = jax.ShapeDtypeStruct((b, 1, h, d), q.dtype)
    k_probe = jax.ShapeDtypeStruct((b, 1, kv, d), q.dtype)
    if not fa.supports(q_probe, k_probe, block_q=1, block_k=1):
        return False
    # a page is the kernel's key block and a major dim of it (the
    # block's last two dims are the pool's own KV x hd), so any page
    # size lowers — but below 8 cells the grid overhead swamps the
    # work
    if page_size < 8:
        return False
    if table.ndim != 2 or table.shape[0] != b:
        return False
    return True


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
    single decode query per row at position lengths-1."""
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


def _paged_kernel(layer_ref, table_ref, len_ref,  # scalar prefetch
                  q_ref, k_ref, v_ref, o_ref,
                  m_scr, l_scr, acc_scr,
                  *, scale, page_size, num_pages, n_rep, quant,
                  window=None):
    """Grid (B, P): one invocation attends query row b — every KV
    head of it — over physical page table[b, p] of layer layer[0]
    (the index map's business: the body never reads `layer_ref`).
    The page block is the pool's own [page, KV, hd] slab: its last
    two dims ARE the array's, which Mosaic's (8, 128) block rule
    accepts at any head count (a block of ONE head on the KV axis,
    second to last, is refused). KV heads sit on sublanes and head_dim on lanes, so the
    score is a multiply + lane reduction per rep-group member and the
    value sum a reduction over the page's cells — VPU/XLU work, which
    a one-row decode query cannot feed the MXU with anyway, and
    arithmetic that never mixes heads (the tp byte-parity argument).
    Online softmax in VMEM scratch across the page axis (sequential
    'arbitrary' dim); pages past the row's valid length are skipped
    whole."""
    bi = pl.program_id(0)
    pi = pl.program_id(1)

    @pl.when(pi == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = len_ref[bi]
    # a window layer walks its ring from the first logical page that
    # still holds a position inside the window (the index map starts
    # there too); `window=None` leaves the program as it was
    page = pi  # the logical page this invocation attends over
    if window is not None:
        page = pi + jnp.maximum(length - window, 0) // page_size

    @pl.when(page * page_size < length)
    def _compute():
        k = k_ref[0][0, 0].astype(jnp.float32)     # [page, KV, hd]
        v = v_ref[0][0, 0].astype(jnp.float32)
        if quant:
            # int8 cells, [page, KV, 1] scales: the dequant multiply
            # runs on the VMEM-resident block — HBM traffic stays int8
            k = k * k_ref[1][0][0, 0].astype(jnp.float32)
            v = v * v_ref[1][0][0, 0].astype(jnp.float32)
        cells = page * page_size + jax.lax.broadcasted_iota(
            jnp.int32, k.shape[:2] + (1,), 0
        )
        live = cells < length                      # [page, KV, 1]
        if window is not None:
            # the oldest page's cells that have left the window
            live = live & (cells >= length - window)
        for r in range(n_rep):
            q = q_ref[0, r].astype(jnp.float32)    # [KV, hd]
            s = jnp.sum(k * q[None], axis=2, keepdims=True) * scale
            s = jnp.where(live, s, NEG_INF)        # [page, KV, 1]
            m_prev = m_scr[r][:, :1]               # [KV, 1]
            l_prev = l_scr[r][:, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=0))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new[None])
            l_new = alpha * l_prev + jnp.sum(p, axis=0)
            acc_scr[r] = acc_scr[r] * alpha + jnp.sum(p * v, axis=0)
            m_scr[r] = jnp.broadcast_to(m_new, m_scr.shape[1:])
            l_scr[r] = jnp.broadcast_to(l_new, l_scr.shape[1:])

    @pl.when(pi == num_pages - 1)
    def _finalize():
        for r in range(n_rep):
            l = l_scr[r][:, :1]
            l = jnp.where(l == 0.0, 1.0, l)
            o_ref[0, r] = (acc_scr[r] / l).astype(o_ref.dtype)


def _kernel(q, pages, layer, table, lengths, scale, window=None):
    """q [B, H, hd] → [B, H, hd] over layer `layer` (int32[1]) of the
    stacked pool. The layer index, the page table and lengths ride as
    scalar-prefetch operands so the k/v BlockSpec index maps can
    dereference (layer[0], table[b, p]) — the pipeline then streams
    the PHYSICAL pages of that layer out of the whole pool, never a
    sliced or gathered copy. q travels rep-major ([B, n_rep, KV, hd])
    so one rep-group member is a [KV, hd] tile laid out like a page
    cell."""
    b, h, hd = q.shape
    page_size, kv = pages["k"].shape[2:4]
    n_rep = h // kv
    num_pages = table.shape[1]
    quant = "k_scale" in pages
    qg = q.reshape(b, kv, n_rep, hd).swapaxes(1, 2)

    def q_map(bi, pi, lay, tab, lens):
        return (bi, 0, 0, 0)

    if window is None:
        def kv_map(bi, pi, lay, tab, lens):
            return (lay[0], tab[bi, pi], 0, 0, 0)
    else:
        def kv_map(bi, pi, lay, tab, lens):
            # ring entries past the last written page are not read
            # again: they map to the last one (an unchanged block
            # index starts no new copy)
            first = jnp.maximum(lens[bi] - window, 0) // page_size
            page = jnp.minimum(
                first + pi, jnp.maximum(lens[bi] - 1, 0) // page_size
            )
            return (lay[0], tab[bi, page % num_pages], 0, 0, 0)

    q_spec = pl.BlockSpec((1, n_rep, kv, hd), q_map)
    kv_spec = pl.BlockSpec((1, 1, page_size, kv, hd), kv_map)
    sc_spec = pl.BlockSpec((1, 1, page_size, kv, 1), kv_map)
    if quant:
        in_specs = [q_spec, (kv_spec, (sc_spec,)), (kv_spec, (sc_spec,))]
        operands = [
            qg,
            (pages["k"], (pages["k_scale"],)),
            (pages["v"], (pages["v_scale"],)),
        ]
    else:
        in_specs = [q_spec, (kv_spec,), (kv_spec,)]
        operands = [qg, (pages["k"],), (pages["v"],)]

    kernel = functools.partial(
        _paged_kernel, scale=scale, page_size=page_size,
        num_pages=num_pages, n_rep=n_rep, quant=quant, window=window,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, num_pages),
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((n_rep, kv, 128), jnp.float32),
            pltpu.VMEM((n_rep, kv, 128), jnp.float32),
            pltpu.VMEM((n_rep, kv, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, n_rep, kv, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=fa._interpret(),
        name=(
            "paged_attention_decode" if window is None
            else "paged_attention_decode_window"
        ),
    )(
        layer, table.astype(jnp.int32), lengths.astype(jnp.int32),
        *operands,
    )
    return out.swapaxes(1, 2).reshape(b, h, hd)


def _sharded_kernel(q, pages, layer, table, lengths, scale, mesh,
                    window=None):
    """`_kernel` shard_mapped over the serving mesh's "tp" axis: q
    and the page pool split on their head axes, the layer index, the
    page table and lengths replicated (host-planned — every shard
    walks the same pages, reading only its own KV-head slice of
    them). Attention is per-KV-head local, so the body needs NO
    collectives, and the kernel's grid/scratch shapes depend only on
    per-shard head counts: output is byte-identical to the tp=1
    kernel chunked by head. Specs come from parallel/mesh.py:serving_head_specs, the
    one layout source."""
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
    `window=None` is the program as it was, operation for operation."""
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
