"""Quantization ops: Pallas int8 block kernels + compressed collectives.

Reference parity: ATorch's CUDA quantization suite
(atorch/ops/csrc/quantization/{quantize.cu,dequantize.cu,quant_reduce.cu,
swizzled_quantize.cu}) — block-wise int8/fp8 quantize/dequantize and a
quantized gradient reduction used to halve NVLink/IB bytes in ZeRO.

TPU design: quantize/dequantize are Pallas kernels (VPU elementwise +
per-block absmax reduction, tiles staged HBM→VMEM); the quantized
reduction is a ring reduce-scatter under `shard_map` whose per-hop
payload is int8 blocks + f32 scales — `ppermute` moves 1/4 the bytes of
an f32 ring over ICI, and dequant-accumulate runs in f32 on the VPU.
CPU backend runs the same kernels in interpret mode (tests)."""

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from dlrover_tpu.common.log import warning_once

shard_map = functools.partial(jax.shard_map, check_vma=False)

INT8_MAX = 127.0
DEFAULT_BLOCK = 256


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


# ---------------------------------------------------------------------------
# pallas kernels
# ---------------------------------------------------------------------------


def _quant_kernel(x_ref, q_ref, s_ref):
    x = x_ref[...].astype(jnp.float32)            # [bm, block]
    amax = jnp.max(jnp.abs(x), axis=1)            # [bm]
    scale = jnp.where(amax > 0, amax / INT8_MAX, 1.0)
    q = jnp.clip(
        jnp.round(x / scale[:, None]), -INT8_MAX, INT8_MAX
    )
    q_ref[...] = q.astype(jnp.int8)
    s_ref[...] = scale[:, None]


def _dequant_kernel(q_ref, s_ref, x_ref, *, out_dtype):
    q = q_ref[...].astype(jnp.float32)
    x_ref[...] = (q * s_ref[...]).astype(out_dtype)


# Mosaic requires every block's last two dims be (8,128)-divisible OR
# equal to the whole array's dims. The natural [m, n]-tiled layout
# gives the scales a (bm, 1) block over [m, n/block] — illegal on real
# TPU (it only ever lowered in CPU interpret mode). So the kernels run
# in ROW FORM: x reshaped to [rows, block] (one quant block per row),
# scales [rows, 1] — last dim EQUAL to the array's, q/x blocks
# (bm, block) with block a multiple of 128. The reshapes and the
# row-count pad to a bm multiple happen outside pallas in XLA, where
# they're layout no-ops.
_ROW_BM = 1024  # bm*block*4B = 1 MB of VMEM per instance at block 256


def _row_tile(rows: int) -> int:
    """Row-block size for `rows` total rows: small inputs get ONE grid
    instance padded only to the 8-row sublane multiple (padding a
    16-row layernorm param to 1024 rows would be ~64x wasted work on
    every quantized-optimizer step); large inputs tile at _ROW_BM."""
    if rows >= _ROW_BM:
        return _ROW_BM
    return rows + ((-rows) % 8)


def _row_pad(rows2d: jax.Array, bm: int) -> Tuple[jax.Array, int]:
    pad = (-rows2d.shape[0]) % bm
    if pad:
        rows2d = jnp.pad(rows2d, ((0, pad), (0, 0)))
    return rows2d, pad


def quantize_int8(
    x: jax.Array, block: int = DEFAULT_BLOCK, block_m: int = 256
) -> Tuple[jax.Array, jax.Array]:
    """Symmetric per-block int8 quantization along the last dim.

    x: [m, n] with n % block == 0 → (q int8 [m, n], scales f32 [m, n/block]).
    `block_m` is accepted for API compat; tiling is chosen internally.
    """
    m, n = x.shape
    assert n % block == 0, (n, block)
    rows = m * (n // block)
    bm = _row_tile(rows)
    xr, pad = _row_pad(x.reshape(rows, block), bm)
    q, s = pl.pallas_call(
        _quant_kernel,
        grid=(xr.shape[0] // bm,),
        in_specs=[pl.BlockSpec((bm, block), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((bm, block), lambda i: (i, 0)),
            pl.BlockSpec((bm, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((xr.shape[0], block), jnp.int8),
            jax.ShapeDtypeStruct((xr.shape[0], 1), jnp.float32),
        ],
        interpret=_interpret(),
    )(xr)
    if pad:
        q, s = q[:rows], s[:rows]
    return q.reshape(m, n), s.reshape(m, n // block)


def dequantize_int8(
    q: jax.Array,
    scales: jax.Array,
    out_dtype=jnp.float32,
    block_m: int = 256,
) -> jax.Array:
    m, n = q.shape
    block = n // scales.shape[1]
    rows = m * (n // block)
    bm = _row_tile(rows)
    qr, pad = _row_pad(q.reshape(rows, block), bm)
    sr, _ = _row_pad(scales.reshape(rows, 1), bm)
    x = pl.pallas_call(
        functools.partial(_dequant_kernel, out_dtype=out_dtype),
        grid=(qr.shape[0] // bm,),
        in_specs=[
            pl.BlockSpec((bm, block), lambda i: (i, 0)),
            pl.BlockSpec((bm, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bm, block), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((qr.shape[0], block), out_dtype),
        interpret=_interpret(),
    )(qr, sr)
    if pad:
        x = x[:rows]
    return x.reshape(m, n)


def quantize_any(x: jax.Array, block: int = DEFAULT_BLOCK):
    """Quantize an arbitrary-shaped tensor (flattened + padded to block)."""
    flat = x.reshape(-1)
    pad = (-flat.size) % block
    if pad:
        flat = jnp.pad(flat, (0, pad))
    q, s = quantize_int8(flat.reshape(1, -1), block=block, block_m=1)
    return q, s, x.shape, pad


def dequantize_any(q, s, shape, pad, out_dtype=jnp.float32):
    flat = dequantize_int8(q, s, out_dtype=out_dtype, block_m=1).reshape(-1)
    if pad:
        flat = flat[:-pad]
    return flat.reshape(shape)


def stochastic_round_int8(
    x: jax.Array, key: jax.Array, block: int = DEFAULT_BLOCK
) -> Tuple[jax.Array, jax.Array]:
    """Unbiased int8 quantization (E[dequant] == x): floor + bernoulli on
    the fractional part. Used for gradient compression where rounding
    bias would accumulate across steps (quantization_optimizer.cu's
    stochastic mode)."""
    m, n = x.shape
    amax = jnp.max(
        jnp.abs(x.reshape(m, n // block, block)), axis=2
    ).astype(jnp.float32)
    scale = jnp.where(amax > 0, amax / INT8_MAX, 1.0)
    xs = x.astype(jnp.float32) / jnp.repeat(scale, block, axis=1)
    lo = jnp.floor(xs)
    frac = xs - lo
    up = jax.random.uniform(key, x.shape) < frac
    q = jnp.clip(lo + up.astype(jnp.float32), -INT8_MAX, INT8_MAX)
    return q.astype(jnp.int8), scale


# ---------------------------------------------------------------------------
# int8 weight-quantized matmul (serving decode path)
# ---------------------------------------------------------------------------
#
# Decode is weight-HBM-bandwidth bound: every step streams the full
# projection/MLP/unembed weights through the MXU once. Storing them as
# per-block int8 + f32 scales reads ~0.27x the f32 bytes (int8 values
# + 4B/block scales), and the dequant runs on the VPU between the
# HBM->VMEM stage and the MXU dot — bandwidth, not FLOPs, pays.
#
# Layout: OUTPUT-MAJOR, blocks along the CONTRACTION dim. A weight
# w [K, O] (activations contract K) is stored transposed as
# q8 [O, K] int8 with s8 [O, K/block] f32 — one scale per contiguous
# K-block of one output row. Two properties fall out:
#   * tp column-sharding splits O, never K, so a shard boundary can
#     never straddle a quant block — resharding at a new tp (elastic
#     resize) moves q8+s8 as-is, NO requantize;
#   * the contraction dim is never split, preserving the serving
#     byte-parity argument (models/decode.py): per-output-element
#     reduction order is identical at every tp.


@jax.tree_util.register_pytree_with_keys_class
class QuantizedWeight:
    """Per-block int8 weight in output-major (transposed) layout.

    q8: int8 ``[..., O, K]`` (leading dims: stacked layers), blocks of
    size `block` along the last (contraction) dim; s8: f32
    ``[..., O, K/block]``. Registered as a keyed pytree node so the
    pair flows through ``lax.scan`` (per-layer slicing of the leading
    axis), ``shard_tree`` (children path like ``layers/wq/q8`` match
    the serving placement rules), jit, and device_put like any other
    param subtree."""

    __slots__ = ("q8", "s8", "block")

    def __init__(self, q8, s8, block: int):
        self.q8 = q8
        self.s8 = s8
        self.block = int(block)

    def tree_flatten_with_keys(self):
        return (
            (
                (jax.tree_util.GetAttrKey("q8"), self.q8),
                (jax.tree_util.GetAttrKey("s8"), self.s8),
            ),
            self.block,
        )

    @classmethod
    def tree_unflatten(cls, aux, children):
        q8, s8 = children
        return cls(q8, s8, aux)

    @property
    def shape(self):
        """Shape of the DENSE weight this stands in for ([..., K, O])."""
        *lead, o, k = self.q8.shape
        return tuple(lead) + (k, o)

    def __repr__(self):  # pragma: no cover - debugging aid
        return (
            f"QuantizedWeight(q8={getattr(self.q8, 'shape', None)}, "
            f"s8={getattr(self.s8, 'shape', None)}, "
            f"block={self.block})"
        )


def weight_quant_block(k: int, cap: int = DEFAULT_BLOCK) -> int:
    """Quant block for a contraction dim of size `k`: the largest
    power-of-two divisor of k, capped at `cap`. Returns 0 when k has
    no even divisor >= 8 (leave such a weight dense rather than
    per-element scales). Real-TPU Mosaic wants >= 128; tiny test
    configs (k=64) only ever run the interpret/reference paths, same
    convention as the quantize kernels above."""
    b = 1
    while b < cap and k % (b * 2) == 0:
        b *= 2
    return b if b >= 8 else 0


# VMEM the fused kernel may plan for, in the two terms that grow with
# the operands (both blocks hold the WHOLE contraction dim). Not a
# model of Mosaic's allocator: bounds the v5e compiler accepted at
# every shape probed under its 16 MiB scoped-VMEM default (K from
# 4096 to 14336, bf16 and f32 activations) with room to spare —
# tests/test_tpu_compile.py holds the 7B shapes to it.
_DQMM_W_ELEMS = 3 * 1024 * 1024   # int8 weight block: bo * K
_DQMM_X_BYTES = 2 * 1024 * 1024   # activation block: bt * K * itemsize


def _dqmm_out_tile(k: int, o: int) -> int:
    """Output-dim tile `bo` for an [O, K] weight: the largest lane
    multiple (256, then 128) that divides O and keeps the [bo, K]
    int8 block — and the dequant staging Mosaic derives from it —
    inside the budget; O itself for a weight smaller than one tile.
    0 when nothing fits (K too long for a whole-K block): the caller
    takes the XLA reference, visibly."""
    for bo in (256, 128):
        if o % bo == 0 and bo * k <= _DQMM_W_ELEMS:
            return bo
    if o < 256 and o * k <= _DQMM_W_ELEMS:
        return o
    return 0


def _dqmm_row_tile(t: int, k: int, itemsize: int) -> int:
    """Row tile `bt`: the largest power of two (>= 8 sublanes) whose
    [bt, K] activation block fits the budget, or all `t` rows when
    they already do — so VMEM use stops growing with prompt length."""
    bt = 8
    while bt * 2 * k * itemsize <= _DQMM_X_BYTES:
        bt *= 2
    return t if t <= bt else bt


def use_quant_matmul_kernel(tp: int = 1, w=None) -> bool:
    """Kernel-vs-reference gate for the fused dequant matmul, the
    KERNEL-001 shape shared with attention dispatch: the Pallas path
    is dispatchable on TPU or when force_kernels() opts the
    interpret-mode kernel in on CPU. tp > 1 stays on the XLA
    reference — the weights are GSPMD-sharded over the output axis
    and XLA partitions dequant+dot natively (per-shard pallas
    dispatch for sharded weights is a real-TPU follow-up). With a
    QuantizedWeight `w` the gate also asks whether a block of it fits
    VMEM: on TPU a weight that does not is a logged decision, never a
    compile error."""
    from dlrover_tpu.ops.flash_attention import force_kernels

    if tp > 1:
        return False
    if jax.default_backend() != "tpu":
        return force_kernels()
    if w is None:
        return True
    o, k = w.q8.shape[-2:]
    if _dqmm_out_tile(k, o):
        return True
    warning_once(
        "int8 matmul [K=%d -> O=%d]: no whole-K block fits the fused "
        "kernel's VMEM budget; this weight takes the XLA "
        "dequant-then-dot reference", k, o,
    )
    return False


def _dq_weight(q8: jax.Array, s8: jax.Array, block: int, dtype):
    """Dequantize one output-major weight [O, K] to `dtype`. The ONE
    dequant formulation both the kernel body and the XLA reference
    run — broadcast scales over their block, multiply in f32, cast —
    so the two paths stay byte-identical on the same backend."""
    o, k = q8.shape
    g = s8.shape[-1]
    s = jnp.broadcast_to(s8[:, :, None], (o, g, block)).reshape(o, k)
    return (q8.astype(jnp.float32) * s).astype(dtype)


def _dqmm_dot(x: jax.Array, wt: jax.Array) -> jax.Array:
    """x [T, K] . wt [O, K] -> [T, O], f32 accumulation on the MXU."""
    return jax.lax.dot_general(
        x,
        wt,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def _dqmm_kernel(x_ref, q_ref, s_ref, o_ref, *, block):
    wt = _dq_weight(q_ref[...], s_ref[...], block, x_ref.dtype)
    o_ref[...] = _dqmm_dot(x_ref[...], wt).astype(o_ref.dtype)


def quantized_matmul_kernel(x: jax.Array, w: QuantizedWeight):
    """Pallas fused dequant-matmul: the grid tiles rows and the
    output dim, every instance holds the full K (one pass, whole-row
    reduction — no partial sums to reassociate), and the int8 block +
    its scales dequantize in VMEM right before the dot. Tiles come
    from the shapes (`_dqmm_row_tile`, `_dqmm_out_tile`); a ragged
    last row tile is Pallas edge padding, harmless because rows never
    mix. In interpret mode the grid collapses to one instance, so the
    body runs the exact op sequence of `quantized_matmul_reference`
    — that is the byte-parity oracle the tests and bench phase lock."""
    t, k = x.shape
    o = w.q8.shape[0]
    if _interpret():
        bt, bo = t, o
    else:
        bt = _dqmm_row_tile(t, k, x.dtype.itemsize)
        bo = _dqmm_out_tile(k, o)
        if not bo:
            raise ValueError(
                f"int8 matmul kernel: no VMEM-sized block for a "
                f"[{o}, {k}] weight; use the reference path"
            )
    return pl.pallas_call(
        functools.partial(_dqmm_kernel, block=w.block),
        grid=(pl.cdiv(t, bt), o // bo),
        in_specs=[
            pl.BlockSpec((bt, k), lambda i, j: (i, 0)),
            pl.BlockSpec((bo, k), lambda i, j: (j, 0)),
            pl.BlockSpec((bo, w.s8.shape[-1]), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bt, bo), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((t, o), x.dtype),
        interpret=_interpret(),
        name="int8_dequant_matmul",
    )(x, w.q8, w.s8)


def quantized_matmul_reference(x: jax.Array, w: QuantizedWeight):
    """XLA reference formulation: dequantize the whole weight, then
    one dot. Same `_dq_weight` + `_dqmm_dot` sequence as the kernel
    body; under tp > 1 XLA partitions it over the output axis with
    zero collectives (O is the sharded dim, K is whole)."""
    wt = _dq_weight(w.q8, w.s8, w.block, x.dtype)
    return _dqmm_dot(x, wt).astype(x.dtype)


def quantized_matmul(
    x: jax.Array, w: QuantizedWeight, tp: int = 1
) -> jax.Array:
    """Dequant-fused ``x @ dense(w)`` for an output-major quantized
    weight; x may carry leading batch dims ([..., K] -> [..., O])."""
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    if use_quant_matmul_kernel(tp=tp, w=w):
        y = quantized_matmul_kernel(x2, w)
    else:
        y = quantized_matmul_reference(x2, w)
    return y.reshape(*lead, y.shape[-1])


def matmul_any(x: jax.Array, w, tp: int = 1) -> jax.Array:
    """The models' one matmul dispatch: dense weights take the exact
    legacy primitive (``x @ w`` — weight_quant="none" stays
    byte-identical by construction), QuantizedWeight takes the fused
    dequant path."""
    if isinstance(w, QuantizedWeight):
        return quantized_matmul(x, w, tp=tp)
    return x @ w


# ---------------------------------------------------------------------------
# compressed collectives (the quant_reduce equivalent)
# ---------------------------------------------------------------------------


def _ring_reduce_scatter_q(x, axis_name: str, block: int):
    """Inside shard_map: ring reduce-scatter with int8 wire format.

    x: [n_chunks * c, ...] local array; returns this rank's reduced chunk
    [c, ...]. Each of the n-1 hops sends one quantized chunk to the next
    rank (ppermute), which dequantizes and accumulates its local data.
    """
    n = jax.lax.axis_size(axis_name)
    rank = jax.lax.axis_index(axis_name)
    if x.shape[0] % n != 0:
        raise ValueError(
            f"ring reduce-scatter needs the local leading dim "
            f"({x.shape[0]}) divisible by axis size ({n}); pad the "
            "input (global leading dim must divide by n*n)"
        )
    chunks = x.shape[0] // n
    perm = [(i, (i + 1) % n) for i in range(n)]

    def chunk_at(i):
        return jax.lax.dynamic_slice_in_dim(x, i * chunks, chunks, axis=0)

    # travelling-accumulator ring: rank r starts the accumulator for
    # chunk (r-1); each hop the accumulator moves one rank forward and
    # picks up that rank's local share, so after n-1 hops rank r holds
    # the fully reduced chunk r
    acc = chunk_at((rank + n - 1) % n)
    for step in range(n - 1):
        q, s, shape, pad = quantize_any(acc, block)
        q = jax.lax.ppermute(q, axis_name, perm)
        s = jax.lax.ppermute(s, axis_name, perm)
        recv = dequantize_any(q, s, shape, pad)
        idx = (rank + n - 2 - step) % n
        acc = recv + chunk_at(idx)
    return acc


def quantized_reduce_scatter(
    x: jax.Array, mesh, axis_name: str, block: int = DEFAULT_BLOCK
) -> jax.Array:
    """Reduce-scatter over `axis_name` with int8 payloads. x is replicated
    per-shard input [n*c, ...]; result is each rank's summed chunk."""
    from jax.sharding import PartitionSpec as P

    fn = shard_map(
        functools.partial(
            _ring_reduce_scatter_q, axis_name=axis_name, block=block
        ),
        mesh=mesh,
        in_specs=P(axis_name),
        out_specs=P(axis_name),
    )
    return fn(x)


def quantized_all_reduce_tree(
    grads, mesh, axis_name: str, block: int = DEFAULT_BLOCK
):
    """Compressed gradient all-reduce over a pytree of *per-rank
    contributions*: each leaf has a leading axis of size n (= mesh axis
    size) holding rank i's gradient at index i, sharded over
    `axis_name`. Each rank quantizes its own slice once (own scale),
    all-gathers the int8 payload + scales (1/4 the f32 wire bytes),
    then dequantizes every contribution and sums in f32 locally —
    one-shot compression for DCN-crossing reduces where ring latency
    dominates. Returns the replicated sum with the leading axis dropped.
    Wire format matches quant_reduce.cu's role; the sum itself is exact
    given the quantized inputs.

    Distinct inputs must arrive as distinct shards: a replicated
    jax.Array holds one value per-rank, so a plain in_specs=P() design
    cannot combine different gradients (it would just scale by n)."""
    from jax.sharding import PartitionSpec as P

    n_ranks = mesh.shape[axis_name]

    def one(g):
        if g.shape[0] != n_ranks:
            raise ValueError(
                f"leaf leading dim {g.shape[0]} != axis size {n_ranks}; "
                "stack per-rank contributions on axis 0"
            )

        def inner(gl):
            # gl: [1, ...] — this rank's contribution
            q, s, shape, pad = quantize_any(gl[0], block)
            qg = jax.lax.all_gather(q, axis_name)  # [n, 1, L]
            sg = jax.lax.all_gather(s, axis_name)  # [n, 1, L/block]
            n = qg.shape[0]
            deq = dequantize_int8(
                qg.reshape(n, -1), sg.reshape(n, -1), block_m=1
            )
            total = jnp.sum(deq, axis=0)
            if pad:
                total = total[:-pad]
            return total.reshape(shape)

        fn = shard_map(
            inner, mesh=mesh, in_specs=P(axis_name), out_specs=P()
        )
        return fn(g)

    return jax.tree_util.tree_map(one, grads)
