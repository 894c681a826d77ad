"""Pallas TPU flash attention (forward + backward kernels, custom VJP).

Reference parity: ATorch's flash-attention integration patches CUDA
flash_attn into HF modules (atorch/atorch/modules/transformer/layers.py);
TFPlus ships a CUDA fmha op (tfplus/flash_attn/kernels/). Here the kernel
is written for the TPU memory hierarchy: blocks staged HBM→VMEM by the
pallas pipeline, S = QK^T on the MXU per (128, 128) tile, online softmax
in f32 on the VPU, O accumulated in VMEM scratch.

Layout contract: public API takes [batch, seq, heads, head_dim]; kernels
run on [batch, heads, seq, head_dim]. GQA is handled by a differentiable
broadcast outside the custom_vjp boundary (autodiff reduces dK/dV).
"""

import functools
import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

shard_map = functools.partial(jax.shard_map, check_vma=False)

NEG_INF = -1e30
# Blocks as large as the VMEM budget allows: the 1024^2 score tile
# measured 2.2x faster than 128^2 at head_dim 64 on v5e (grid-step
# overhead dominates small tiles when the contraction dim is short).
_MAX_BLOCK = 1024
# VMEM bytes budgeted per kernel invocation (v5e has ~16 MB; leave
# headroom for Mosaic's double buffering of the HBM->VMEM pipeline)
_VMEM_BUDGET = 8 * 1024 * 1024


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


def force_kernels() -> bool:
    """DLROVER_TPU_FORCE_KERNELS=1 makes the dispatch gates
    (attention.dot_product_attention 'auto', paged_attention
    use_kernel) treat the interpret-mode kernels as dispatchable on a
    non-TPU backend. Test/bench escape hatch ONLY: it is how the
    forced-8-device CPU host exercises the shard_mapped kernel paths
    end-to-end; production 'auto' stays reference off-TPU."""
    return os.environ.get("DLROVER_TPU_FORCE_KERNELS", "") == "1"


def per_shard_heads(
    h: int, kv: int, tp: int
) -> Optional[Tuple[int, int]]:
    """The (q_heads, kv_heads) one shard sees under GSPMD head
    sharding of degree `tp`, or None when the global counts don't
    split evenly (then no head layout exists and every kernel gate
    must fail). The ONE divisibility check both `supports()` gates
    (flash and paged) share, so they cannot drift."""
    if tp > 1:
        if h % tp != 0 or kv % tp != 0:
            return None
        return h // tp, kv // tp
    return h, kv


def _pick_block(s: int, cap: int) -> int:
    """Largest power-of-two block <= cap that divides s (min 128)."""
    b = cap
    while b >= 128:
        if s % b == 0:
            return b
        b //= 2
    return 0


def _vmem_estimate(bq: int, bk: int, d: int) -> int:
    """Rough per-invocation VMEM bytes for the worst (dkv) kernel:
    f32 score tile + f32 dk/dv/acc scratches + bf16 staged blocks."""
    return 4 * bq * bk + 8 * d * bq + 10 * d * bk


def auto_blocks(s_q: int, s_k: int, d: int) -> Tuple[int, int]:
    """Pick (block_q, block_k) for the shapes: as large as the VMEM
    budget allows given head_dim d. Returns (0, 0) when no block >= 128
    divides the sequence (then the caller must use the XLA reference).

    s_q == 1 is the KV-cache decode shape: block_q is the whole
    (one-row) query axis — legal because a block dim that MATCHES the
    array dim needs no (8, 128) tiling — and only the key axis blocks.
    """
    bq = 1 if s_q == 1 else _pick_block(s_q, _MAX_BLOCK)
    bk = _pick_block(s_k, _MAX_BLOCK)
    while max(bq, bk) >= 256 and _vmem_estimate(bq, bk, d) > _VMEM_BUDGET:
        if bq >= bk:
            bq //= 2
        else:
            bk //= 2
    return bq, bk


def supports(
    q, k, segment_ids=None, block_q=None, block_k=None, tp: int = 1
) -> bool:
    """Whether the flash path handles these shapes (else XLA reference).

    `tp` is the serving tensor-parallel degree: under GSPMD head
    sharding the kernel would run on PER-SHARD heads, so the head
    constraints are evaluated after dividing both head counts by tp —
    a global head count that doesn't split evenly can't shard at all,
    and the GQA group check must hold within one shard."""
    if segment_ids is not None:
        return False
    shard = per_shard_heads(q.shape[2], k.shape[2], tp)
    if shard is None:
        return False
    h, kv = shard
    d = q.shape[-1]
    s_q = q.shape[1]
    s_k = k.shape[1]
    # Mosaic pads the minor dim to the 128-lane register width, so any
    # multiple-of-8 head_dim lowers; below 32 the pad waste is too high
    # to beat the XLA reference
    if d % 8 != 0 or d < 32 or d > 512:
        return False
    if s_q != s_k and s_q != 1:
        # the kernel's causal mask is top-left aligned; general
        # cross-length attention needs the bottom-right offset the XLA
        # reference applies — don't take the flash path. The s_q == 1
        # decode shape is the EXCEPTION: a single query at the
        # bottom-right row attends every key, so causal masking
        # degenerates to no mask at all and the kernel handles it
        # (the paged-attention decode gate reuses this).
        return False
    auto_q, auto_k = auto_blocks(s_q, s_k, d)
    bq = block_q or auto_q
    bk = block_k or auto_k
    if not bq or not bk:
        return False
    if s_q % bq != 0 or s_k % bk != 0:
        return False
    if h % kv != 0:
        return False
    return True


# ---------------------------------------------------------------------------
# forward kernel
# ---------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr,
                *, scale, causal, block_q, block_k, num_kb, window=0,
                block=0):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # causal: block row qi only attends to key blocks up to the diagonal
    last_ki = num_kb - 1
    if causal:
        last_ki = jnp.minimum(
            num_kb - 1, ((qi + 1) * block_q - 1) // block_k
        )
    in_band = ki <= last_ki
    if window:
        # a causal band: key blocks wholly older than the block row's
        # first query's window are skipped like those past the diagonal
        first_ki = jnp.maximum(qi * block_q - (window - 1), 0) // block_k
        in_band = in_band & (ki >= first_ki)

    @pl.when(in_band)
    def _compute():
        q = q_ref[0, 0]  # [bq, d]
        k = k_ref[0, 0]  # [bk, d]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [bq, bk]
        if causal:
            rows = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            cols = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            keep = rows >= cols
            if window:
                keep = keep & (rows - cols < window)
            if block:
                # the diagonal rounded up to its block's last
                # position (`_fwd`; a power of two: no vector division)
                keep = (rows | (block - 1)) >= cols
            s = jnp.where(keep, s, NEG_INF)
        m_prev = m_scr[:, :1]  # [bq, 1]
        l_prev = l_scr[:, :1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)  # [bq, bk] f32
        l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0, 0],
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ki == last_ki)
    def _finalize():
        l = l_scr[:, :1]
        l = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows
        o_ref[0, 0] = (acc_scr[:] / l).astype(o_ref.dtype)
        lse = m_scr[:, :1] + jnp.log(l)
        # lse rides an 8-lane padded layout: Mosaic requires the last
        # two block dims to tile (8, 128) or match the array dims, so a
        # bare [block_q] vector output cannot lower on real TPU
        lse_ref[0, 0] = jnp.broadcast_to(lse, lse_ref[0, 0].shape)


def _fwd(q, k, v, causal, scale, block_q, block_k, window=0, block=0):
    """q,k,v: [B, H, S, D] (equal head counts). Returns (o, lse).
    `window` > 0 (with causal): query i sees keys i - window < j <= i.
    `block` > 0 (with causal, no window): key j is seen by query i iff
    j // block <= i // block, the diagonal rounded up to its block of
    `block` positions (a block-diffusion model's prefill). `block`
    is a power of two that divides both tile sizes, so a tile the
    diagonal skips holds no key of a rounded-up row either."""
    if block and (
        not causal or window or block & (block - 1)
        or block_q % block or block_k % block
    ):
        raise ValueError(
            f"a block mask of {block} is causal, has no window, is a "
            f"power of two and divides the tiles ({block_q}, {block_k})"
        )
    b, h, s_q, d = q.shape
    s_k = k.shape[2]
    num_qb = s_q // block_q
    num_kb = s_k // block_k
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, num_kb=num_kb,
        **({"window": window} if window else {}),
        **({"block": block} if block else {}),
    )
    o, lse = pl.pallas_call(
        kernel,
        grid=(b, h, num_qb, num_kb),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b, h, qi, ki: (b, h, ki, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b, h, qi, ki: (b, h, ki, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_q, 8),
                         lambda b, h, qi, ki: (b, h, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, s_q, d), q.dtype),
            jax.ShapeDtypeStruct((b, h, s_q, 8), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(
                "parallel", "parallel", "parallel", "arbitrary"
            ),
        ),
        interpret=_interpret(),
        name="flash_attention_fwd",
    )(q, k, v)
    return o, lse


# ---------------------------------------------------------------------------
# backward kernels
# ---------------------------------------------------------------------------


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, dq_scr,
                   *, scale, causal, block_q, block_k, num_kb):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    last_ki = num_kb - 1
    if causal:
        last_ki = jnp.minimum(
            num_kb - 1, ((qi + 1) * block_q - 1) // block_k
        )

    @pl.when(ki <= last_ki)
    def _compute():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0][:, :1]      # [bq, 1] from the 8-lane pad
        delta = delta_ref[0, 0][:, :1]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        if causal:
            rows = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            cols = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            s = jnp.where(rows >= cols, s, NEG_INF)
        p = jnp.exp(s - lse)  # [bq, bk]
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta) * scale
        dq_scr[:] = dq_scr[:] + jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(ki == last_ki)
    def _finalize():
        dq_ref[0, 0] = dq_scr[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr,
                    *, scale, causal, block_q, block_k, num_qb):
    ki = pl.program_id(2)
    qi = pl.program_id(3)

    @pl.when(qi == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    # causal: key block ki only receives gradient from q blocks at/after it
    first_qi = 0
    if causal:
        first_qi = (ki * block_k) // block_q

    @pl.when(qi >= first_qi)
    def _compute():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        # transposed padded layout [8, bq]: row 0 is the real data
        lse = lse_ref[0, 0][:1, :]      # [1, bq]
        delta = delta_ref[0, 0][:1, :]
        # transposed score block: [bk, bq]
        s_t = jax.lax.dot_general(
            k, q, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        if causal:
            rows = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_k, block_q), 0
            )
            cols = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_k, block_q), 1
            )
            s_t = jnp.where(cols >= rows, s_t, NEG_INF)
        p_t = jnp.exp(s_t - lse)  # [bk, bq]
        dv_scr[:] = dv_scr[:] + jax.lax.dot_general(
            p_t.astype(do.dtype), do, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp_t = jax.lax.dot_general(
            v, do, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [bk, bq]
        ds_t = p_t * (dp_t - delta) * scale
        dk_scr[:] = dk_scr[:] + jax.lax.dot_general(
            ds_t.astype(q.dtype), q, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(qi == num_qb - 1)
    def _finalize():
        dk_ref[0, 0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[:].astype(dv_ref.dtype)


def _bwd(q, k, v, o, lse, do, causal, scale, block_q, block_k):
    b, h, s_q, d = q.shape
    s_k = k.shape[2]
    num_qb = s_q // block_q
    num_kb = s_k // block_k
    delta = jnp.sum(
        do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1
    )  # [B, H, S]
    # mirror lse's 8-lane padded layout (see _fwd) + a transposed view
    # for the dkv kernel, whose rows are key blocks
    delta_p = jnp.broadcast_to(delta[..., None], (b, h, s_q, 8))
    lse_t = jnp.swapaxes(lse, 2, 3)      # [B, H, 8, S]
    delta_t = jnp.swapaxes(delta_p, 2, 3)

    dq_kernel = functools.partial(
        _bwd_dq_kernel, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, num_kb=num_kb,
    )
    dq = pl.pallas_call(
        dq_kernel,
        grid=(b, h, num_qb, num_kb),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b, h, qi, ki: (b, h, ki, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b, h, qi, ki: (b, h, ki, 0)),
            pl.BlockSpec((1, 1, block_q, d),
                         lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_q, 8),
                         lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_q, 8),
                         lambda b, h, qi, ki: (b, h, qi, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, s_q, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(
                "parallel", "parallel", "parallel", "arbitrary"
            ),
        ),
        interpret=_interpret(),
        name="flash_attention_bwd_dq",
    )(q, k, v, do, lse, delta_p)

    dkv_kernel = functools.partial(
        _bwd_dkv_kernel, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, num_qb=num_qb,
    )
    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid=(b, h, num_kb, num_qb),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda b, h, ki, qi: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b, h, ki, qi: (b, h, ki, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b, h, ki, qi: (b, h, ki, 0)),
            pl.BlockSpec((1, 1, block_q, d),
                         lambda b, h, ki, qi: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, 8, block_q),
                         lambda b, h, ki, qi: (b, h, 0, qi)),
            pl.BlockSpec((1, 1, 8, block_q),
                         lambda b, h, ki, qi: (b, h, 0, qi)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b, h, ki, qi: (b, h, ki, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b, h, ki, qi: (b, h, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, s_k, d), k.dtype),
            jax.ShapeDtypeStruct((b, h, s_k, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(
                "parallel", "parallel", "parallel", "arbitrary"
            ),
        ),
        interpret=_interpret(),
        name="flash_attention_bwd_dkv",
    )(q, k, v, do, lse_t, delta_t)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom VJP + public API
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, causal, scale, block_q, block_k):
    o, _ = _fwd(q, k, v, causal, scale, block_q, block_k)
    return o


def _flash_fwd(q, k, v, causal, scale, block_q, block_k):
    o, lse = _fwd(q, k, v, causal, scale, block_q, block_k)
    return o, (q, k, v, o, lse)


def _flash_bwd(causal, scale, block_q, block_k, res, do):
    q, k, v, o, lse = res
    dq, dk, dv = _bwd(
        q, k, v, o, lse, do, causal, scale, block_q, block_k
    )
    return dq, dk, dv


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    window: int = 0,
    block: int = 0,
) -> jax.Array:
    """Flash attention on [B, S, H, D] tensors; returns [B, S, H, D].

    block_q/block_k default to the VMEM-budget auto choice (auto_blocks);
    pass explicit sizes only for tuning experiments. `window` > 0 is a
    causal band (query i sees keys i - window < j <= i) in the
    FORWARD kernel only — the serving prefill of a window layer; it
    has no backward. `block` > 0 rounds the causal diagonal up to
    blocks of `block` positions (`_fwd`), forward only as well."""
    if (window or block) and not (causal and q.shape[1] == k.shape[1]):
        raise ValueError(
            "a window or a block mask needs causal attention over "
            "equal q/k lengths"
        )
    if causal and q.shape[1] != k.shape[1]:
        if q.shape[1] == 1:
            # single-query decode: the query sits at the bottom-right
            # row of the (1, s_k) score matrix, where the causal mask
            # keeps every column — run the kernel unmasked (identical
            # math, no per-block mask work)
            causal = False
        else:
            raise ValueError(
                "flash_attention causal masking requires equal q/k "
                f"lengths (got {q.shape[1]} vs {k.shape[1]}) unless "
                "q_len == 1 (decode); use the XLA reference path"
            )
    if block_q is None or block_k is None:
        auto_q, auto_k = auto_blocks(
            q.shape[1], k.shape[1], q.shape[-1]
        )
        block_q = block_q or auto_q
        block_k = block_k or auto_k
    if not block_q or not block_k:
        raise ValueError(
            f"no flash block size divides seq lengths "
            f"{q.shape[1]}/{k.shape[1]}; use the XLA reference path"
        )
    # explicit (tuning-sweep) blocks must tile the sequence exactly —
    # the grid uses floor division, so a non-dividing block would
    # silently leave the tail rows unwritten
    if q.shape[1] % block_q or k.shape[1] % block_k:
        raise ValueError(
            f"block_q={block_q}/block_k={block_k} do not divide seq "
            f"lengths {q.shape[1]}/{k.shape[1]}"
        )
    if scale is None:
        scale = float(q.shape[-1]) ** -0.5
    n_rep = q.shape[2] // k.shape[2]
    if n_rep > 1:
        from dlrover_tpu.ops.attention import _kv_repeat

        # differentiable broadcast: autodiff sums dK/dV over the group
        k = _kv_repeat(k, n_rep)
        v = _kv_repeat(v, n_rep)
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    if window or block:
        o, _ = _fwd(
            qt, kt, vt, causal, scale, block_q, block_k, window, block
        )
    else:
        o = _flash(qt, kt, vt, causal, scale, block_q, block_k)
    return o.transpose(0, 2, 1, 3)


def sharded_flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh,
    causal: bool = True,
    scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
) -> jax.Array:
    """`flash_attention` shard_mapped over `mesh`: each shard runs
    the unmodified kernel on its own batch rows and heads (the TPU
    compiler will not partition a Pallas kernel itself). Attention is
    embarrassingly parallel over both, so the body needs NO
    collectives — and because scale, blocks and the causal mask
    depend only on the (unsharded) seq/head_dim axes, every shard
    runs the exact arithmetic the one-device kernel runs on its
    slice: output is byte-identical to it, chunked. Under a serving
    mesh the caller (models/decode.py) keeps the replicated-output
    constraint before the out-projection.

    q/k/v are GLOBAL [B, S, H, D] arrays (head axes divisible by the
    head-shard degree — `supports(..., tp=...)` gates this); the spec
    comes from parallel/mesh.py:attention_qkv_spec, the one layout
    source for serving and training meshes alike."""
    from dlrover_tpu.parallel.mesh import attention_qkv_spec

    if scale is None:
        scale = float(q.shape[-1]) ** -0.5
    # pin blocks OUTSIDE the shard_map body: auto_blocks reads only
    # seq/head_dim (unsharded), but resolving them here makes the
    # tp-invariance explicit rather than a property of the body
    if block_q is None or block_k is None:
        auto_q, auto_k = auto_blocks(
            q.shape[1], k.shape[1], q.shape[-1]
        )
        block_q = block_q or auto_q
        block_k = block_k or auto_k
    spec, _ = attention_qkv_spec(mesh)
    fn = functools.partial(
        flash_attention, causal=causal, scale=scale,
        block_q=block_q, block_k=block_k,
    )
    return shard_map(
        fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec
    )(q, k, v)
