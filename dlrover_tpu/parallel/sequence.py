"""Sequence/context parallelism: Ulysses all-to-all + ring attention.

Reference parity (SURVEY.md §2.5): ATorch ships two SP mechanisms —
(a) Ulysses-style head-scatter/seq-gather all-to-all
    (`_SeqAllToAll` atorch/atorch/distributed/distributed.py:474,
    auto/opt_lib/sequence_parallel_optimization.py:10-17), and
(b) a distributed-softmax attention keeping KV sharded along sequence
    with allreduced softmax stats (modules/distributed_transformer/
    distributed_attention.py:21).

TPU design: both run inside one `shard_map` over the mesh's "seq" axis.
Ulysses maps to `jax.lax.all_to_all` (one ICI all-to-all each way); ring
attention rotates KV chunks with `jax.lax.ppermute` while accumulating a
blockwise online softmax in f32 — the blockwise/ring family — so the
sequence never materializes on one chip and comm overlaps the per-step
matmuls that XLA schedules around the permute. Both are plain
differentiable JAX (autodiff derives the backward ring), with
`jax.checkpoint` on the ring body to keep residuals O(S_local).
"""

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

# the scan carries start replicated and become device-varying, so the
# varying-manual-axes check is disabled rather than pcast-ing every init
shard_map = functools.partial(jax.shard_map, check_vma=False)

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Ulysses: scatter heads, gather sequence
# ---------------------------------------------------------------------------


def _heads_to_seq(x: jax.Array, axis_name: str) -> jax.Array:
    """[B, S/sp, H, D] → [B, S, H/sp, D] (one all-to-all over ICI)."""
    return jax.lax.all_to_all(
        x, axis_name, split_axis=2, concat_axis=1, tiled=True
    )


def _seq_to_heads(x: jax.Array, axis_name: str) -> jax.Array:
    """[B, S, H/sp, D] → [B, S/sp, H, D] (inverse all-to-all)."""
    return jax.lax.all_to_all(
        x, axis_name, split_axis=1, concat_axis=2, tiled=True
    )


def _kv_repeat_local(kv: jax.Array, n_rep: int) -> jax.Array:
    """Broadcast KV heads [B,S,KV,D] → [B,S,KV*n_rep,D] (differentiable;
    autodiff sums the group gradient back onto the shared head)."""
    if n_rep == 1:
        return kv
    b, s, h, d = kv.shape
    kv = jnp.broadcast_to(kv[:, :, :, None, :], (b, s, h, n_rep, d))
    return kv.reshape(b, s, h * n_rep, d)


def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str,
    attn_fn: Callable[..., jax.Array],
    causal: bool = True,
) -> jax.Array:
    """Ulysses SP attention on seq-sharded [B, S/sp, H, D] inputs.

    All-to-all converts seq sharding into head sharding, runs full-sequence
    attention on H/sp local heads, and converts back. Requires H % sp == 0;
    KV heads are broadcast up to a multiple of sp first if needed.
    """
    sp = jax.lax.axis_size(axis_name)
    h = q.shape[2]
    if h % sp:
        raise ValueError(f"ulysses needs n_heads % sp == 0 ({h} % {sp})")
    kv_h = k.shape[2]
    if kv_h % sp:
        # GQA with fewer KV heads than the SP degree: replicate KV groups
        # so each SP shard owns whole heads.
        rep = (h // kv_h) if h % kv_h == 0 else 1
        k = _kv_repeat_local(k, rep)
        v = _kv_repeat_local(v, rep)
        if k.shape[2] % sp:
            raise ValueError(
                f"ulysses: kv_heads {kv_h} not alignable to sp={sp}"
            )
    q = _heads_to_seq(q, axis_name)
    k = _heads_to_seq(k, axis_name)
    v = _heads_to_seq(v, axis_name)
    o = attn_fn(q, k, v, causal=causal)
    return _seq_to_heads(o, axis_name)


# ---------------------------------------------------------------------------
# Ring attention: rotate KV chunks, blockwise online softmax
# ---------------------------------------------------------------------------


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str,
    causal: bool = True,
    scale: Optional[float] = None,
) -> jax.Array:
    """Ring attention on seq-sharded [B, S/sp, H, D] inputs (inside
    shard_map). KV chunks rotate around the "seq" ring via ppermute; each
    step folds one chunk into an online-softmax accumulator. Handles GQA
    (H % KV == 0) and causal masking in global coordinates.
    """
    sp = jax.lax.axis_size(axis_name)
    my = jax.lax.axis_index(axis_name)
    if scale is None:
        scale = float(q.shape[-1]) ** -0.5

    n_rep = q.shape[2] // k.shape[2]
    if n_rep > 1:
        k = _kv_repeat_local(k, n_rep)
        v = _kv_repeat_local(v, n_rep)

    b, s_q, h, d = q.shape
    s_k = k.shape[1]
    # compute layout [B, H, S, D]
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)

    rows = my * s_q + jax.lax.broadcasted_iota(jnp.int32, (s_q, s_k), 0)

    def step(carry, t):
        m, l, acc, k_blk, v_blk = carry
        src = jnp.mod(my - t, sp)  # which global chunk we hold at step t
        s = jax.lax.dot_general(
            qt, k_blk,
            (((3,), (3,)), ((0, 1), (0, 1))),
            preferred_element_type=jnp.float32,
        ) * scale  # [B, H, Sq, Sk]
        if causal:
            cols = src * s_k + jax.lax.broadcasted_iota(
                jnp.int32, (s_q, s_k), 1
            )
            s = jnp.where(rows >= cols, s, NEG_INF)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m, m_cur)
        # p gated to exactly 0 on masked entries so fully-masked blocks
        # contribute nothing and exp() never sees garbage in the vjp
        p = jnp.where(
            s <= NEG_INF / 2, 0.0, jnp.exp(s - m_new)
        )  # [B,H,Sq,Sk] f32
        alpha = jnp.exp(m - m_new)
        l_new = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * alpha + jax.lax.dot_general(
            p, vt_cast(v_blk),
            (((3,), (2,)), ((0, 1), (0, 1))),
            preferred_element_type=jnp.float32,
        )
        k_nxt, v_nxt = jax.lax.ppermute(
            (k_blk, v_blk), axis_name,
            [(i, (i + 1) % sp) for i in range(sp)],
        )
        return (m_new, l_new, acc_new, k_nxt, v_nxt), None

    def vt_cast(v_blk):
        return v_blk.astype(jnp.float32)

    m0 = jnp.full((b, h, s_q, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, s_q, 1), jnp.float32)
    acc0 = jnp.zeros((b, h, s_q, d), jnp.float32)
    (m, l, acc, _, _), _ = jax.lax.scan(
        jax.checkpoint(step),
        (m0, l0, acc0, kt, vt),
        jnp.arange(sp),
    )
    l = jnp.where(l == 0.0, 1.0, l)
    o = (acc / l).astype(q.dtype)
    return o.transpose(0, 2, 1, 3)  # [B, Sq, H, D]


# ---------------------------------------------------------------------------
# mesh-level entry point
# ---------------------------------------------------------------------------


def sp_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    mode: str = "ring",
    causal: bool = True,
    attn_fn: Optional[Callable] = None,
    seq_axis: str = "seq",
    batch_axes=("data", "fsdp"),
    head_axis: str = "tensor",
) -> jax.Array:
    """Run SP attention over the mesh's sequence axis.

    Inputs are GLOBAL [B, S, H, D] arrays (GSPMD-sharded); shard_map takes
    the per-device view with batch on (data, fsdp), seq on `seq_axis`,
    heads on `head_axis`, and runs ring / ulysses over the seq axis.
    """
    if mode not in ("ring", "ulysses"):
        raise ValueError(f"unknown sp mode: {mode}")
    if attn_fn is None:
        from dlrover_tpu.ops.attention import dot_product_attention

        attn_fn = dot_product_attention

    qspec = P(batch_axes, seq_axis, head_axis, None)

    def local(q, k, v):
        if mode == "ulysses":
            return ulysses_attention(
                q, k, v, seq_axis, attn_fn, causal=causal
            )
        return ring_attention(q, k, v, seq_axis, causal=causal)

    return shard_map(
        local,
        mesh=mesh,
        in_specs=(qspec, qspec, qspec),
        out_specs=qspec,
    )(q, k, v)


def seq_chunk_positions(
    s_global: int, mesh: Mesh, seq_axis: str = "seq"
) -> jax.Array:
    """Global position ids [S] — identical to arange; kept for clarity
    that RoPE must use GLOBAL positions under sequence sharding."""
    return jnp.arange(s_global, dtype=jnp.int32)
