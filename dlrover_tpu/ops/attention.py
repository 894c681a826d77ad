"""Attention entry point: dispatches to the Pallas TPU flash kernel on TPU
and a fused-softmax jnp reference elsewhere (CPU tests, debugging).

Reference parity: ATorch integrates CUDA flash-attention by patching HF
modules (atorch/atorch/modules/transformer/layers.py FA adapters). Here
attention is a first-class op the models call directly.

Shapes follow the TPU-friendly layout [batch, seq, heads, head_dim].
"""

from typing import Optional

import jax
import jax.numpy as jnp

from dlrover_tpu.common.log import warning_once

NEG_INF = -1e30


def _kv_repeat(k: jax.Array, n_rep: int) -> jax.Array:
    """Grouped-query attention: repeat KV heads to match Q heads."""
    if n_rep == 1:
        return k
    b, s, h, d = k.shape
    k = jnp.broadcast_to(k[:, :, :, None, :], (b, s, h, n_rep, d))
    return k.reshape(b, s, h * n_rep, d)


def reference_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    scale: Optional[float] = None,
    segment_ids: Optional[jax.Array] = None,
    window: int = 0,
    block: int = 0,
) -> jax.Array:
    """Plain XLA attention, softmax in f32. [B, S, H, D] in and out.
    `window` > 0 (causal): query i sees keys i - window < j <= i.
    `block` > 0 (causal): the diagonal rounded up to its block of
    `block` positions, key j is seen iff j // block <= i // block."""
    orig_dtype = q.dtype
    n_rep = q.shape[2] // k.shape[2]
    k = _kv_repeat(k, n_rep)
    v = _kv_repeat(v, n_rep)
    if scale is None:
        scale = q.shape[-1] ** -0.5
    logits = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    )
    logits = logits * scale
    q_len, k_len = logits.shape[-2], logits.shape[-1]
    if causal:
        q_pos = jnp.arange(q_len)[:, None] + (k_len - q_len)
        k_pos = jnp.arange(k_len)[None, :]
        keep = q_pos >= k_pos
        if window:
            keep = keep & (q_pos - k_pos < window)
        if block:
            keep = q_pos // block >= k_pos // block
        logits = jnp.where(keep, logits, NEG_INF)
    if segment_ids is not None:
        seg_mask = (
            segment_ids[:, None, :, None] == segment_ids[:, None, None, :]
        )
        logits = jnp.where(seg_mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(orig_dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _tpu_available() -> bool:
    return jax.default_backend() == "tpu"


def dot_product_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    scale: Optional[float] = None,
    segment_ids: Optional[jax.Array] = None,
    impl: str = "auto",
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    tp: int = 1,
    mesh=None,
    window: int = 0,
    block: int = 0,
) -> jax.Array:
    """Main entry. impl: 'auto' | 'flash' | 'reference'.
    `window` > 0 is a causal band (a window layer's serving prefill,
    forward only, one device). `block` > 0 rounds the causal diagonal
    up to blocks of `block` positions (a block-diffusion model's
    prefill; forward only, one device, no window).

    'auto' uses the Pallas flash kernel on TPU when shapes allow
    (seq % block == 0, head_dim tile-able), else the XLA reference.
    DLROVER_TPU_FORCE_KERNELS=1 (flash_attention.force_kernels) lets
    tests/bench dispatch the interpret-mode kernel off-TPU too.

    `mesh` declares the caller runs under GSPMD on that mesh (a
    serving mesh, or a training mesh whose seq/pipe axes are idle)
    and `tp` its head-shard degree: with more than one device 'auto'
    takes the kernel shard_mapped over the mesh — each shard runs
    flash on its own batch rows and PER-SHARD heads (attention mixes
    neither, so the body needs no collectives) — whenever the
    per-shard shapes pass `supports(..., tp=tp)`; otherwise the
    reference, whose einsums GSPMD partitions for free. `tp` > 1
    without a mesh has no layout to shard_map over and stays on the
    reference.
    """
    if window and mesh is not None and mesh.devices.size > 1:
        raise ValueError("window attention is not sharded over a mesh")
    if block and (
        window or not causal or (mesh is not None and mesh.devices.size > 1)
    ):
        raise ValueError(
            "a block mask is causal, has no window and is not sharded "
            "over a mesh"
        )
    if impl == "reference":
        return reference_attention(
            q, k, v, causal, scale, segment_ids, window, block
        )
    if impl in ("auto", "flash"):
        from dlrover_tpu.ops import flash_attention as fa

        if impl == "flash" and segment_ids is not None:
            raise ValueError(
                "flash attention does not support segment_ids yet; "
                "use impl='reference' for packed sequences"
            )
        take_flash = impl == "flash" or (
            (_tpu_available() or fa.force_kernels())
            and (tp == 1 or mesh is not None)
            and fa.supports(
                q, k, segment_ids,
                block_q=block_q, block_k=block_k, tp=tp,
            )
        )
        if take_flash:
            if mesh is not None and mesh.devices.size > 1:
                return fa.sharded_flash_attention(
                    q, k, v, mesh, causal=causal, scale=scale,
                    block_q=block_q, block_k=block_k,
                )
            return fa.flash_attention(
                q, k, v, causal=causal, scale=scale,
                block_q=block_q, block_k=block_k, window=window,
                block=block,
            )
        if block_q or block_k:
            # explicit tuning blocks were given but the flash path was
            # NOT taken (any supports() failure: divisibility, head
            # dim, cross-length, segment_ids, non-TPU backend) — a
            # silent reference fallback would record wrong sweep
            # results as tuned-flash numbers
            raise ValueError(
                f"explicit block_q={block_q}/block_k={block_k} given "
                "but the flash path is unsupported for these "
                f"shapes/backend (q{q.shape} k{k.shape})"
            )
        if _tpu_available():
            warning_once(
                "attention impl='auto' takes the XLA reference on "
                "this TPU: flash_attention.supports() refuses q%s k%s "
                "(segment_ids=%s, tp=%d, mesh=%s)",
                tuple(q.shape), tuple(k.shape),
                segment_ids is not None, tp, mesh is not None,
            )
        return reference_attention(
            q, k, v, causal, scale, segment_ids, window, block
        )
    raise ValueError(f"unknown attention impl: {impl}")
