"""KV-cache autoregressive decoding (Llama + GPT-2 families).

Reference parity: the serving path the reference delegates to vLLM
(atorch/rl/inference_backend/vllm_backend.py) and the incremental decode
TFPlus's fmha skips (flash_attention.h:161 is training-only, like ours).
TPU redesign: one jittable step with STATIC shapes — the cache is a
fixed [L, B, M, KV, hd] buffer, each step writes position `pos` via
dynamic_update_slice and attends over the full buffer under a position
mask. O(M) attention per token instead of the O(P+t) re-forward
rl/generate.py does; `lax.scan` drives the whole generation in one
compiled program.

Prefill and decode share `_block` (S=P vs S=1) so there is exactly one
attention/cache implementation to keep correct.
"""

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from dlrover_tpu.models.llama import (
    LlamaConfig,
    _attn_qkv,
    _attn_residual,
    _compute_weights,
    _head_matrix,
    _mlp_residual,
    _rms_norm,
    _rope,
    _swiglu,
)
from dlrover_tpu.ops.quantization import matmul_any
from dlrover_tpu.parallel.mesh import SERVING_TP_AXIS
from dlrover_tpu.parallel.sharding import constrain

Params = Dict


def _mesh_tp(mesh) -> int:
    """Size of the serving tensor axis (1 when no mesh is threaded)."""
    if mesh is None:
        return 1
    return dict(zip(mesh.axis_names, mesh.devices.shape)).get(
        SERVING_TP_AXIS, 1
    )


# ---------------------------------------------------------------------------
# Layers in PERIODS. A model whose layers differ in kind (window and
# full attention mixed) repeats one period of kinds; the layer loops
# below scan over periods and write the period's layers out inside the
# body, each with its own static kind. A homogeneous model is a period
# of one: the stacked leaves go through the scan as they are.
# ---------------------------------------------------------------------------

_EXPERT_LEAVES = ("we_gate", "we_up", "we_down")


def _kinds(cfg) -> Tuple:
    """The period's kinds, or (None,) for a homogeneous model (None:
    the block takes the config's one rotary base and no window)."""
    pattern = getattr(cfg, "layer_pattern", ())
    return tuple(pattern) if pattern else (None,)


def _by_period(cfg, tree):
    """Leaves `[L, ...]` -> `[n_periods, period, ...]` (a bitcast)."""
    n = len(_kinds(cfg))
    if n == 1:
        return tree
    return jax.tree_util.tree_map(
        lambda a: a.reshape((a.shape[0] // n, n) + a.shape[1:]), tree
    )


def _place(cfg, tree, j: int):
    """The j-th layer of one period's slice of the leaves."""
    if len(_kinds(cfg)) == 1:
        return tree
    return jax.tree_util.tree_map(lambda a: a[j], tree)


def _stack_places(cfg, trees):
    if len(trees) == 1:
        return trees[0]
    return jax.tree_util.tree_map(lambda *a: jnp.stack(a), *trees)


def _dropless(cfg) -> bool:
    return (
        getattr(cfg, "n_experts", 0) > 0
        and getattr(cfg, "moe_routing", "capacity") == "dropless"
    )


def moe_counts_shape(cfg) -> Tuple:
    """Shape of the int32 counts a paged forward with dropless
    experts returns: the routed pairs per expert held here, summed
    over the layers; where the chip holds a SHARE of the experts, a
    second row: in how many layers each held expert got a pair at all
    (an expert no pair lands on is not read)."""
    held = cfg.held[1]
    return (2, held) if cfg.experts_held else (held,)


def _split_experts(cfg, layers):
    """(the leaves the layer loop scans over, the experts' stacks).
    Dropless experts stay OUT of the scan's xs: the grouped kernel
    addresses `[L, E, ...]` by the layer's index, so no loop body ever
    slices a layer's experts out of the stack."""
    if not _dropless(cfg):
        return layers, None
    experts = {k: layers[k] for k in _EXPERT_LEAVES}
    rest = {k: v for k, v in layers.items() if k not in experts}
    return rest, experts


def _window_of(cfg, kind) -> int:
    return cfg.sliding_window if kind == "window" else 0


def _qk_norms(cfg, layer_params):
    """The layer's per-head scales of q and k where the configuration
    norms them (`cfg.qk_norm`), else None."""
    if not getattr(cfg, "qk_norm", False):
        return None
    return layer_params["q_norm"], layer_params["k_norm"]


def _block_of(cfg) -> int:
    """Positions a diffusion block holds (0: one token a forward)."""
    return getattr(cfg, "block_length", 0)


def _block_end(positions, block: int):
    """The last position of each position's own block: what a query
    sees up to, where attention is two-sided inside a block."""
    return positions // block * block + (block - 1)


def _ffn_residual(cfg, x, layer_params, lp, tp, layer, experts):
    """The feed-forward half of a served block: llama's own
    `_mlp_residual`, or where the configuration routes without
    dropping, `moe.dropless_moe` over the stacked experts (beside the
    shared experts, where it has them); a leading dense layer of such
    a model (`experts` None) is a plain SwiGLU. Returns (x,
    int32[E_held] routed pairs per held expert, or None)."""
    if experts is None and not _dropless(cfg):
        x, _aux = _mlp_residual(cfg, None, x, layer_params, lp, tp=tp)
        return x, None
    b, s, d = x.shape
    h = _rms_norm(x, layer_params["mlp_norm"], cfg.norm_eps)
    if experts is None:
        with jax.named_scope("ffn_dense"):
            return x + _swiglu(
                None, h, lp["w_gate"], lp["w_up"], lp["w_down"], tp
            ), None
    from dlrover_tpu.models.moe import dropless_moe

    y, counts = dropless_moe(
        h.reshape(b * s, d), layer_params["router"],
        experts["we_gate"], experts["we_up"], experts["we_down"],
        cfg.routing, layer=layer, bias=layer_params.get("router_bias"),
    )
    x = x + y.reshape(b, s, d)
    if "ws_gate" in lp:
        with jax.named_scope("moe_shared"):
            x = x + _swiglu(
                None, h, lp["ws_gate"], lp["ws_up"], lp["ws_down"], tp
            )
    return x, counts


# Why byte parity survives head sharding (the tp>1 oracle of
# tests/test_serving_mesh.py): only OUTPUT dimensions of matmuls are
# ever sharded — the QKV projections split their head/output columns,
# so every output element still reduces over the full model dim in
# the same order as the unsharded program. Attention is per-KV-head
# local (scores contract head_dim, softmax runs over cache cells, the
# value einsum contracts cache cells — all within one head), and the
# attention output is constrained back to REPLICATED before the out
# projection, which reconstructs the exact per-shard values via
# all-gather. No contraction dimension is ever split, so XLA never
# introduces a partial-sum all-reduce whose float additions could
# reassociate — tp=N runs the same arithmetic as tp=1, chunked by
# head.


def init_kv_cache(
    cfg, batch: int, max_len: int, quant: bool = False
) -> Dict[str, jax.Array]:
    """Fixed-size cache buffers; dtype follows compute dtype. Works for
    any family config with n_layers/n_heads/head_dim (GPT has no GQA,
    so its KV head count is n_heads).

    quant=True stores K/V as symmetric per-vector int8 (+ one bf16
    scale per [position, head]) — the fp8-KV-cache idea of serving
    stacks (vLLM), sized for TPU HBM: cache bytes drop ~2x (int8 +
    1/hd scale overhead vs bf16), and decode attention, which is
    bound on reading the whole cache every step, reads half the
    bytes. Dequantization fuses into the attention einsum's loads.
    Opt-in: exact-parity paths (tests, PPO behavior-policy concerns)
    keep the full-precision default. A latent model's bank is
    `init_latent_cache`'s."""
    if getattr(cfg, "latent", False):
        if quant:
            raise NotImplementedError("a latent cache is not quantized")
        return init_latent_cache(cfg, batch, max_len)
    kv_heads = getattr(cfg, "n_kv_heads", cfg.n_heads)
    shape = (cfg.n_layers, batch, max_len, kv_heads, cfg.head_dim)
    if not quant:
        return {
            "k": jnp.zeros(shape, cfg.dtype),
            "v": jnp.zeros(shape, cfg.dtype),
        }
    scale_shape = shape[:-1] + (1,)
    # bf16 scales: the quantum is 1/127 of the vector max, so the
    # scale's own 2^-8 relative error is noise — and f32 scales
    # would double the overhead at small head_dims
    return {
        "k": jnp.zeros(shape, jnp.int8),
        "v": jnp.zeros(shape, jnp.int8),
        "k_scale": jnp.zeros(scale_shape, jnp.bfloat16),
        "v_scale": jnp.zeros(scale_shape, jnp.bfloat16),
    }


def _kv_quantize(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Symmetric per-vector int8: one scale per [..., head] vector
    (max|x|/127). Same formulation as ops/quantization.py's row
    scheme, at KV granularity."""
    scale = jnp.max(
        jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True
    ) / 127.0
    scale = jnp.maximum(scale, 1e-8)
    q = jnp.clip(
        jnp.round(x.astype(jnp.float32) / scale), -127, 127
    ).astype(jnp.int8)
    return q, scale


def _cached_attention(q, layer_cache, q_positions, scale, window=0):
    """q [B,S,H,hd] attends over the whole cache [B,M,KV,hd] under the
    causal position mask (cache col j visible to query at position p
    iff j <= p). Unwritten cache slots are masked out by the same rule.
    GQA runs as a grouped einsum against the UNEXPANDED cache — no
    n_rep-times repeat of the K/V buffers per step. Quantized caches
    dequantize here (int8 * per-vector scale), where XLA fuses the
    multiply into the einsum's cache loads."""
    k_cache, v_cache = layer_cache["k"], layer_cache["v"]
    if "k_scale" in layer_cache:
        k_cache = (
            k_cache.astype(q.dtype)
            * layer_cache["k_scale"].astype(q.dtype)
        )
        v_cache = (
            v_cache.astype(q.dtype)
            * layer_cache["v_scale"].astype(q.dtype)
        )
    b, s, h, hd = q.shape
    m = k_cache.shape[1]
    kv = k_cache.shape[2]
    n_rep = h // kv
    qg = q.reshape(b, s, kv, n_rep, hd)
    scores = jnp.einsum(
        "bskrd,bmkd->bkrsm", qg, k_cache,
        preferred_element_type=jnp.float32,
    ) * scale
    cols = jnp.arange(m)[None, None, None, None, :]   # [1,1,1,1,M]
    rows = q_positions[:, None, None, :, None]        # [B,1,1,S,1]
    if window:
        # a window layer: query at position p sees p - window < j <= p
        scores = jnp.where(
            (cols <= rows) & (cols > rows - window), scores, -jnp.inf
        )
    else:
        scores = jnp.where(cols <= rows, scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkrsm,bmkd->bskrd", p, v_cache)
    return out.reshape(b, s, h, hd)


def _cache_write(cache_arr, update, start):
    """Write `update` [B,S,...] into `cache_arr` [B,M,...] at offset
    `start` — scalar (all rows same offset) or [B] per-row vector
    (vmapped dynamic_update_slice → scatter)."""
    # per-row dims = the M/S axis plus the trailing dims; the
    # index tuples below need nd-1 trailing zeros after the
    # offset entry
    nd = update.ndim - 1
    if getattr(start, "ndim", 0) == 1:
        return jax.vmap(
            lambda cr, ur, s: jax.lax.dynamic_update_slice(
                cr, ur.astype(cr.dtype), (s,) + (0,) * (nd - 1)
            )
        )(cache_arr, update, start)
    return jax.lax.dynamic_update_slice(
        cache_arr,
        update.astype(cache_arr.dtype),
        (0, start) + (0,) * (nd - 1),
    )


def _write_cache_and_attend(
    q, k, v, layer_cache, positions, start, head_dim,
    attn_impl: str = "auto",
    plain_causal: bool = False,
    mesh=None,
    window: int = 0,
    block: int = 0,
):
    """THE decode-specific core, shared by both family blocks: write
    this chunk's K/V into the cache at `start` and attend over the
    whole buffer under the position mask.

    `plain_causal` is the prefill fast path, asserted by the CALLER
    that owns the invariant (prefill(): start==0 and positions are a
    dense arange, so the chunk IS the entire valid cache prefix): the
    position-masked attention over the full [B, max_len] buffer
    (dense scores, max_len >> prompt wasted, no flash kernel) reduces
    to plain causal attention over the chunk — the Pallas flash
    kernel on TPU (ops/attention.dot_product_attention). Shape/type
    sniffing here would silently mis-handle future callers with
    padded or packed positions.

    `start` may be a scalar (all rows write at the same offset — the
    lockstep generate() path) or a [B] vector of per-row offsets (the
    continuous-batching path, rl/serve.py: every slot sits at its own
    length; _cache_write vmaps to a scatter).

    `layer_cache` is this layer's {"k","v"[,"k_scale","v_scale"]};
    quantized caches get the chunk's K/V int8-quantized on write and
    dequantized inside the masked attention.

    `mesh` (optional serving mesh) pins the GSPMD layout: q/k/v stay
    split on their head axis so the cache write and the per-head
    attention run shard-local, and the attention output is replicated
    (all-gather) before returning so every downstream op — out
    projection, MLP, logits — is the identical full-width program on
    every shard (the byte-parity argument at the top of this file).

    `block` > 0 (a block-diffusion model): a query sees every key up
    to the END of its own block of `block` positions."""
    q = constrain(q, mesh, None, None, SERVING_TP_AXIS, None)
    k = constrain(k, mesh, None, None, SERVING_TP_AXIS, None)
    v = constrain(v, mesh, None, None, SERVING_TP_AXIS, None)
    out_cache = dict(layer_cache)
    if "k_scale" in layer_cache:
        kq, ks = _kv_quantize(k)
        vq, vs = _kv_quantize(v)
        out_cache["k"] = _cache_write(layer_cache["k"], kq, start)
        out_cache["v"] = _cache_write(layer_cache["v"], vq, start)
        out_cache["k_scale"] = _cache_write(
            layer_cache["k_scale"], ks, start
        )
        out_cache["v_scale"] = _cache_write(
            layer_cache["v_scale"], vs, start
        )
    else:
        out_cache["k"] = _cache_write(layer_cache["k"], k, start)
        out_cache["v"] = _cache_write(layer_cache["v"], v, start)
    if plain_causal:
        from dlrover_tpu.ops.attention import dot_product_attention

        # honor an explicit 'reference', but soften 'flash' to 'auto':
        # a strict flash demand hard-fails on prompt lengths no block
        # size divides (fine to enforce at training seq lengths,
        # wrong to crash inference over) — auto still picks the flash
        # kernel whenever the prompt tiles
        impl = "reference" if attn_impl == "reference" else "auto"
        attn = dot_product_attention(
            q, k, v, causal=True, impl=impl, tp=_mesh_tp(mesh),
            mesh=mesh, window=window, block=block,
        )
    else:
        attn = _cached_attention(
            q, out_cache,
            _block_end(positions, block) if block else positions,
            float(head_dim) ** -0.5, window=window,
        )
    attn = constrain(attn, mesh)
    return attn, out_cache


def _block(
    cfg: LlamaConfig,
    x: jax.Array,            # [B, S, D]
    layer_params: Params,
    layer_cache: Dict[str, jax.Array],  # per-layer k/v(+scales)
    positions: jax.Array,    # [B, S] global positions of x's tokens
    start,                   # scalar: cache slot of x's first token
    plain_causal: bool = False,
    mesh=None,
    lora=None,               # (bank slices, idx, scale) or None
    kind=None,               # "full" | "window" | None (homogeneous)
    layer=None,              # this block's index among all layers
    experts=None,            # stacked dropless experts, or None
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One decoder block writing its K/V into the cache. Prefill is
    S=prompt_len/start=0; decode is S=1/start=pos. The projections,
    RoPE, residuals and MLP are llama._layer's own helpers — the cache
    write + position-masked attention are the only decode-specific
    parts. `_attn_qkv`/`_attn_residual` get mesh=None on purpose:
    their constraints speak the TRAINING axis names; the serving tp
    layout is pinned inside `_write_cache_and_attend`. `lora` carries
    one layer's stacked adapter bank slices for batched multi-adapter
    serving (see `_forward_cached`)."""
    lp = _compute_weights(cfg, layer_params)
    tp = _mesh_tp(mesh)
    with jax.named_scope("attn" if kind is None else "attn_" + kind):
        h = _rms_norm(x, layer_params["attn_norm"], cfg.norm_eps)
        q, k, v = _attn_qkv(
            cfg, None, h, lp, positions, lora=lora, tp=tp, kind=kind,
            qk_norms=_qk_norms(cfg, layer_params),
        )
        attn, layer_cache = _write_cache_and_attend(
            q, k, v, layer_cache, positions, start, cfg.head_dim,
            attn_impl=getattr(cfg, "attn_impl", "auto"),
            plain_causal=plain_causal,
            mesh=mesh,
            window=_window_of(cfg, kind),
            block=_block_of(cfg),
        )
        x = _attn_residual(cfg, None, x, attn, lp, lora=lora, tp=tp)
    with jax.named_scope("mlp"):
        x, _counts = _ffn_residual(
            cfg, x, layer_params, lp, tp, layer, experts
        )
    return x, layer_cache


def _block_gpt(
    cfg, x, lp, layer_cache, positions, start,
    plain_causal: bool = False,
    mesh=None,
    lora=None,  # rejected upstream (_check_adapters); kept for the
                # shared block-call signature
    kind=None, layer=None, experts=None,  # llama-only; likewise
):
    """GPT-2 pre-LN block with cache write — built from gpt.py's own
    helpers; the cache write + masked attention are the only
    decode-specific parts (positions are consumed at embedding time)."""
    from dlrover_tpu.models import gpt

    tp = _mesh_tp(mesh)
    q, k, v = gpt._attn_qkv(cfg, x, lp, tp=tp)
    attn, layer_cache = _write_cache_and_attend(
        q, k, v, layer_cache, positions, start, cfg.head_dim,
        attn_impl=getattr(cfg, "attn_impl", "auto"),
        plain_causal=plain_causal,
        mesh=mesh,
    )
    x = gpt._attn_residual(cfg, x, attn, lp, tp=tp)
    x = gpt._mlp_residual(cfg, x, lp, tp=tp)
    return x, layer_cache


# ---------------------------------------------------------------------------
# Latent attention (MLA). A token's keys and values are ONE normed
# vector c of `kv_lora_rank` numbers and one rotary key r of
# `qk_rope_head_dim` shared by every head; the cache holds (c, r) and
# nothing else, in one leaf `ckv` whose rows are `cfg.latent_width`
# wide (c, r, zeros up to whole 128-lane tiles). Two forms compute the
# same attention: EXPANDED (a prefill: k and v of every head are made
# from c, then plain causal attention, the flash kernel on a TPU) and
# ABSORBED (every step over the cache: the head's key matrix is
# multiplied into its query, so scores and value sums are taken
# against the cached rows themselves and K and V never exist).
# ---------------------------------------------------------------------------


def init_latent_cache(cfg, batch: int, max_len: int):
    """The dense bank of a latent model: `[L, B, M, W]`."""
    shape = (cfg.n_layers, batch, max_len, cfg.latent_width)
    return {"ckv": jnp.zeros(shape, cfg.dtype)}


def init_latent_pool(cfg, n_pages: int, page_size: int):
    """The latent class of page pool: `[L, n_pages, page_size, W]`,
    one row a token and layer that is both key and value. W pads the
    latent and the rotary key (512 + 64) to 640, whole lane tiles, so
    that the kernel copies a page as it lies; page 0 is the trash page
    as in `init_page_pool`."""
    shape = (cfg.n_layers, n_pages, page_size, cfg.latent_width)
    return {"ckv": jnp.zeros(shape, cfg.dtype)}


def _leaf(cache):
    """Any one leaf of a bank or pool (their leading dims agree)."""
    return next(iter(cache.values()))


def _latent_qkv(cfg, h, layer_params, lp, positions, tp: int = 1):
    """(q_nope [B,S,H,nope], q_rope [B,S,H,rope] rotated, row [B,S,W]
    as the cache holds it: the normed latent, the rotated shared key,
    zeros)."""
    b, s, _ = h.shape
    H, cr = cfg.n_heads, cfg.kv_lora_rank
    nope, rope = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    spec = cfg.rope_of("full")
    cq = _rms_norm(
        matmul_any(h, lp["wq_a"], tp=tp), layer_params["q_norm"],
        cfg.norm_eps,
    )
    q = matmul_any(cq, lp["wq_b"], tp=tp).reshape(b, s, H, nope + rope)
    q_nope = q[..., :nope]
    q_rope = _rope(q[..., nope:], positions, spec)
    ckv = matmul_any(h, lp["wkv_a"], tp=tp)
    c = _rms_norm(ckv[..., :cr], layer_params["kv_norm"], cfg.norm_eps)
    r = _rope(ckv[..., None, cr:], positions, spec)[:, :, 0]
    pad = cfg.latent_width - cr - rope
    row = jnp.concatenate(
        [c, r, jnp.zeros((b, s, pad), c.dtype)], axis=-1
    )
    return q_nope, q_rope, row


def _latent_expanded(cfg, q_nope, q_rope, row, lp, tp: int = 1):
    """The expanded form over the chunk itself (a prefill from
    position 0): every head's k = [c W_uk, r] and v = c W_uv, plain
    causal attention -> [B, S, H * v_head_dim]."""
    from dlrover_tpu.ops.attention import dot_product_attention

    b, s, H, nope = q_nope.shape
    cr, rope = cfg.kv_lora_rank, cfg.qk_rope_head_dim
    c = row[..., :cr]
    k_nope = matmul_any(c, lp["wk_b"], tp=tp).reshape(b, s, H, nope)
    v = matmul_any(c, lp["wv_b"], tp=tp).reshape(b, s, H, cfg.v_head_dim)
    r = jnp.broadcast_to(
        row[:, :, None, cr:cr + rope], (b, s, H, rope)
    )
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate([k_nope, r], axis=-1)
    # the flash kernel takes q, k and v of one width (192 each at the
    # published sizes); another value width goes to XLA
    impl = "auto"
    if cfg.attn_impl == "reference" or v.shape[-1] != q.shape[-1]:
        impl = "reference"
    attn = dot_product_attention(
        q, k, v, causal=True, scale=cfg.attn_scale, impl=impl
    )
    return attn.reshape(b, s, H * cfg.v_head_dim)


def _latent_absorb_q(cfg, q_nope, q_rope, lp):
    """A head's query against the cached rows: [W_uk q_nope, q_rope,
    zeros] -> [B, S, H, W]."""
    b, s, H, nope = q_nope.shape
    with jax.named_scope("mla_absorb"):
        wk = lp["wk_b"].reshape(cfg.kv_lora_rank, H, nope)
        q_lat = jnp.einsum("bshd,chd->bshc", q_nope, wk)
    pad = cfg.latent_width - cfg.kv_lora_rank - cfg.qk_rope_head_dim
    return jnp.concatenate(
        [q_lat, q_rope, jnp.zeros((b, s, H, pad), q_lat.dtype)], axis=-1
    )


def _latent_absorb_out(cfg, u, lp):
    """The heads' sums of latents [B, S, H, cr] through their value
    matrices -> [B, S, H * v_head_dim]."""
    b, s, H, cr = u.shape
    with jax.named_scope("mla_absorb"):
        wv = lp["wv_b"].reshape(cr, H, cfg.v_head_dim)
        o = jnp.einsum("bshc,chd->bshd", u, wv)
    return o.reshape(b, s, H * cfg.v_head_dim)


def _block_latent(
    cfg, x, layer_params, layer_cache, positions, start,
    plain_causal: bool = False, mesh=None, lora=None, kind=None,
    layer=None, experts=None,
):
    """`_block` for latent attention over a dense bank: the chunk's
    (c, r) rows are written at `start`; a prefill from 0 attends in
    the expanded form, everything else in the absorbed form over the
    bank (`pa.latent_scores_and_sums`: what the latent pool's
    reference runs on the gathered pages, operation for operation)."""
    from dlrover_tpu.ops import paged_attention as pa

    lp = _compute_weights(cfg, layer_params)
    tp = _mesh_tp(mesh)
    scope = "attn_latent_prefill" if plain_causal else "attn_latent_decode"
    with jax.named_scope(scope):
        h = _rms_norm(x, layer_params["attn_norm"], cfg.norm_eps)
        q_nope, q_rope, row = _latent_qkv(
            cfg, h, layer_params, lp, positions, tp
        )
        layer_cache = {
            "ckv": _cache_write(layer_cache["ckv"], row, start)
        }
        if plain_causal:
            attn = _latent_expanded(cfg, q_nope, q_rope, row, lp, tp)
        else:
            u = pa.latent_scores_and_sums(
                _latent_absorb_q(cfg, q_nope, q_rope, lp),
                layer_cache["ckv"], positions, cfg.attn_scale,
                cfg.kv_lora_rank,
            )
            attn = _latent_absorb_out(cfg, u, lp)
        x = x + matmul_any(attn, lp["wo"], tp=tp)
    with jax.named_scope("mlp"):
        x, _counts = _ffn_residual(
            cfg, x, layer_params, lp, tp, layer, experts
        )
    return x, layer_cache


def _block_latent_paged(
    cfg, x, layer_params, pool, layer, table, positions, mesh=None,
    lora=None, kind=None, abs_layer=None, experts=None,
):
    """`_block_paged` for latent attention: the chunk's rows are
    scattered into the slot's pages of layer `layer` and the queries
    attend in the absorbed form over them: one query a slot through
    `ops/paged_attention.latent_paged_attention` (the kernel on a TPU,
    the gathered view elsewhere), more of them over the gathered
    view."""
    from dlrover_tpu.ops import paged_attention as pa

    lp = _compute_weights(cfg, layer_params)
    tp = _mesh_tp(mesh)
    with jax.named_scope("attn_latent_decode"):
        h = _rms_norm(x, layer_params["attn_norm"], cfg.norm_eps)
        q_nope, q_rope, row = _latent_qkv(
            cfg, h, layer_params, lp, positions, tp
        )
        arr = pool["ckv"]
        ps = arr.shape[2]
        pids = jnp.take_along_axis(table, positions // ps, axis=1)
        with jax.named_scope("kv_pool_writeback"):
            pool = {"ckv": arr.at[layer, pids, positions % ps].set(
                row.astype(arr.dtype)
            )}
        qc = _latent_absorb_q(cfg, q_nope, q_rope, lp)
        if qc.shape[1] == 1:
            impl = "reference" if cfg.attn_impl == "reference" else "auto"
            with jax.named_scope("paged_attn"):
                u = pa.latent_paged_attention(
                    qc[:, 0], pool, table, positions[:, 0] + 1,
                    cfg.attn_scale, cfg.kv_lora_rank, layer=layer,
                    impl=impl,
                )[:, None]
        else:
            with jax.named_scope("kv_pool_slice"):
                view = _paged_view(pool, layer, table)
            u = pa.latent_scores_and_sums(
                qc, view["ckv"], positions, cfg.attn_scale,
                cfg.kv_lora_rank,
            )
        x = x + matmul_any(
            _latent_absorb_out(cfg, u, lp), lp["wo"], tp=tp)
    with jax.named_scope("mlp"):
        x, counts = _ffn_residual(
            cfg, x, layer_params, lp, tp, abs_layer, experts
        )
    if experts is None:
        return x, pool
    return x, pool, counts


def _is_gpt(cfg) -> bool:
    from dlrover_tpu.models.gpt import GptConfig

    return isinstance(cfg, GptConfig)


def _check_positional_capacity(cfg, max_len: int):
    """GPT's LEARNED position table hard-stops at max_seq_len: JAX
    clamps out-of-bounds gathers, so decoding past it would silently
    reuse wpe[-1] and emit garbage. RoPE (llama) computes any position,
    so no bound applies there."""
    if _is_gpt(cfg) and max_len > cfg.max_seq_len:
        raise ValueError(
            f"decode length {max_len} exceeds the GPT position table "
            f"(max_seq_len={cfg.max_seq_len}); positions would clamp "
            "and produce wrong logits"
        )


def _check_adapters(cfg, adapters):
    if adapters is not None and _is_gpt(cfg):
        raise ValueError(
            "multi-adapter serving targets the llama attention "
            "projections; GPT's fused qkv has no per-target bank"
        )


def _forward_cached(
    cfg, params, tokens, cache, positions, start,
    plain_causal: bool = False,
    mesh=None,
    adapters=None,
):
    """tokens [B,S] → logits [B,S,V], writing the cache at
    [start, start+S). Family dispatch: llama (RoPE/GQA/RMSNorm) or
    GPT-2 (learned positions, pre-LN, tied wte head).

    `adapters` (serving/adapters.py) enables batched multi-adapter
    LoRA: {"bank": per-target stacked arrays with leading [L, S]
    (wq_a [L, S, in, r], wq_b [L, S, r, out], …), "idx": [B] int32
    per-row cache slot, "scale": [S] f32}. The bank rides the layer
    scan's xs next to the params/cache, so each block gathers its own
    layer's [S, …] slices and adds the per-row delta inside the
    projections. When None the scan carries the EXACT pre-adapter
    pytree — the base program is structurally untouched."""
    _check_adapters(cfg, adapters)
    gpt = _is_gpt(cfg)
    if gpt:
        x = (
            params["wte"].astype(cfg.dtype)[tokens]
            + params["wpe"].astype(cfg.dtype)[positions]
        )
        block = _block_gpt
    else:
        x = params["embed"]["weight"].astype(cfg.dtype)[tokens]
        block = _block_latent if cfg.latent else _block

    kinds = _kinds(cfg)
    scanned_params, experts = _split_experts(cfg, params["layers"])
    # leading dense layers: a prologue before the scan (their leaves
    # have other shapes than the expert layers' and cannot be stacked
    # with them); the scan then runs over the rest of the bank
    lead = getattr(cfg, "first_k_dense", 0)
    lead_cache = []
    for j in range(lead):
        x, layer_cache = block(
            cfg, x,
            jax.tree_util.tree_map(
                lambda a: a[j], params["dense_layers"]),
            {name: arr[j] for name, arr in cache.items()},
            positions, start, plain_causal=plain_causal, mesh=mesh,
        )
        lead_cache.append(layer_cache)
    if lead:
        cache = {name: arr[lead:] for name, arr in cache.items()}

    def body(carry, inp):
        # one PERIOD of layers, written out; a homogeneous model's
        # period is its one layer
        h = carry
        period_params, period_cache, first, period_bank = inp
        caches = []
        for j, kind in enumerate(kinds):
            lora = None
            if adapters is not None:
                lora = (
                    _place(cfg, period_bank, j), adapters["idx"],
                    adapters["scale"],
                )
            h, layer_cache = block(
                cfg, h, _place(cfg, period_params, j),
                _place(cfg, period_cache, j), positions, start,
                plain_causal=plain_causal,
                mesh=mesh,
                lora=lora,
                kind=kind,
                layer=first + j,
                experts=experts,
            )
            caches.append(layer_cache)
        return h, _stack_places(cfg, caches)

    # the cache dict scans as a pytree: each layer body sees its own
    # {"k","v"[,"k_scale","v_scale"]} slice and emits the updated one
    n_layers = _leaf(cache).shape[0]
    xs = (
        _by_period(cfg, scanned_params),
        _by_period(cfg, dict(cache)),
        jnp.arange(0, n_layers, len(kinds), dtype=jnp.int32),
        None if adapters is None
        else _by_period(cfg, dict(adapters["bank"])),
    )
    with jax.named_scope("layers"):
        x, scanned = jax.lax.scan(body, x, xs)
    cache_new = scanned
    if len(kinds) > 1:
        cache_new = jax.tree_util.tree_map(
            lambda a: a.reshape((n_layers,) + a.shape[2:]), scanned
        )
    if lead:
        cache_new = {
            name: jnp.concatenate(
                [jnp.stack([c[name] for c in lead_cache]), arr]
            )
            for name, arr in cache_new.items()
        }
    if gpt:
        from dlrover_tpu.models.gpt import _layer_norm

        x = _layer_norm(
            x, params["lnf_g"], params["lnf_b"], cfg.norm_eps
        )
        head = params["wte"].astype(cfg.dtype).T
    else:
        x = _rms_norm(
            x, params["final_norm"]["scale"], cfg.norm_eps
        )
        head = _head_matrix(cfg, params)
    logits = matmul_any(x, head, tp=_mesh_tp(mesh)).astype(jnp.float32)
    return logits, cache_new


def prefill(
    cfg: LlamaConfig,
    params: Params,
    tokens: jax.Array,  # [B, P]
    cache: Dict[str, jax.Array],
    mesh=None,
    adapters=None,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Fill the cache from a prompt; returns (last-token logits, cache)."""
    b, p = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(p), (b, p))
    # prefill owns the fast-path invariant: start 0, dense arange
    # positions -> the chunk is the whole valid prefix
    logits, cache = _forward_cached(
        cfg, params, tokens, cache, positions, 0,
        plain_causal=p > 1,
        mesh=mesh,
        adapters=adapters,
    )
    return logits[:, -1], cache


def decode_step(
    cfg: LlamaConfig,
    params: Params,
    token: jax.Array,   # [B] current token
    cache: Dict[str, jax.Array],
    pos,                # position of `token`: scalar, or [B] per slot
    mesh=None,
    adapters=None,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One cached step → (next-token logits [B,V], updated cache).

    Scalar `pos` is the lockstep path (all rows at the same length);
    a [B] vector decodes every row at its OWN position — the
    continuous-batching path (rl/serve.py), where each slot carries a
    different sequence."""
    b = token.shape[0]
    pos = jnp.asarray(pos, jnp.int32)
    if pos.ndim == 1:
        positions = pos[:, None]
    else:
        positions = jnp.broadcast_to(pos, (b, 1))
    logits, cache = _forward_cached(
        cfg, params, token[:, None], cache, positions, pos, mesh=mesh,
        adapters=adapters,
    )
    return logits[:, 0], cache


def verify_step(
    cfg: LlamaConfig,
    params: Params,
    tokens: jax.Array,  # [B, S]: carry token + S-1 draft tokens
    cache: Dict[str, jax.Array],
    pos,                # [B] position of tokens[:, 0] per slot
    mesh=None,
    adapters=None,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Batched speculative verify: run the target model over all S
    positions per row in ONE compiled forward (the speculative
    decoding counterpart of decode_step — S=K+1 instead of S=1).

    Row b's tokens occupy global positions [pos[b], pos[b]+S); their
    K/V is written there first, then every query attends the whole
    buffer under the causal position mask — so draft token j attends
    the carry token and drafts 1..j exactly as if they had been
    decoded one step at a time. logits[:, j] is therefore the target
    distribution for the token FOLLOWING tokens[:, j], for every j at
    once: one memory-bandwidth-bound pass prices K drafts plus the
    bonus position.

    S is static per program (one trace per draft width); pos is a
    traced [B] vector, so mixed-length slots share the compile. The
    caller guarantees pos + S <= the cache buffer length (the serving
    engine over-allocates its bank by the draft width so the write
    window can never clamp near max_len)."""
    b, s = tokens.shape
    pos = jnp.asarray(pos, jnp.int32)
    positions = pos[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]
    logits, cache = _forward_cached(
        cfg, params, tokens, cache, positions, pos, mesh=mesh,
        adapters=adapters,
    )
    return logits, cache


def spec_accept_greedy(
    logits: jax.Array,  # [B, K+1, V] verify logits
    drafts: jax.Array,  # [B, K] proposed draft tokens
    draft_len: jax.Array,  # [B] valid drafts per row (<= K)
) -> Tuple[jax.Array, jax.Array]:
    """Greedy acceptance: draft j survives while it equals the target
    argmax at its position (and every earlier draft survived). Returns
    (m, extra): m accepted drafts per row plus the target's own token
    at the first divergence (the 'bonus' token when all K accepted) —
    so the emitted prefix is exactly the target's greedy continuation,
    whatever the drafter proposed."""
    k = drafts.shape[1]
    tgt = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [B, K+1]
    ok = (drafts == tgt[:, :k]) & (
        jnp.arange(k)[None, :] < draft_len[:, None]
    )
    m = jnp.cumprod(ok.astype(jnp.int32), axis=1).sum(axis=1)
    extra = jnp.take_along_axis(tgt, m[:, None], axis=1)[:, 0]
    return m, extra


def spec_accept_sampled(
    key: jax.Array,
    probs: jax.Array,   # [B, K+1, V] warped target probabilities
    drafts: jax.Array,  # [B, K]
    draft_len: jax.Array,  # [B]
) -> Tuple[jax.Array, jax.Array]:
    """Standard speculative rejection sampling, specialized to a
    DETERMINISTIC drafter (n-gram lookup proposes a point mass q):
    accept draft d_j with probability min(1, p_j(d_j)/q_j(d_j)) =
    p_j(d_j); on the first rejection sample the replacement from the
    residual norm(max(p_j - q_j, 0)) — p_j with d_j's mass removed,
    renormalized; when every draft survives, sample the bonus token
    from p_K+1 directly. The emitted marginal at each position is
    exactly p_j (p(d)·1[x=d] + (1-p(d))·p(x)1[x≠d]/(1-p(d)) = p(x)),
    so the output distribution is provably the target's — pinned by
    tests/test_serving_speculative.py's Monte-Carlo check.

    A rejected row always has residual mass: rejection means
    u >= p(d) with u < 1, so p(d) < 1 and the renormalizer 1 - p(d)
    is positive; rows with no rejection never read the residual."""
    b, kp1, v = probs.shape
    k = kp1 - 1
    ku, kr = jax.random.split(key)
    u = jax.random.uniform(ku, (b, k))
    p_draft = jnp.take_along_axis(
        probs[:, :k], drafts[..., None], axis=-1
    )[..., 0]
    ok = (u < p_draft) & (
        jnp.arange(k)[None, :] < draft_len[:, None]
    )
    m = jnp.cumprod(ok.astype(jnp.int32), axis=1).sum(axis=1)
    pm = jnp.take_along_axis(probs, m[:, None, None], axis=1)[:, 0]
    # the draft at the rejection index (pad column keeps the gather
    # in-bounds when m == K; `rejected` is False there anyway)
    drafts_p = jnp.concatenate(
        [drafts, jnp.zeros((b, 1), drafts.dtype)], axis=1
    )
    d_at_m = jnp.take_along_axis(drafts_p, m[:, None], axis=1)[:, 0]
    rejected = m < draft_len
    resid = jnp.where(
        rejected[:, None] & (jnp.arange(v)[None, :] == d_at_m[:, None]),
        0.0,
        pm,
    )
    # categorical renormalizes; zero-mass tokens become -inf logits
    extra = jax.random.categorical(kr, jnp.log(resid)).astype(
        jnp.int32
    )
    return m, extra


def prefill_into_slot(
    cfg: LlamaConfig,
    params: Params,
    prompt: jax.Array,  # [P] (pad tail beyond the real length is fine)
    cache: Dict[str, jax.Array],
    slot,
    mesh=None,
    adapters=None,
) -> Dict[str, jax.Array]:
    """Run a single-sequence prefill and install its K/V into row
    `slot` of a multi-slot cache — the admission step of continuous
    batching (rl/serve.py). `adapters` carries a 1-row idx vector for
    the admitted request's adapter slot (the prefill K/V must come
    from the adapted projections, or decode would attend a base-model
    prefix).

    Pad-tail correctness: cells beyond the prompt's true length hold
    pad-token K/V, but the decode mask (`cols <= pos`) hides every
    cell past the slot's current position, and generation overwrites
    them one by one — so they are never attended. The same argument
    covers stale cells left by the slot's previous occupant."""
    p = prompt.shape[0]
    if _leaf(cache).shape[2] < p:
        raise ValueError(
            f"prompt chunk {p} exceeds cache max_len "
            f"{_leaf(cache).shape[2]}"
        )
    mini = init_kv_cache(cfg, 1, p, quant="k_scale" in cache)
    _, mini = prefill(
        cfg, params, prompt[None], mini, mesh=mesh, adapters=adapters
    )
    out = {}
    for name, arr in cache.items():
        out[name] = jax.lax.dynamic_update_slice(
            arr,
            mini[name].astype(arr.dtype),
            (0, slot) + (0,) * (arr.ndim - 2),
        )
    return out


# ---------------------------------------------------------------------------
# prefix-pool primitives (serving/engine.py's admission-time prefix cache)
#
# The pool is a second KV bank beside the slot bank whose rows hold
# EXACT (unquantized) K/V for block-aligned prompt prefixes. Keeping
# the pool exact is what makes cached admission token-for-token equal
# to cold prefill even with an int8 slot bank: install re-quantizes
# the exact values with the same _kv_quantize the cold write path
# uses, so the slot bytes come out identical either way (whereas a
# quantized pool would chain dequantize→attend→requantize drift into
# the suffix).
#
# All four helpers are shape-static in everything but scalars
# (slot/row/start), so the engine compiles each exactly once per
# suffix bucket — the same log2(max_len) discipline as prefill.
# ---------------------------------------------------------------------------


def exact_row_cache(cfg, max_len: int) -> Dict[str, jax.Array]:
    """A single-sequence full-precision cache row [L, 1, M, KV, hd] —
    the working buffer admission prefills into and publishes from."""
    return init_kv_cache(cfg, 1, max_len, quant=False)


def prefill_exact_row(
    cfg, params, prompt: jax.Array, max_len: int, mesh=None,
    adapters=None,
) -> Dict[str, jax.Array]:
    """Cold-admission prefill: run `prompt` [P] (pad tail fine) into a
    fresh exact row. The forward is identical to prefill_into_slot's
    (plain-causal attention never reads the cache, so an unquantized
    target changes nothing about the computed K/V). `adapters` (1-row
    idx) serves the paged cold-admit of an adaptered request; rows
    bound for the SHARED prefix pool must pass None — published
    prefixes are base-model K/V by contract."""
    row = exact_row_cache(cfg, max_len)
    _, row = prefill(
        cfg, params, prompt[None], row, mesh=mesh, adapters=adapters
    )
    return row


def prefill_suffix_row(
    cfg, params, suffix: jax.Array, row: Dict[str, jax.Array], start,
    mesh=None,
) -> Dict[str, jax.Array]:
    """Warm-admission prefill: extend an exact row that already holds
    K/V for positions [0, start) with `suffix` [S] at positions
    [start, start+S). Suffix queries attend over the installed prefix
    AND the suffix itself through the position-masked cached-attention
    path (each chunk position is written before it is read).

    `start` is a traced scalar — one compiled program per suffix
    bucket, any prefix length. The caller guarantees start + S fits
    the row (engine clamps the match depth so the bucket fits)."""
    s = suffix.shape[0]
    positions = (jnp.asarray(start, jnp.int32) + jnp.arange(s))[None]
    _, row = _forward_cached(
        cfg, params, suffix[None], row, positions, start, mesh=mesh
    )
    return row


def prefill_chunk_into_slot(
    cfg,
    params,
    chunk: jax.Array,  # [C] REAL tokens only — no pad tail
    cache: Dict[str, jax.Array],
    slot,
    start,
    mesh=None,
    adapters=None,
) -> Dict[str, jax.Array]:
    """Resume a slot's prefill at an arbitrary write frontier: run
    `chunk` at positions [start, start+C), writing K/V straight into
    row `slot` of the multi-slot bank. The chunked-admission twin of
    `prefill_into_slot` — instead of one synchronous whole-prompt
    prefill, the engine calls this once per budgeted chunk until the
    frontier reaches the prompt end.

    Byte-exactness of the resume is the `prefill_suffix_row`
    argument: chunk queries attend over the already-installed cells
    [0, start) AND the chunk itself through the position-masked
    cached-attention path (each chunk position is written before it
    is read), so the K/V this writes equals what one blocking prefill
    would have written — exactly, for exact banks. An int8 bank
    dequantizes the earlier chunks' cells where blocking prefill
    attends full-precision activations, so chunked int8 prefill is
    self-consistent but not bit-par with blocking (DEVIATIONS §19).

    `slot` and `start` are traced scalars; C is static (the engine
    quantizes chunk lengths down to powers of two, so the tail costs
    log2(prefill_chunk) compiles, never one per remainder). The
    chunk carries no pad tail by contract — every cell written is a
    real prompt cell, which is what lets the next chunk resume at
    start+C without a masked garbage gap."""
    c = chunk.shape[0]
    row = {}
    for name, arr in cache.items():
        size = (arr.shape[0], 1) + arr.shape[2:]
        row[name] = jax.lax.dynamic_slice(
            arr, (0, slot) + (0,) * (arr.ndim - 2), size
        )
    positions = (jnp.asarray(start, jnp.int32) + jnp.arange(c))[None]
    _, row = _forward_cached(
        cfg, params, chunk[None], row, positions, start, mesh=mesh,
        adapters=adapters,
    )
    out = {}
    for name, arr in cache.items():
        out[name] = jax.lax.dynamic_update_slice(
            arr,
            row[name].astype(arr.dtype),
            (0, slot) + (0,) * (arr.ndim - 2),
        )
    return out


def install_exact_row(
    cache: Dict[str, jax.Array], row: Dict[str, jax.Array], slot
) -> Dict[str, jax.Array]:
    """Write an exact row into slot `slot` of the (possibly int8)
    slot bank, quantizing on the way in when the bank is quantized —
    the same per-vector scheme the cold write path applies, on the
    same exact values, so the installed bytes match a cold prefill's.
    Whole-row write: cells beyond the valid prefix carry garbage that
    the decode position mask hides until generation overwrites them
    (the prefill_into_slot pad-tail argument)."""
    if "k_scale" in cache:
        kq, ks = _kv_quantize(row["k"])
        vq, vs = _kv_quantize(row["v"])
        src = {"k": kq, "v": vq, "k_scale": ks, "v_scale": vs}
    else:
        src = row
    out = {}
    for name, arr in cache.items():
        out[name] = jax.lax.dynamic_update_slice(
            arr,
            src[name].astype(arr.dtype),
            (0, slot) + (0,) * (arr.ndim - 2),
        )
    return out


def pool_take_row(
    pool: Dict[str, jax.Array], row
) -> Dict[str, jax.Array]:
    """Copy pool row `row` out as a single-sequence exact cache."""
    out = {}
    for name, arr in pool.items():
        size = (arr.shape[0], 1) + arr.shape[2:]
        out[name] = jax.lax.dynamic_slice(
            arr, (0, row) + (0,) * (arr.ndim - 2), size
        )
    return out


def pool_put_row(
    pool: Dict[str, jax.Array], row_cache: Dict[str, jax.Array], row
) -> Dict[str, jax.Array]:
    """Publish an exact row into pool row `row` (whole-row write)."""
    out = {}
    for name, arr in pool.items():
        out[name] = jax.lax.dynamic_update_slice(
            arr,
            row_cache[name].astype(arr.dtype),
            (0, row) + (0,) * (arr.ndim - 2),
        )
    return out


# ---------------------------------------------------------------------------
# paged KV primitives (serving/engine.py's kv_layout="paged")
#
# The paged layout replaces the dense per-slot bank [L, B, M, KV, hd]
# with a global page POOL [L, n_pages, page_size, KV, hd] plus a
# per-slot page TABLE [B, P] of physical page ids (P = M / page_size;
# logical cell m of slot b lives at pool[:, table[b, m // ps], m % ps]).
# Slots no longer own M cells each — they own only the pages their
# request actually touches, and radix prefix hits SHARE pages by
# pointing two tables at the same physical ids (ref-counted host-side
# by serving/paged_kv.PageAllocator; copy-on-write when a shared page
# is appended into).
#
# Byte parity with the dense bank is the design invariant: the paged
# forward gathers each layer's pages into the dense [B, M, KV, hd]
# view and runs the IDENTICAL `_cached_attention` — same einsums, same
# mask, same softmax — so `kv_layout="paged"` produces bit-identical
# tokens to `kv_layout="dense"`. Cells a table maps to the trash page
# (or stale pages) surface garbage the position mask zeroes exactly.
# On a real TPU the S==1 decode step swaps the gathered view for the
# Pallas paged-attention kernel (ops/paged_attention.py) that streams
# physical pages without materializing the view.
#
# The pool is stored stacked over layers and WALKED stacked: the layer
# loop carries it whole, a layer scatters its rows at
# pool[layer, page, cell] and attends over pool[layer, table] (one
# gather, or the kernel's index map), so a forward moves the rows it
# writes and the pages it reads — never a layer's, let alone the
# pool's, worth of bytes.
# ---------------------------------------------------------------------------


def init_page_pool(
    cfg, n_pages: int, page_size: int, quant: bool = False
) -> Dict[str, jax.Array]:
    """The global page pool: [L, n_pages, page_size, KV, hd] (+ per
    [page, cell, head] bf16 scales when quant — the same per-vector
    int8 scheme as init_kv_cache, so quantized bytes match the dense
    bank's for the same values). Page id 0 is the TRASH page by
    engine convention: retired/done slots' table rows point there so
    frozen rewrites land somewhere no live table reads. Every paged
    forward takes the pool in this stacked form and addresses a
    layer by its index (`_forward_paged`). A latent model's pool is
    `init_latent_pool`'s."""
    if getattr(cfg, "latent", False):
        if quant:
            raise NotImplementedError("a latent cache is not quantized")
        return init_latent_pool(cfg, n_pages, page_size)
    kv_heads = getattr(cfg, "n_kv_heads", cfg.n_heads)
    shape = (cfg.n_layers, n_pages, page_size, kv_heads, cfg.head_dim)
    if not quant:
        return {
            "k": jnp.zeros(shape, cfg.dtype),
            "v": jnp.zeros(shape, cfg.dtype),
        }
    scale_shape = shape[:-1] + (1,)
    return {
        "k": jnp.zeros(shape, jnp.int8),
        "v": jnp.zeros(shape, jnp.int8),
        "k_scale": jnp.zeros(scale_shape, jnp.bfloat16),
        "v_scale": jnp.zeros(scale_shape, jnp.bfloat16),
    }


def init_hybrid_pools(
    cfg, n_pages_full: int, n_pages_window: int, page_size: int
) -> Dict[str, Dict[str, jax.Array]]:
    """Two CLASSES of pages for a model that mixes window and full
    layers: `pool["full"]` `[L_full, n_pages_full, page, KV, hd]` keeps
    every position of its slots, `pool["window"]` `[L_win,
    n_pages_window, ...]` only the pages that still hold one of a
    slot's last `sliding_window` positions (a ring a slot: logical
    page p at ring entry p % R; the host frees the pages behind the
    window between dispatches, serving/engine.py). Page 0 of each
    class is its trash page."""
    kv_heads, hd = cfg.n_kv_heads, cfg.head_dim

    def one(kind, n_pages):
        shape = (cfg.layers_of(kind), n_pages, page_size, kv_heads, hd)
        return {
            "k": jnp.zeros(shape, cfg.dtype),
            "v": jnp.zeros(shape, cfg.dtype),
        }

    return {
        "full": one("full", n_pages_full),
        "window": one("window", n_pages_window),
    }


def _paged_view(
    pool: Dict[str, jax.Array], layer, table: jax.Array
) -> Dict[str, jax.Array]:
    """Gather layer `layer`'s pages of the stacked pool into the dense
    [B, M, KV, ...] view (M = P * page_size) — the shape
    `_cached_attention` attends over. ONE gather per leaf
    (`arr[layer, table]`, never `arr[layer][table]`: no layer is
    sliced out first); whatever dead pages hold is masked exactly."""
    out = {}
    for name, arr in pool.items():
        g = arr[layer, table]  # [B, P, page_size, KV, ...]
        out[name] = g.reshape((g.shape[0], -1) + g.shape[3:])
    return out


def _write_pages_and_attend(
    q, k, v, pool, layer, table, positions, head_dim, mesh=None,
    attn_impl: str = "auto", window: int = 0, block: int = 0,
    carried=None,
):
    """The paged counterpart of `_write_cache_and_attend`, on the
    STACKED pool and a traced layer index: scatter this chunk's K/V
    into the slot's PAGES of that layer (row b, chunk position s →
    pool[layer, table[b, pos//ps], pos%ps]) and attend over the
    layer's pages with the identical position-masked attention. The
    pool is the layer loop's carry, so the scatter of B*S rows
    updates it in place, and both attention paths address the layer
    by its index: nothing slices a layer out or stacks it back.

    Within a chunk a row's positions are distinct, and across rows
    live tables never share a writable page (the allocator CoWs
    shared pages before handing them to a writer) — the only scatter
    collisions are done/retired rows parked on the trash page, whose
    cells no live mask ever admits. Quantized pools quantize the
    chunk with the same `_kv_quantize` as the dense write path, so
    the stored bytes are identical either way.

    `block` > 0 (a block-diffusion model): the chunk is ONE block of
    `block` positions a slot, starting on a block boundary; every
    query of it sees the pool up to the end of the block, its own
    keys, just written, among them (`_attend_block`). With `carried`
    ([B] bool) the chunk is TWO blocks a slot: the finished block
    before it, run once more so that its final keys and values land
    in its cells, and then the block. Where `carried[b]` is false the
    slot carries nothing and its first `block` rows are dead: they
    write to the trash page."""
    q = constrain(q, mesh, None, None, SERVING_TP_AXIS, None)
    k = constrain(k, mesh, None, None, SERVING_TP_AXIS, None)
    v = constrain(v, mesh, None, None, SERVING_TP_AXIS, None)
    ps = pool["k"].shape[2]
    if window:
        # a window layer's table is the slot's RING: logical page p
        # lives at entry p % R (the host keeps the entries of the
        # pages inside the window, serving/engine.py)
        if q.shape[1] != 1 or "k_scale" in pool:
            raise NotImplementedError(
                "window layers over paged KV serve one query a slot "
                "from an unquantized pool"
            )
        pids = jnp.take_along_axis(
            table, (positions // ps) % table.shape[1], axis=1
        )
    else:
        pids = jnp.take_along_axis(table, positions // ps, axis=1)
    if carried is not None:
        dead = ~carried[:, None] & (
            jnp.arange(positions.shape[1]) < block)[None, :]
        pids = jnp.where(dead, 0, pids)
    offs = positions % ps
    out_pool = dict(pool)
    if "k_scale" in pool:
        kq, ks = _kv_quantize(k)
        vq, vs = _kv_quantize(v)
        writes = {"k": kq, "v": vq, "k_scale": ks, "v_scale": vs}
    else:
        writes = {"k": k, "v": v}
    with jax.named_scope("kv_pool_writeback"):
        for name, upd in writes.items():
            arr = pool[name]
            out_pool[name] = arr.at[layer, pids, offs].set(
                upd.astype(arr.dtype)
            )
    s = q.shape[1]
    if block:
        return _attend_block(
            q, out_pool, layer, table, positions, head_dim, block,
            attn_impl,
        ), out_pool
    if window:
        from dlrover_tpu.ops import paged_attention as pa

        q1 = q[:, 0]
        takes = attn_impl != "reference" and pa.use_kernel(
            q1, out_pool, table, tp=_mesh_tp(mesh)
        )
        with jax.named_scope("paged_attn"):
            attn = pa.paged_attention(
                q1, out_pool, table, positions[:, 0] + 1,
                scale=float(head_dim) ** -0.5,
                impl="kernel" if takes else "reference",
                mesh=mesh, layer=layer, window=window,
            )
        return constrain(attn[:, None], mesh), out_pool
    # attn_impl='reference' is the byte-parity oracle knob: it pins
    # the gathered-view formulation even where use_kernel would take
    # the Pallas path (real TPU, or forced interpret kernels)
    if s == 1 and attn_impl != "reference":
        from dlrover_tpu.ops import paged_attention as pa

        q1 = q[:, 0]
        if pa.use_kernel(q1, out_pool, table, tp=_mesh_tp(mesh)):
            lengths = positions[:, 0] + 1
            with jax.named_scope("paged_attn"):
                attn = pa.paged_attention(
                    q1, out_pool, table, lengths,
                    scale=float(head_dim) ** -0.5, impl="kernel",
                    mesh=mesh, layer=layer,
                )
            return constrain(attn[:, None], mesh), out_pool
    with jax.named_scope("kv_pool_slice"):
        view = _paged_view(out_pool, layer, table)
    attn = _cached_attention(
        q, view, positions, float(head_dim) ** -0.5
    )
    attn = constrain(attn, mesh)
    return attn, out_pool


def _attend_block(
    q, pool, layer, table, positions, head_dim, block, attn_impl
):
    """One diffusion block a slot over the paged pool: q `[B, block,
    H, hd]` at positions start .. start + block - 1, every query
    seeing the cells 0 .. start + block - 1; or `[B, 2 * block, H,
    hd]`, the carried block before it first, whose queries see the
    cells before `start`. With one length a slot the queries are
    further query rows of their K/V head, so the paged walk takes
    them as `S * H` heads, the carried block's a group of rows one
    block shorter (`paged_attention(..., block=)`: the kernel on the
    chip, the gathered view under the same mask off it or where the
    kernel refuses the shapes)."""
    from dlrover_tpu.ops import paged_attention as pa

    if "k_scale" in pool:
        raise NotImplementedError(
            "a block-diffusion model is served from an unquantized pool"
        )
    with jax.named_scope("attn_block"):
        return pa.paged_attention(
            q, pool, table, positions[:, -1] + 1,
            scale=float(head_dim) ** -0.5,
            impl="reference" if attn_impl == "reference" else "auto",
            layer=layer, block=block,
        )


def _block_paged(
    cfg, x, layer_params, pool, layer, table, positions, mesh=None,
    lora=None, kind=None, abs_layer=None, experts=None, carried=None,
):
    """Llama block over paged KV — identical projections/residuals to
    `_block` (including the per-slot `lora` deltas); only the cache
    write + view differ. `pool` is the whole stacked pool and `layer`
    this block's (traced) index into it. `carried`: see
    `_write_pages_and_attend`."""
    lp = _compute_weights(cfg, layer_params)
    tp = _mesh_tp(mesh)
    with jax.named_scope("attn" if kind is None else "attn_" + kind):
        h = _rms_norm(x, layer_params["attn_norm"], cfg.norm_eps)
        q, k, v = _attn_qkv(
            cfg, None, h, lp, positions, lora=lora, tp=tp, kind=kind,
            qk_norms=_qk_norms(cfg, layer_params),
        )
        attn, pool = _write_pages_and_attend(
            q, k, v, pool, layer, table, positions, cfg.head_dim,
            mesh=mesh,
            attn_impl=getattr(cfg, "attn_impl", "auto"),
            window=_window_of(cfg, kind),
            block=_block_of(cfg), carried=carried,
        )
        x = _attn_residual(cfg, None, x, attn, lp, lora=lora, tp=tp)
    with jax.named_scope("mlp"):
        x, counts = _ffn_residual(
            cfg, x, layer_params, lp, tp, abs_layer, experts
        )
    if experts is None:
        return x, pool
    return x, pool, counts


def _block_gpt_paged(
    cfg, x, lp, pool, layer, table, positions, mesh=None, lora=None,
    kind=None, abs_layer=None, experts=None,  # llama-only
):
    from dlrover_tpu.models import gpt

    tp = _mesh_tp(mesh)
    q, k, v = gpt._attn_qkv(cfg, x, lp, tp=tp)
    attn, pool = _write_pages_and_attend(
        q, k, v, pool, layer, table, positions, cfg.head_dim,
        mesh=mesh,
        attn_impl=getattr(cfg, "attn_impl", "auto"),
    )
    x = gpt._attn_residual(cfg, x, attn, lp, tp=tp)
    x = gpt._mlp_residual(cfg, x, lp, tp=tp)
    return x, pool


def _forward_paged(
    cfg, params, tokens, pool, table, positions, mesh=None,
    adapters=None, table_win=None, carried=None,
):
    """tokens [B, S] → logits [B, S, V] over the paged pool. The
    pool goes through the layer scan as a CARRY, whole and stacked
    (`[L, n_pages, page_size, KV, hd]` leaves); the scan runs over
    PERIODS of layers (a homogeneous model: periods of one), its xs
    are the periods' parameters, their first layers' indices and the
    optional `adapters` bank, and each layer writes and reads its own
    part of the pool by its index. The table is shared by every layer.

    A model that mixes window and full layers brings two classes of
    pages, `pool["full"]` and `pool["window"]`
    (`init_hybrid_pools`), each carried whole, and a second table:
    `table_win`, the slots' rings. A layer's index into its class's
    pool is its rank among the layers of its kind. Where the experts
    are routed without dropping, a third value comes back: int32[E],
    the routed pairs per expert summed over the layers.

    `carried` ([B] bool, a block-diffusion model's): the tokens are
    two blocks a slot, the finished block before it carried for its
    keys and values (`_write_pages_and_attend`) and then the block.
    The carried rows run the layers and nothing after them: logits
    [B, block, V], the block's alone."""
    _check_adapters(cfg, adapters)
    gpt = _is_gpt(cfg)
    if gpt:
        x = (
            params["wte"].astype(cfg.dtype)[tokens]
            + params["wpe"].astype(cfg.dtype)[positions]
        )
        block = _block_gpt_paged
    else:
        x = params["embed"]["weight"].astype(cfg.dtype)[tokens]
        block = _block_latent_paged if cfg.latent else _block_paged
    kinds = _kinds(cfg)
    classed = "full" in pool  # {"full": {...}, "window": {...}}
    scanned_params, experts = _split_experts(cfg, params["layers"])
    # leading dense layers: a prologue before the scan, on the first
    # layers of the pool (see `_forward_cached`)
    lead = getattr(cfg, "first_k_dense", 0)
    pool = {c: dict(p) for c, p in pool.items()} if classed else dict(pool)
    for j in range(lead):
        x, pool = block(
            cfg, x,
            jax.tree_util.tree_map(
                lambda a: a[j], params["dense_layers"]),
            pool, j, table, positions, mesh=mesh,
        )
    # a layer's rank among its kind inside the period, and how many
    # of its kind a period holds
    rank = [kinds[:j].count(kind) for j, kind in enumerate(kinds)]
    per_period = {kind: kinds.count(kind) for kind in kinds}

    def body(carry, inp):
        h, pool, counts = carry
        if adapters is None:
            period_params, first = inp
        else:
            period_params, first, period_bank = inp
        period = first // len(kinds)
        for j, kind in enumerate(kinds):
            lora = None
            if adapters is not None:
                lora = (
                    _place(cfg, period_bank, j), adapters["idx"],
                    adapters["scale"],
                )
            kw = dict(kind=kind, abs_layer=first + j, experts=experts)
            if carried is not None:
                kw["carried"] = carried
            if classed:
                out = block(
                    cfg, h, _place(cfg, period_params, j), pool[kind],
                    period * per_period[kind] + rank[j],
                    table_win if kind == "window" else table,
                    positions, mesh=mesh, lora=lora, **kw,
                )
                pool = dict(pool, **{kind: out[1]})
            else:
                out = block(
                    cfg, h, _place(cfg, period_params, j), pool,
                    lead + first + j, table, positions, mesh=mesh,
                    lora=lora, **kw,
                )
                pool = out[1]
            h = out[0]
            if experts is not None:
                c = out[2]
                if counts.ndim == 2:
                    c = jnp.stack([c, (c > 0).astype(jnp.int32)])
                counts = counts + c
        return (h, pool, counts), None

    n_layers = jax.tree_util.tree_leaves(scanned_params)[0].shape[0]
    firsts = jnp.arange(0, n_layers, len(kinds), dtype=jnp.int32)
    xs = (
        (_by_period(cfg, scanned_params), firsts)
        if adapters is None
        else (
            _by_period(cfg, scanned_params), firsts,
            _by_period(cfg, dict(adapters["bank"])),
        )
    )
    counts0 = (
        jnp.zeros(moe_counts_shape(cfg), jnp.int32)
        if experts is not None else None
    )
    carry0 = (x, pool, counts0)
    with jax.named_scope("layers"):
        (x, pool_new, counts), _ = jax.lax.scan(body, carry0, xs)
    if carried is not None:
        x = x[:, -_block_of(cfg):]
    if gpt:
        from dlrover_tpu.models.gpt import _layer_norm

        x = _layer_norm(
            x, params["lnf_g"], params["lnf_b"], cfg.norm_eps
        )
        head = params["wte"].astype(cfg.dtype).T
    else:
        x = _rms_norm(
            x, params["final_norm"]["scale"], cfg.norm_eps
        )
        head = _head_matrix(cfg, params)
    logits = matmul_any(x, head, tp=_mesh_tp(mesh)).astype(jnp.float32)
    if experts is not None:
        return logits, pool_new, counts
    return logits, pool_new


def paged_decode_step(
    cfg, params, token: jax.Array, pool, table, pos, mesh=None,
    adapters=None, table_win=None,
):
    """One cached step over paged KV → (logits [B, V], pool). The
    paged twin of `decode_step` ([B] per-slot positions only — the
    paged layout exists for continuous batching). `table_win`: the
    slots' rings, where the pool has a window class; dropless
    experts add their int32[E] routed pairs as a third value."""
    pos = jnp.asarray(pos, jnp.int32)
    positions = pos[:, None]
    logits, *rest = _forward_paged(
        cfg, params, token[:, None], pool, table, positions,
        mesh=mesh,
        adapters=adapters,
        **({} if table_win is None else {"table_win": table_win}),
    )
    return (logits[:, 0], *rest)


def paged_verify_step(
    cfg, params, tokens: jax.Array, pool, table, pos, mesh=None,
    adapters=None,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Batched speculative verify over paged KV — the paged twin of
    `verify_step`. The engine sizes each request's page run for
    limit - 1 + draft_len cells so the clamped write window lands in
    owned (or trash) pages, never a neighbour's."""
    b, s = tokens.shape
    pos = jnp.asarray(pos, jnp.int32)
    positions = pos[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]
    logits, pool, *_counts = _forward_paged(
        cfg, params, tokens, pool, table, positions, mesh=mesh,
        adapters=adapters,
    )
    return logits, pool


def gather_pool_view(
    pool: Dict[str, jax.Array], table: jax.Array
) -> Dict[str, jax.Array]:
    """Gather EVERY layer's pages into the dense bank layout
    [L, B, M, ...] (M = P * page_size) — the exact pytree
    `decode_step`/`verify_step` consume. One materialized copy per
    call; the chunk program amortizes it over a whole scan (a
    per-step gather would copy the full cache once PER TOKEN, the
    dominant paged overhead on backends without the Pallas kernel)."""
    out = {}
    with jax.named_scope("kv_pool_slice"):
        for name, arr in pool.items():
            g = arr[:, table]  # [L, B, P, page_size, ...]
            out[name] = g.reshape(g.shape[:2] + (-1,) + g.shape[4:])
    return out


def scatter_pool_window(
    pool: Dict[str, jax.Array],
    view: Dict[str, jax.Array],
    table: jax.Array,
    start,          # [B] first logical cell each row may have written
    width: int,     # STATIC window width (chunk k, or draft K+1)
) -> Dict[str, jax.Array]:
    """Write the view's cells at logical positions start_b+[0, width)
    back into their physical pages — the inverse of
    `gather_pool_view`, restricted to the only window a dispatch can
    touch (a chunk scan writes at most `k` cells past each row's
    entry position; a verify writes K+1). Unwritten window cells
    carry their own gathered values, so scattering them is the
    identity; rows parked on the trash page collide there with other
    parked rows, which no live mask ever reads. Positions clamp to
    the last cell exactly like the dense bank's write does."""
    ps = pool["k"].shape[2]
    m = view["k"].shape[2]
    start = jnp.asarray(start, jnp.int32)
    positions = jnp.minimum(
        start[:, None] + jnp.arange(width, dtype=jnp.int32)[None, :],
        m - 1,
    )  # [B, W]
    pids = jnp.take_along_axis(table, positions // ps, axis=1)
    offs = positions % ps
    idx = positions[None, :, :, None, None]  # broadcast L, KV, tail
    out = {}
    with jax.named_scope("kv_pool_writeback"):
        for name, arr in pool.items():
            cells = jnp.take_along_axis(view[name], idx, axis=2)
            out[name] = arr.at[:, pids, offs].set(cells)
    return out


def paged_install_row(
    pool: Dict[str, jax.Array],
    row_cache: Dict[str, jax.Array],
    table_row: jax.Array,   # [P] page ids for the receiving slot
    start,                  # traced scalar: first cell to install
    length: int,            # STATIC cell count (the suffix bucket)
) -> Dict[str, jax.Array]:
    """Install cells [start, start+length) of an exact (fp32) cache
    row into the pages `table_row` maps them to — the paged twin of
    `install_exact_row` (cold admission installs the whole prompt
    bucket at start=0; warm admission installs only the suffix, the
    shared prefix pages are already populated). Quantizes on the way
    in when the pool is int8 — per-VECTOR scales make quantizing the
    slice equal to slicing the quantized whole, so the installed
    bytes match the dense bank's cold path exactly. `length` is
    static (one program per suffix bucket), `start` traced."""
    ps = _leaf(pool).shape[2]
    start = jnp.asarray(start, jnp.int32)
    positions = start + jnp.arange(length, dtype=jnp.int32)  # [Sb]
    pids = table_row[positions // ps]
    offs = positions % ps
    src = {}
    for name, arr in row_cache.items():  # [L, 1, M, KV, hd] (ckv: [L, 1, M, W])
        sl = jax.lax.dynamic_slice(
            arr,
            (0, 0, start) + (0,) * (arr.ndim - 3),
            (arr.shape[0], 1, length) + arr.shape[3:],
        )
        src[name] = sl[:, 0]  # [L, Sb, KV, hd]
    if "k_scale" in pool:
        kq, ks = _kv_quantize(src["k"])
        vq, vs = _kv_quantize(src["v"])
        src = {"k": kq, "v": vq, "k_scale": ks, "v_scale": vs}
    out = {}
    for name, arr in pool.items():
        if arr.ndim == 4:
            # a latent leaf: with the layers as a slice the TPU
            # compiler re-lays the whole pool out for the scatter and
            # back (two copies of 2.5 GB at the published sizes, my
            # AOT compile); with the layer an index like the page and
            # the cell it writes the rows where they lie
            lay = jnp.arange(arr.shape[0], dtype=jnp.int32)[:, None]
            out[name] = arr.at[lay, pids[None], offs[None]].set(
                src[name].astype(arr.dtype)
            )
            continue
        out[name] = arr.at[:, pids, offs].set(
            src[name].astype(arr.dtype)
        )
    return out


def paged_install_hybrid(
    cfg,
    pools: Dict[str, Dict[str, jax.Array]],
    row_cache: Dict[str, jax.Array],   # [L, 1, M, KV, hd], all layers
    table_row_full: jax.Array,         # [P] the slot's full-class pages
    table_row_win: jax.Array,          # [R] the slot's ring
    prompt_len,                        # traced: the prompt's TRUE length
    length: int,                       # STATIC: the prompt's bucket
) -> Dict[str, Dict[str, jax.Array]]:
    """Install a prefilled row into both classes of pages. The full
    layers' cells [0, length) go where `paged_install_row` puts them.
    The window layers keep only the prompt's last `sliding_window`
    positions: cells [start, start + n) with n = min(window, length)
    and start = max(prompt_len - n, 0), each into ring entry
    (cell // page) % R. Cells at or past `prompt_len` (the bucket's
    pad tail) land in pages the slot owns ahead of its frontier, where
    the length mask hides them until decode overwrites them, or in the
    trash page (the host leaves unowned entries at 0)."""
    kinds = cfg.period
    n_layers = row_cache["k"].shape[0]
    layers_of = {
        kind: [i for i in range(n_layers) if kinds[i % len(kinds)] == kind]
        for kind in ("full", "window")
    }
    ps = pools["full"]["k"].shape[2]
    ring = table_row_win.shape[0]
    n = min(cfg.sliding_window, length)
    start = jnp.maximum(jnp.asarray(prompt_len, jnp.int32) - n, 0)
    cells = {
        "full": jnp.arange(length, dtype=jnp.int32),
        "window": start + jnp.arange(n, dtype=jnp.int32),
    }
    pids = {
        "full": table_row_full[cells["full"] // ps],
        "window": table_row_win[(cells["window"] // ps) % ring],
    }
    out = {}
    for kind, pool in pools.items():
        idx = jnp.asarray(layers_of[kind], jnp.int32)
        out[kind] = {}
        for name, arr in pool.items():
            src = row_cache[name][idx, 0][:, cells[kind]]  # [Lk, n, KV, hd]
            out[kind][name] = arr.at[
                :, pids[kind], cells[kind] % ps
            ].set(src.astype(arr.dtype))
    return out


def paged_prefill_chunk(
    cfg,
    params,
    chunk: jax.Array,       # [C] REAL tokens only — no pad tail
    pool: Dict[str, jax.Array],
    table_row: jax.Array,   # [P] the slot's REAL page ids
    start,
    mesh=None,
    adapters=None,
) -> Dict[str, jax.Array]:
    """Paged twin of `prefill_chunk_into_slot`: run `chunk` at
    positions [start, start+C), scattering K/V through `table_row`'s
    pages (the same `_write_pages_and_attend` path every paged
    forward uses, so int8 pools quantize on write identically).

    The caller passes the slot's REAL table row — never the
    trash-routed table the fused chunk program's decode half sees: a
    mid-prefill slot rides with device done=True so the decode scan
    freezes it (its frozen rewrites trash-route exactly like any done
    row's), while its prefill writes land in its owned pages here.
    The engine allocates the slot's full page run at admission, so
    every chunk position maps to an owned page."""
    c = chunk.shape[0]
    positions = (jnp.asarray(start, jnp.int32) + jnp.arange(c))[None]
    _, pool, *_counts = _forward_paged(
        cfg, params, chunk[None], pool, table_row[None], positions,
        mesh=mesh,
        adapters=adapters,
    )
    return pool


def pool_copy_page(
    pool: Dict[str, jax.Array], src, dst
) -> Dict[str, jax.Array]:
    """Copy physical page `src` onto `dst` across every layer — the
    device half of copy-on-write (the allocator hands the writer a
    fresh page preloaded with the shared page's cells). Traced
    src/dst: one compiled program covers every CoW."""
    out = {}
    for name, arr in pool.items():
        out[name] = arr.at[:, dst].set(
            jax.lax.dynamic_slice(
                arr, (0, src) + (0,) * (arr.ndim - 2),
                (arr.shape[0], 1) + arr.shape[2:],
            )[:, 0]
        )
    return out


def _mask_top_k(logits: jax.Array, k: int) -> jax.Array:
    """Keep the k highest logits per row; the rest become -inf. Static
    k, so the top_k + threshold compare stays one fused XLA program.
    Value-threshold semantics: tokens exactly TIED with the k-th logit
    all survive (HF's TopKLogitsWarper masks with the same `scores <
    kth` compare, so ties behave identically there)."""
    kth = jax.lax.top_k(logits, k)[0][..., -1:]
    return jnp.where(logits < kth, -jnp.inf, logits)


def _mask_top_p(logits: jax.Array, p: float) -> jax.Array:
    """Nucleus filtering: keep the smallest prefix of the
    probability-sorted vocab whose mass reaches `p` (the top token
    always survives, even when its mass alone exceeds `p`). Tokens
    tied with the boundary logit all survive — degenerate flat rows
    widen the nucleus rather than picking a sort-order-dependent
    subset."""
    sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
    probs = jax.nn.softmax(sorted_logits, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    # a sorted position is kept while the mass BEFORE it is < p
    keep = jnp.concatenate(
        [
            jnp.ones_like(cum[..., :1], bool),
            cum[..., :-1] < p,
        ],
        axis=-1,
    )
    # threshold = smallest kept logit, mapped back to vocab order
    kth = jnp.min(
        jnp.where(keep, sorted_logits, jnp.inf), axis=-1, keepdims=True
    )
    return jnp.where(logits < kth, -jnp.inf, logits)


def _warp(
    logits: jax.Array, temperature: float, top_k: int, top_p: float
) -> jax.Array:
    """The sampler's logits warp, for `generate` here and every
    program of serving/engine.py. HF/vLLM order: temperature first,
    then the filters (the nucleus set is computed on the TEMPERED
    distribution). Static knobs, so an unset filter traces nothing."""
    logits = logits / temperature
    if 0 < top_k < logits.shape[-1]:
        logits = _mask_top_k(logits, top_k)
    if top_p < 1.0:
        logits = _mask_top_p(logits, top_p)
    return logits


def generate(
    cfg: LlamaConfig,
    params: Params,
    prompt: jax.Array,      # [B, P]
    max_new_tokens: int,
    temperature: float = 0.0,
    key: Optional[jax.Array] = None,
    max_len: Optional[int] = None,
    top_k: int = 0,
    top_p: float = 1.0,
    eos_id: Optional[int] = None,
    pad_id: int = 0,
    kv_quant: bool = False,
) -> jax.Array:
    """Greedy / temperature sampling with the KV cache; one compiled
    scan drives all steps. Returns [B, P + max_new_tokens].

    `top_k > 0` and/or `top_p < 1.0` filter the distribution before a
    temperature draw (vLLM-style knobs — reference inference backend:
    atorch/rl/inference_backend/vllm_backend.py); both are ignored for
    greedy decoding (temperature <= 0).

    `eos_id` enables early stopping per sequence: the eos token is
    emitted, every later position is `pad_id` (same semantics as
    rl/generate's done mask). Shapes stay static — finished rows keep
    stepping cheaply through the compiled scan — so the output is
    always [B, P + max_new_tokens] with a pad tail."""
    b, p = prompt.shape
    m = max_len or (p + max_new_tokens)
    if m < p + max_new_tokens:
        raise ValueError(
            f"max_len {m} < prompt {p} + new {max_new_tokens}"
        )
    if not 0.0 < top_p <= 1.0:
        raise ValueError(f"top_p must be in (0, 1], got {top_p}")
    if top_k < 0:
        raise ValueError(f"top_k must be >= 0, got {top_k}")
    if eos_id is not None and eos_id == pad_id:
        raise ValueError(
            f"eos_id and pad_id must differ (both {eos_id}): the pad "
            "tail would re-trigger the done mask's eos detection"
        )
    # positions actually used reach p + max_new_tokens - 1; the cache
    # buffer (m) may be padded larger for static-shape reuse
    _check_positional_capacity(cfg, p + max_new_tokens)
    if max_new_tokens == 0:
        return prompt
    if key is None:
        key = jax.random.PRNGKey(0)
    cache = init_kv_cache(cfg, b, m, quant=kv_quant)
    logits, cache = prefill(cfg, params, prompt, cache)

    def sample(logits, key):
        if temperature <= 0.0:
            return jnp.argmax(logits, axis=-1).astype(prompt.dtype)
        return jax.random.categorical(
            key, _warp(logits, temperature, top_k, top_p)
        ).astype(prompt.dtype)

    def emit(raw, done):
        """Apply the done mask: finished rows emit pad; a fresh eos
        marks the row done AFTER being emitted itself."""
        if eos_id is None:
            return raw, done
        tok = jnp.where(done, jnp.asarray(pad_id, raw.dtype), raw)
        return tok, done | (tok == eos_id)

    # single-use key discipline: the first draw gets its own subkey,
    # never the key the scan derives the rest from
    key, first_key = jax.random.split(key)
    done0 = jnp.zeros((b,), jnp.bool_)
    first, done0 = emit(sample(logits, first_key), done0)

    def step(carry, t):
        token, cache, key, done = carry
        key, sub = jax.random.split(key)
        logits, cache = decode_step(
            cfg, params, token, cache, p + t
        )
        nxt, done = emit(sample(logits, sub), done)
        return (nxt, cache, key, done), token

    # N-1 steps: `first` is token #1 (from the prefill logits); each
    # step feeds the previous sample and emits it, and the final carry
    # is token #N — no wasted trailing forward whose sample would be
    # dropped
    (last_tok, _, _, _), out_tokens = jax.lax.scan(
        step, (first, cache, key, done0), jnp.arange(max_new_tokens - 1)
    )
    gen = jnp.concatenate(
        [out_tokens.swapaxes(0, 1), last_tok[:, None]], axis=1
    )  # [B, N]
    return jnp.concatenate([prompt, gen], axis=1)
