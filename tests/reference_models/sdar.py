"""The plain reference of SDAR-30B-A3B-Chat (`model_type: sdar_moe`):
its forward pass under the block mask and its generation by diffusion
over blocks, from the published description (config.json, the
Qwen3-MoE block whose keys `sdar_moe`'s config carries, and the
family's released `generate.py`), in straightforward jax.numpy:
float32, matrix products at `highest` precision, a Python loop over
the layers, a dense mask, every expert computed for every token and
weighted by the routing weights (zero off the top k), no cache, no
kernel, no sort, no batching. It imports nothing from dlrover_tpu.

`model` is the configuration as its config.json spells it
(`hidden_size`, `num_experts`, `rope_theta`, ...); `params` is the
weight tree documented in `shapes`.

  h = embed[ids]
  per layer, 48 times, every layer sparse:
    u = RMSNorm(h); q, k, v = u Wq, u Wk, u Wv as 32 / 4 / 4 heads of 128
    q = g_q * q / rms(q), k = g_k * k / rms(k)   (per head, over its 128)
    rotary turn of all 128 dimensions, theta 1e6, rotate_half form
    scores q . k / sqrt(128) under M[i, j] = 1 iff j // B <= i // B
      (causal across blocks of B positions, two-sided inside one),
    softmax in float32, GQA; h = h + attn Wo
    m = RMSNorm(h); p = softmax_f32(m Wr) over 128; the 8 largest,
    divided by their sum; h = h + sum_e w_e (silu(m Wg_e) * (m Wu_e)) Wd_e
  logits = RMSNorm(h) W_head; the logits at position i are for
  position i's OWN token (no shift)

Generation (`block_diffusion_generate`): the first p - p % B prompt
tokens are context; then block by block, a block's state is its B
ids, p % B of the first block's given and the rest the mask id. One
forward re-runs the WHOLE sequence so far under M. While a position of
the block is masked, the forward denoises: every masked position takes
its arg-max token and its confidence (that token's softmax
probability) and the ceil(B / T) most confident masked positions are
unmasked, ties to the lower position. When none is masked the block
is committed and generation moves B positions on; the served loop
spends one more forward there (it stores the block's keys and
values), this loop has nothing to store and counts it
(`forwards`). Tokens past the request's limit in its last block are
dropped. Greedy; no end-of-sequence token.

Departures from the published description, and what it does not give:
- config.json has no key for the per-head norms of q and k; they are
  the Qwen3-MoE block's `q_norm` / `k_norm`, which `sdar_moe` inherits
  with every other key of its config (assumed).
- block length 4, mask id 151669 and the `low_confidence_static` rule
  are the released `generate.py`'s defaults (assumed); its
  `low_confidence_dynamic` threshold rule is not here.
- the released schedule unmasks B // T positions a step and spreads
  the remainder over the first steps; ceil(B / T) a step is the same
  schedule wherever T divides B (every T the tests and the benchmark
  use) and ends a step sooner elsewhere.
- WHICH positions are masked is kept beside the ids and not read off
  them (`ids == mask_id`): a model that predicts the mask id itself,
  as seeded random weights do once in 151936 tokens, would never
  finish a block in the released loop; here such a token is a token.
- `intermediate_size` 6144 is used by no layer (`decoder_sparse_step`
  1, `mlp_only_layers` []).

`precision` other than "f32" is a CONTROL: every matmul operand
rounded to bfloat16 ("bf16") or to 4 exponent and 3 mantissa bits
under a per-tensor scale ("fp8").
"""

import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def shapes(model: dict) -> dict:
    L, D = model["num_hidden_layers"], model["hidden_size"]
    H, KV, hd = (
        model["num_attention_heads"], model["num_key_value_heads"],
        model["head_dim"],
    )
    E, M, V = (
        model["num_experts"], model["moe_intermediate_size"],
        model["vocab_size"],
    )
    return {
        "embed": {"weight": (V, D)},
        "layers": {
            "attn_norm": (L, D),
            "wq": (L, D, H * hd), "wk": (L, D, KV * hd),
            "wv": (L, D, KV * hd), "wo": (L, H * hd, D),
            "q_norm": (L, hd), "k_norm": (L, hd),
            "mlp_norm": (L, D),
            "router": (L, D, E),
            "we_gate": (L, E, D, M), "we_up": (L, E, D, M),
            "we_down": (L, E, M, D),
        },
        "final_norm": {"scale": (D,)},
        "lm_head": {"weight": (D, V)},
    }


def _operand(x, precision: str):
    if precision == "f32":
        return x
    if precision == "bf16":
        return jax.lax.reduce_precision(x, 8, 7)
    if precision == "fp8":
        amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
        scale = amax / 224.0
        return jax.lax.reduce_precision(x / scale, 4, 3) * scale
    raise ValueError(f"unknown precision {precision!r}")


def _einsum(spec, a, b, precision):
    return jnp.einsum(
        spec, _operand(a, precision), _operand(b, precision),
        precision=HIGHEST, preferred_element_type=jnp.float32,
    )


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rotate_half(x):
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([-x2, x1], axis=-1)


def _rope(x, positions, theta: float):
    """x [S, heads, hd], the published rotate_half form."""
    hd = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    angles = positions[:, None].astype(jnp.float32) * freqs
    emb = jnp.concatenate([angles, angles], axis=-1)[:, None, :]
    return x * jnp.cos(emb) + _rotate_half(x) * jnp.sin(emb)


def block_mask(positions, block: int):
    """M[i, j] = 1 iff j // B <= i // B."""
    return (positions[None, :] // block) <= (positions[:, None] // block)


def _attention(model, precision, h, lp, positions, block):
    H, KV, hd = (
        model["num_attention_heads"], model["num_key_value_heads"],
        model["head_dim"],
    )
    s = h.shape[0]
    eps = model["rms_norm_eps"]
    u = _rms_norm(h, lp["attn_norm"], eps)
    q = _einsum("sd,de->se", u, lp["wq"], precision).reshape(s, H, hd)
    k = _einsum("sd,de->se", u, lp["wk"], precision).reshape(s, KV, hd)
    v = _einsum("sd,de->se", u, lp["wv"], precision).reshape(s, KV, hd)
    q = _rms_norm(q, lp["q_norm"], eps)
    k = _rms_norm(k, lp["k_norm"], eps)
    theta = float(model["rope_theta"])
    q, k = _rope(q, positions, theta), _rope(k, positions, theta)
    q = q.reshape(s, KV, H // KV, hd)
    scores = _einsum("qkgd,skd->kgqs", q, k, precision) / math.sqrt(hd)
    seen = block_mask(positions, block)[None, None]
    probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
    attn = _einsum("kgqs,skd->qkgd", probs, v, precision)
    return h + _einsum(
        "se,ed->sd", attn.reshape(s, H * hd), lp["wo"], precision
    )


def routing_weights(model, m, router):
    """[S, E] float32: the top k of softmax(m Wr) over their sum, zero
    elsewhere. The router is never rounded: its choice is what the
    controls are compared ON, not part of what they round."""
    p = jax.nn.softmax(
        jnp.einsum("sd,de->se", m, router, precision=HIGHEST), axis=-1
    )
    top, idx = jax.lax.top_k(p, model["num_experts_per_tok"])
    if model["norm_topk_prob"]:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    return jnp.sum(
        jax.nn.one_hot(idx, p.shape[-1], dtype=p.dtype) * top[..., None],
        axis=-2,
    )


def _experts(model, precision, h, lp):
    m = _rms_norm(h, lp["mlp_norm"], model["rms_norm_eps"])
    w = routing_weights(model, m, lp["router"])              # [S, E]
    out = jnp.zeros_like(h)
    for e in range(model["num_experts"]):  # every expert, every token
        gate = jax.nn.silu(
            _einsum("sd,dm->sm", m, lp["we_gate"][e], precision)
        )
        up = _einsum("sd,dm->sm", m, lp["we_up"][e], precision)
        out = out + w[:, e:e + 1] * _einsum(
            "sm,md->sd", gate * up, lp["we_down"][e], precision
        )
    return h + out


def forward(model: dict, params, ids, block: int, precision: str = "f32"):
    """ids [S] -> logits [S, V] in float32 under the block mask of
    `block` positions; row i is position i's own token's."""
    ids = jnp.asarray(ids, jnp.int32)
    positions = jnp.arange(ids.shape[0])
    with jax.default_matmul_precision("highest"):
        h = params["embed"]["weight"].astype(jnp.float32)[ids]
        for layer in range(model["num_hidden_layers"]):
            lp = jax.tree_util.tree_map(
                lambda w: w[layer].astype(jnp.float32), params["layers"]
            )
            h = _attention(model, precision, h, lp, positions, block)
            h = _experts(model, precision, h, lp)
        h = _rms_norm(
            h, params["final_norm"]["scale"].astype(jnp.float32),
            model["rms_norm_eps"],
        )
        return _einsum(
            "sd,dv->sv", h,
            params["lm_head"]["weight"].astype(jnp.float32), precision,
        )


def unmask_count(block: int, steps: int) -> int:
    return -(-block // steps)


def denoise(logits, masked, count: int):
    """One denoising decision over a block's logits [B, V] and its
    masked positions (a list of bools): (the positions unmasked, lowest
    first, each one's arg-max id). The `count` most confident masked
    positions, the confidence being the arg-max token's softmax
    probability, ties to the lower position."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    best = [int(t) for t in jnp.argmax(logits, axis=-1)]
    conf = [float(c) for c in jnp.max(probs, axis=-1)]
    order = sorted(
        (i for i, on in enumerate(masked) if on),
        key=lambda i: (-conf[i], i),
    )
    taken = sorted(order[:count])
    return taken, [best[i] for i in taken]


def block_diffusion_generate(
    model: dict, params, prompt, n: int, block: int, steps: int,
    mask_id: int, trace: list = None,
):
    """`n` tokens after `prompt` by diffusion over blocks of `block`
    positions with `steps` denoising steps a block
    (`low_confidence_static`). Every forward re-runs the whole sequence
    under the block mask. `trace` (a list) is given, a forward, (the
    block's first position, the block's ids before the forward, the
    positions unmasked, their ids, the block's logits)."""
    seq = [int(t) for t in prompt]
    p = len(seq)
    limit = p + n
    count = unmask_count(block, steps)
    start = p - p % block
    while start < limit:
        given = len(seq) - start
        ids = seq[start:] + [mask_id] * (block - given)
        masked = [False] * given + [True] * (block - given)
        while any(masked):
            logits = forward(model, params, seq[:start] + ids, block)[start:]
            taken, toks = denoise(logits, masked, count)
            if trace is not None:
                trace.append((start, list(ids), taken, toks, logits))
            for i, t in zip(taken, toks):
                ids[i], masked[i] = t, False
        seq = seq[:start] + ids
        start += block
    return seq[p:limit]
