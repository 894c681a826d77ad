"""The plain reference of Mellum2-12B-A2.5B-Instruct's forward pass,
from the published description (config.json and the transformers
conventions it names), in straightforward jax.numpy: float32, matrix
products at `highest` precision, a Python loop over the layers, dense
masks, every expert computed for every token and weighted by the
routing weights (zero off the top k), no cache, no kernel, no sort.
It imports nothing from dlrover_tpu.

`model` is the configuration as its config.json spells it
(`hidden_size`, `num_experts`, `layer_types`, `rope_parameters`, ...);
`params` is the weight tree documented in `shapes`.

  h = embed[tokens]
  per layer l (kind = layer_types[l]):
    a = RMSNorm(h); q, k, v = a Wq, a Wk, a Wv as heads; rope(q), rope(k)
        sliding_attention: plain rope, theta 500000
        full_attention:    YaRN (static), cos and sin * attention_factor
    causal attention, GQA, scale 1/sqrt(head_dim); a sliding layer's
    query i sees keys j with i - sliding_window < j <= i
    h = h + attn Wo
    m = RMSNorm(h); p = softmax_f32(m Wr); the top k of p, divided by
    their sum; h = h + sum_e w_e (silu(m Wg_e) * (m Wu_e)) Wd_e
  logits = RMSNorm(h) W_head

Assumed, because config.json has no key for them: softmax BEFORE the
top-k, no router bias, no normalisation of q and k, no auxiliary loss
at inference. `described_as` names an MTP head; the config has none,
so there is none here.

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


def rope_frequencies(rope: dict, head_dim: int):
    """(inverse frequencies [head_dim / 2], factor on cos and sin) of
    one section of `rope_parameters`."""
    theta = float(rope["rope_theta"])
    d = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    freqs = 1.0 / theta ** d
    if rope["rope_type"] == "default":
        return freqs, 1.0
    if rope["rope_type"] != "yarn":
        raise ValueError(f"unknown rope_type {rope['rope_type']!r}")
    factor = float(rope["factor"])
    original = rope["original_max_position_embeddings"]

    def dim_of(rotations):
        return (
            head_dim * math.log(original / (rotations * 2 * math.pi))
            / (2 * math.log(theta))
        )

    lo = max(math.floor(dim_of(rope["beta_fast"])), 0)
    hi = min(math.ceil(dim_of(rope["beta_slow"])), head_dim - 1)
    if lo == hi:
        hi += 0.001
    ramp = jnp.clip(
        (jnp.arange(head_dim // 2, dtype=jnp.float32) - lo) / (hi - lo),
        0.0, 1.0,
    )
    freqs = freqs * (1 - ramp) + freqs / factor * ramp
    attention_factor = rope.get("attention_factor")
    if attention_factor is None:
        attention_factor = 0.1 * math.log(factor) + 1.0
    return freqs, float(attention_factor)


def _rotate_half(x):
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([-x2, x1], axis=-1)


def _rope(x, positions, rope: dict):
    """x [B, S, heads, hd], the published rotate_half form."""
    freqs, factor = rope_frequencies(rope, x.shape[-1])
    angles = positions[:, :, None].astype(jnp.float32) * freqs
    emb = jnp.concatenate([angles, angles], axis=-1)[:, :, None, :]
    return x * (jnp.cos(emb) * factor) + _rotate_half(x) * (
        jnp.sin(emb) * factor
    )


def _attention(model, kind, precision, h, lp, positions):
    H, KV, hd = (
        model["num_attention_heads"], model["num_key_value_heads"],
        model["head_dim"],
    )
    b, s, _ = h.shape
    a = _rms_norm(h, lp["attn_norm"], model["rms_norm_eps"])
    q = _einsum("bsd,de->bse", a, lp["wq"], precision).reshape(b, s, H, hd)
    k = _einsum("bsd,de->bse", a, lp["wk"], precision).reshape(b, s, KV, hd)
    v = _einsum("bsd,de->bse", a, lp["wv"], precision).reshape(b, s, KV, hd)
    rope = model["rope_parameters"][kind]
    q, k = _rope(q, positions, rope), _rope(k, positions, rope)
    q = q.reshape(b, s, KV, H // KV, hd)
    scores = _einsum("bqkgd,bskd->bkgqs", q, k, precision) / math.sqrt(hd)
    qi = positions[:, None, None, :, None]
    kj = positions[:, None, None, None, :]
    seen = kj <= qi
    if kind == "sliding_attention":
        seen = seen & (kj > qi - model["sliding_window"])
    probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
    attn = _einsum("bkgqs,bskd->bqkgd", probs, v, precision)
    return h + _einsum(
        "bse,ed->bsd", attn.reshape(b, s, H * hd), lp["wo"], precision
    )


def routing_weights(model, m, router):
    """[.., E] float32: the top k of softmax(m Wr) over their sum,
    zero elsewhere. The router is never rounded: its choice is what
    the controls are compared ON, not part of what they round."""
    p = jax.nn.softmax(
        jnp.einsum("...d,de->...e", m, router, precision=HIGHEST), axis=-1
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
    w = routing_weights(model, m, lp["router"])              # [B, S, E]
    out = jnp.zeros_like(h)
    for e in range(model["num_experts"]):  # every expert, every token
        gate = jax.nn.silu(
            _einsum("bsd,dm->bsm", m, lp["we_gate"][e], precision)
        )
        up = _einsum("bsd,dm->bsm", m, lp["we_up"][e], precision)
        out = out + w[..., e:e + 1] * _einsum(
            "bsm,md->bsd", gate * up, lp["we_down"][e], precision
        )
    return h + out


def forward(model: dict, params, tokens, precision: str = "f32"):
    """tokens [B, S] -> logits [B, S, V] in float32."""
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    h = params["embed"]["weight"].astype(jnp.float32)[tokens]
    for l in range(model["num_hidden_layers"]):
        lp = jax.tree_util.tree_map(
            lambda w: w[l].astype(jnp.float32), params["layers"]
        )
        h = _attention(
            model, model["layer_types"][l], precision, h, lp, positions
        )
        h = _experts(model, precision, h, lp)
    h = _rms_norm(
        h, params["final_norm"]["scale"].astype(jnp.float32),
        model["rms_norm_eps"],
    )
    return _einsum(
        "bsd,dv->bsv", h, params["lm_head"]["weight"].astype(jnp.float32),
        precision,
    )
