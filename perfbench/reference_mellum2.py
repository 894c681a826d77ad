"""The plain reference of Mellum2-12B-A2.5B-Instruct's forward pass,
from the published description (config.json and the transformers
conventions it names), in straightforward jax.numpy: float32, matrix
products at `highest` precision, a Python loop over the layers, dense
masks, every expert computed for every token and weighted by the
routing weights (zero off the top k), no cache, no kernel, no sort.
It imports nothing from dlrover_tpu (the benchmark's copy of
tests/reference_models/mellum2.py; `weights_mellum2` is the
benchmark's own file).

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

import functools
import json
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


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
    """([.., E] float32: the top k of softmax(m Wr) over their sum,
    zero elsewhere; the k chosen experts [.., k], sorted). The router
    is never rounded: its choice is what the controls are compared
    ON, not part of what they round."""
    p = jax.nn.softmax(
        jnp.einsum("...d,de->...e", m, router, precision=HIGHEST), axis=-1
    )
    top, idx = jax.lax.top_k(p, model["num_experts_per_tok"])
    if model["norm_topk_prob"]:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    weights = jnp.sum(
        jax.nn.one_hot(idx, p.shape[-1], dtype=p.dtype) * top[..., None],
        axis=-2,
    )
    return weights, jnp.sort(idx, axis=-1)


def _experts(model, precision, h, lp):
    """Every expert for every token, one expert at a time (a scan
    over the experts' stack: one expert's float32 copy lives at
    once)."""
    m = _rms_norm(h, lp["mlp_norm"], model["rms_norm_eps"])
    w, chosen = routing_weights(model, m, lp["router"].astype(jnp.float32))

    def one(out, expert):
        wg, wu, wd, we = expert
        gate = jax.nn.silu(
            _einsum("bsd,dm->bsm", m, wg.astype(jnp.float32), precision)
        )
        up = _einsum("bsd,dm->bsm", m, wu.astype(jnp.float32), precision)
        y = _einsum("bsm,md->bsd", gate * up, wd.astype(jnp.float32),
                    precision)
        return out + we[..., None] * y, None

    out, _ = jax.lax.scan(
        one, jnp.zeros_like(h),
        (lp["we_gate"], lp["we_up"], lp["we_down"],
         jnp.moveaxis(w, -1, 0)),
    )
    return h + out, chosen


# ---- the forward, in blocks: one jitted program a layer kind ---------------


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _layer_program(model_items, kind, precision, h, lp, positions):
    model = _unhash(model_items)
    small = {
        k: v.astype(jnp.float32) for k, v in lp.items()
        if not k.startswith("we_")
    }
    h = _attention(model, kind, precision, h, small, positions)
    return _experts(model, precision, h, dict(lp, **small))


@functools.partial(jax.jit, static_argnums=(0, 1))
def _head_program(model_items, precision, h, scale, head):
    model = _unhash(model_items)
    h = _rms_norm(h, scale.astype(jnp.float32), model["rms_norm_eps"])
    return _einsum("bsd,dv->bsv", h, head.astype(jnp.float32), precision)


def _hash(model: dict) -> tuple:
    return tuple(sorted(
        (k, json.dumps(v, sort_keys=True)) for k, v in model.items()
        if k in _KEYS
    ))


def _unhash(items: tuple) -> dict:
    return {k: json.loads(v) for k, v in items}


_KEYS = (
    "hidden_size", "num_attention_heads", "num_key_value_heads", "head_dim",
    "num_experts", "num_experts_per_tok", "moe_intermediate_size",
    "norm_topk_prob", "vocab_size", "num_hidden_layers", "layer_types",
    "sliding_window", "rms_norm_eps", "rope_parameters",
)


def forward(model: dict, params, tokens, precision: str = "f32",
            choices: list = None):
    """`choices` (a list) is given each layer's chosen experts
    [B, S, k], sorted.
    tokens [B, S] -> logits [B, S, V] in float32: a Python loop
    over the layers, each one call of its kind's program on that
    layer's slice of the stacked weights (upcast inside, an expert at
    a time), so that the whole fits on a chip beside the bf16
    weights."""
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    items = _hash(model)
    h = params["embed"]["weight"][tokens].astype(jnp.float32)
    for layer in range(model["num_hidden_layers"]):
        lp = {k: v[layer] for k, v in params["layers"].items()}
        h, chosen = _layer_program(
            items, model["layer_types"][layer], precision, h, lp, positions
        )
        if choices is not None:
            choices.append(chosen)
    return _head_program(
        items, precision, h, params["final_norm"]["scale"],
        params["lm_head"]["weight"],
    )


# ---- serving: where a served token lies in the reference's logits ----------


def served_token_gaps(model: dict, params, prompt, served, pad_to: int,
                      precision_control: str = ""):
    """One forward over prompt + served tokens. For each served token,
    the gap by which its reference logit lies below that position's
    best, in units of the position's logit scale (max |logit|).
    Returns (gaps [n_served], control_gaps or None): the control is
    the same measure for the token that the lower precision's forward
    puts first at each of those positions. The sequence is padded to
    `pad_to` (causal: the pad tail changes nothing before it), so one
    program serves every request."""
    seq = list(prompt) + list(served)
    n, p = len(served), len(prompt)
    if len(seq) - 1 > pad_to:
        raise ValueError(f"sequence of {len(seq)} tokens exceeds {pad_to}")
    pad = [0] * (pad_to - (len(seq) - 1))
    tokens = jnp.asarray([seq[:-1] + pad], jnp.int32)
    nxt = jnp.asarray(seq[1:] + pad, jnp.int32)
    logits = forward(model, params, tokens)[0]
    low = None
    if precision_control:
        low = forward(model, params, tokens, precision_control)[0]
    gaps, control = _gaps(logits, nxt, low)
    lo, hi = p - 1, p - 1 + n
    return (
        jax.device_get(gaps)[lo:hi],
        None if control is None else jax.device_get(control)[lo:hi],
    )


@jax.jit
def _gaps(logits, nxt, low):
    best = logits.max(-1)
    scale = jnp.abs(logits).max(-1)
    chosen = jnp.take_along_axis(logits, nxt[:, None], axis=-1)[:, 0]
    gaps = (best - chosen) / scale
    if low is None:
        return gaps, None
    low_tok = jnp.argmax(low, axis=-1)
    low_chosen = jnp.take_along_axis(logits, low_tok[:, None], axis=-1)[:, 0]
    return gaps, (best - low_chosen) / scale


def routing_choice_differs_share(model: dict, params, prompt, served,
                                 pad_to: int, precision: str = "bf16"):
    """How often the experts chosen differ between the float32
    forward and the forward whose matmul operands are rounded to
    `precision` (what the program's arithmetic is nearest to): the
    share of (position, layer) pairs of prompt + served tokens whose
    k chosen experts are not the same set."""
    seq = (list(prompt) + list(served))[:-1]
    tokens = jnp.asarray([seq + [0] * (pad_to - len(seq))], jnp.int32)
    exact, low = [], []
    forward(model, params, tokens, choices=exact)
    forward(model, params, tokens, precision, choices=low)
    differs = [
        jnp.any(a[0, : len(seq)] != b[0, : len(seq)], axis=-1)
        for a, b in zip(exact, low)
    ]
    return float(jnp.mean(jnp.stack(differs)))
