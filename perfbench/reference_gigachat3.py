"""The plain reference of GigaChat3.1-702B-A36B's forward pass
(`model_type: deepseek_v3`), given ONE CHIP'S SHARE of a layer that 16
chips divide: the benchmark's copy of
tests/reference_models/gigachat3.py (the equations, their sources and
the departures are written out there), reading the weights as
weights_gigachat3.py lays them out (`wk_b` and `wv_b`: the published
W_kvb's columns as two leaves) and computed in blocks so that it fits
on the chip beside the bf16 weights: a Python loop over the layers,
one jitted program a kind of layer, attention in the EXPANDED form a
group of heads at a time, an expert at a time. float32, matrix
products at `highest` precision, no cache, no kernel, no sort. It
imports nothing from dlrover_tpu.

The share: the router ranks all `routed_experts_published` experts
and normalises the weights over all the chosen; the sum runs over the
chosen among the `n_routed_experts` held here (from `experts_held[0]`
on) and the shared expert. What the absent experts would add is left
out here as in the program, and that partial result goes on to the
next layer. The vocabulary is the slice.

`precision` other than "f32" is a CONTROL: every matmul operand
rounded to bfloat16 ("bf16") or to 4 exponent and 3 mantissa bits
under a per-tensor scale ("fp8"). The router is never rounded: its
choice is what the controls are compared ON.
"""

import functools
import json
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
HEAD_BLOCK = 8  # heads whose [S, S] scores live at once


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


def yarn(model: dict):
    """(inverse frequencies [rope / 2], factor on cos and sin, the
    softmax scale) of `rope_scaling`."""
    rs = model["rope_scaling"]
    d = model["qk_rope_head_dim"]
    theta, factor = float(model["rope_theta"]), float(rs["factor"])
    original = rs["original_max_position_embeddings"]
    freqs = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)

    def dim_of(rotations):
        return (d * math.log(original / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    lo = max(math.floor(dim_of(rs["beta_fast"])), 0)
    hi = min(math.ceil(dim_of(rs["beta_slow"])), d - 1)
    if lo == hi:
        hi += 0.001
    ramp = jnp.clip(
        (jnp.arange(d // 2, dtype=jnp.float32) - lo) / (hi - lo), 0.0, 1.0)
    freqs = freqs * (1 - ramp) + freqs / factor * ramp

    def m(scale):
        return 0.1 * scale * math.log(factor) + 1.0 if factor > 1 else 1.0

    m_all = m(rs["mscale_all_dim"])
    head = model["qk_nope_head_dim"] + d
    return freqs, m(rs["mscale"]) / m_all, head ** -0.5 * m_all * m_all


def _rope(x, positions, freqs, factor):
    """x [S, ..., d] rotated by its position: halves, not pairs."""
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (-1,)
    angles = (positions.astype(jnp.float32)[:, None] * freqs).reshape(shape)
    cos, sin = jnp.cos(angles) * factor, jnp.sin(angles) * factor
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(model, precision, x, lp, positions):
    """x [S, D] -> x + the block's attention, expanded form, a group
    of HEAD_BLOCK heads at a time."""
    H, cr = model["num_attention_heads"], model["kv_lora_rank"]
    nope, rd, vd = (
        model["qk_nope_head_dim"], model["qk_rope_head_dim"],
        model["v_head_dim"],
    )
    eps = model["rms_norm_eps"]
    freqs, factor, scale = yarn(model)
    s = x.shape[0]
    h = _rms_norm(x, lp["attn_norm"], eps)
    cq = _rms_norm(
        _einsum("sd,de->se", h, lp["wq_a"], precision), lp["q_norm"], eps)
    q = _einsum("sd,de->se", cq, lp["wq_b"], precision).reshape(
        s, H, nope + rd)
    ckv = _einsum("sd,de->se", h, lp["wkv_a"], precision)
    c = _rms_norm(ckv[:, :cr], lp["kv_norm"], eps)
    r = _rope(ckv[:, cr:], positions, freqs, factor)
    k_nope = _einsum("sc,ce->se", c, lp["wk_b"], precision).reshape(s, H, nope)
    v = _einsum("sc,ce->se", c, lp["wv_b"], precision).reshape(s, H, vd)
    q_nope = q[..., :nope]
    q_rope = _rope(q[..., nope:], positions, freqs, factor)
    seen = positions[None, :, None] >= positions[None, None, :]
    groups = H // HEAD_BLOCK

    def block(_, heads):
        qn, qr, kn, vv = heads  # [S, HEAD_BLOCK, ..]
        scores = (
            _einsum("shd,thd->hst", qn, kn, precision)
            + _einsum("shd,td->hst", qr, r, precision)
        ) * scale
        p = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return None, _einsum("hst,thd->shd", p, vv, precision)

    def grouped(a):  # [S, H, d] -> [groups, S, HEAD_BLOCK, d]
        return jnp.moveaxis(
            a.reshape(s, groups, HEAD_BLOCK, a.shape[-1]), 1, 0)

    _, o = jax.lax.scan(
        block, None,
        (grouped(q_nope), grouped(q_rope), grouped(k_nope), grouped(v)))
    o = jnp.moveaxis(o, 0, 1).reshape(s, H * vd)
    return x + _einsum("se,ed->sd", o, lp["wo"], precision)


def _swiglu(m, w_gate, w_up, w_down, precision):
    gate = jax.nn.silu(_einsum("sd,dm->sm", m, w_gate, precision))
    up = _einsum("sd,dm->sm", m, w_up, precision)
    return _einsum("sm,md->sd", gate * up, w_down, precision)


def routing_weights(model, m, router, bias):
    """([S, E] float32: the weight of every chosen expert, zero
    elsewhere; the chosen experts [S, k], sorted). float32 and never
    rounded."""
    E, k = model["routed_experts_published"], model["num_experts_per_tok"]
    groups, keep = model["n_group"], model["topk_group"]
    s = jax.nn.sigmoid(jnp.einsum("sd,de->se", m, router, precision=HIGHEST))
    choice = s + bias
    per = E // groups
    group_score = jnp.sum(
        jax.lax.top_k(choice.reshape(-1, groups, per), 2)[0], axis=-1)
    best = jax.lax.top_k(group_score, keep)[1]
    stays = jnp.any(jax.nn.one_hot(best, groups, dtype=bool), axis=1)
    choice = jnp.where(jnp.repeat(stays, per, axis=1), choice, -jnp.inf)
    chosen = jax.lax.top_k(choice, k)[1]
    mask = jnp.any(jax.nn.one_hot(chosen, E, dtype=bool), axis=1)
    w = jnp.where(mask, s, 0.0)
    w = model["routed_scaling_factor"] * w / jnp.sum(w, -1, keepdims=True)
    return w, jnp.sort(chosen, axis=-1)


def _experts(model, precision, x, lp):
    """x + shared expert + the chosen experts held here, an expert at
    a time (a scan over the held experts' stack: one expert's float32
    copy lives at once)."""
    m = _rms_norm(x, lp["mlp_norm"], model["rms_norm_eps"])
    w, chosen = routing_weights(
        model, m, lp["router"].astype(jnp.float32),
        lp["router_bias"].astype(jnp.float32))
    first = model["experts_held"][0]
    held = jax.lax.dynamic_slice_in_dim(
        w, first, model["n_routed_experts"], axis=1)
    out = _swiglu(
        m, lp["ws_gate"].astype(jnp.float32), lp["ws_up"].astype(jnp.float32),
        lp["ws_down"].astype(jnp.float32), precision)

    def one(out, expert):
        wg, wu, wd, we = expert
        y = _swiglu(m, wg.astype(jnp.float32), wu.astype(jnp.float32),
                    wd.astype(jnp.float32), precision)
        return out + we[:, None] * y, None

    out, _ = jax.lax.scan(
        one, out,
        (lp["we_gate"], lp["we_up"], lp["we_down"], jnp.moveaxis(held, 1, 0)),
    )
    return x + out, chosen


# ---- the forward, in blocks: one jitted program a kind of layer -----------

_BIG = ("we_gate", "we_up", "we_down", "ws_gate", "ws_up", "ws_down")


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _layer_program(model_items, dense, precision, x, lp, positions):
    model = _unhash(model_items)
    small = {
        k: v.astype(jnp.float32) for k, v in lp.items() if k not in _BIG
    }
    x = _attention(model, precision, x, small, positions)
    if dense:
        m = _rms_norm(x, small["mlp_norm"], model["rms_norm_eps"])
        return x + _swiglu(
            m, small["w_gate"], small["w_up"], small["w_down"], precision
        ), None
    return _experts(model, precision, x, dict(lp, **small))


@functools.partial(jax.jit, static_argnums=(0, 1))
def _head_program(model_items, precision, x, scale, head):
    model = _unhash(model_items)
    x = _rms_norm(x, scale.astype(jnp.float32), model["rms_norm_eps"])
    return _einsum("sd,dv->sv", x, head.astype(jnp.float32), precision)


_KEYS = (
    "hidden_size", "num_attention_heads", "q_lora_rank", "kv_lora_rank",
    "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
    "intermediate_size", "moe_intermediate_size", "n_routed_experts",
    "routed_experts_published", "experts_held", "num_experts_per_tok",
    "n_shared_experts", "n_group", "topk_group", "routed_scaling_factor",
    "first_k_dense_replace", "num_hidden_layers", "vocab_size",
    "rms_norm_eps", "rope_theta", "rope_scaling",
)


def _hash(model: dict) -> tuple:
    return tuple(sorted(
        (k, json.dumps(v, sort_keys=True)) for k, v in model.items()
        if k in _KEYS
    ))


def _unhash(items: tuple) -> dict:
    return {k: json.loads(v) for k, v in items}


def forward(model: dict, params, tokens, precision: str = "f32",
            choices: list = None):
    """tokens [S] -> logits [S, V] in float32: a Python loop over the
    layers, each one call of its kind's program on that layer's slice
    of the stacked weights (upcast inside). `choices` (a list) is
    given each expert layer's chosen experts [S, k], sorted."""
    positions = jnp.arange(tokens.shape[0])
    items = _hash(model)
    x = params["embed"]["weight"][tokens].astype(jnp.float32)
    L0 = model["first_k_dense_replace"]
    for layer in range(model["num_hidden_layers"]):
        group, i = (
            ("dense_layers", layer) if layer < L0 else ("layers", layer - L0))
        lp = {k: v[i] for k, v in params[group].items()}
        x, chosen = _layer_program(
            items, layer < L0, precision, x, lp, positions)
        if choices is not None and chosen is not None:
            choices.append(chosen)
    return _head_program(
        items, precision, x, params["final_norm"]["scale"],
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
    tokens = jnp.asarray(seq[:-1] + pad, jnp.int32)
    nxt = jnp.asarray(seq[1:] + pad, jnp.int32)
    logits = forward(model, params, tokens)
    low = None
    if precision_control:
        low = forward(model, params, tokens, precision_control)
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
    share of (position, expert layer) pairs of prompt + served tokens
    whose k chosen experts are not the same set."""
    seq = (list(prompt) + list(served))[:-1]
    tokens = jnp.asarray(seq + [0] * (pad_to - len(seq)), jnp.int32)
    exact, low = [], []
    forward(model, params, tokens, choices=exact)
    forward(model, params, tokens, precision, choices=low)
    differs = [
        jnp.any(a[: len(seq)] != b[: len(seq)], axis=-1)
        for a, b in zip(exact, low)
    ]
    return float(jnp.mean(jnp.stack(differs)))
