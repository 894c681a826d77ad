"""The plain reference of GigaChat3.1-702B-A36B's forward pass
(`model_type: deepseek_v3`), from the published description
(config.json and the transformers conventions it names), in
straightforward jax.numpy: float32, matrix products at `highest`
precision, a Python loop over the layers, dense masks, every expert
that is asked for computed for every token and weighted by the
routing weights (zero off the chosen), no cache, no kernel, no sort.
It imports nothing from dlrover_tpu.

`model` is the configuration as its config.json spells it
(`hidden_size`, `kv_lora_rank`, `n_routed_experts`, `rope_scaling`,
...); `params` is the weight tree documented in `shapes`. Pre-norm
residual blocks, RMSNorm with `rms_norm_eps`.

Latent attention, token t, h = RMSNorm(x_t):
  c_q = RMSNorm(h W_qa); q = c_q W_qb as heads of [q_nope, q_rope];
  q_rope <- RoPE(q_rope, t)
  [c_raw, r_raw] = h W_kva; c = RMSNorm(c_raw); r = RoPE(r_raw, t):
  ONE r for all heads
  expanded: [k_nope_h, v_h] = c W_kvb; k_h = [k_nope_h, r];
    score = (q_h . k_h,s) * scale; causal softmax; o_h = sum_s p_s v_h,s
  absorbed (the same numbers up to rounding), W_kvb's head h split
    into W_UK_h and W_UV_h: q~_h = W_UK_h q_nope_h;
    score = (q~_h . c_s + q_rope_h . r_s) * scale; u_h = sum_s p_s c_s;
    o_h = u_h W_UV_h
  out = concat(o_h) W_o
  scale = (qk_nope_head_dim + qk_rope_head_dim)^-0.5 * m^2 with
  m = 0.1 * mscale_all_dim * ln(factor) + 1; the YaRN frequencies with
  the factor m(mscale) / m(mscale_all_dim) on cos and sin.

Feed-forward: the first `first_k_dense_replace` layers a SwiGLU of
`intermediate_size`; every later layer, in float32 up to the experts,
  s = sigmoid(h W_r); s' = s + b (`e_score_correction_bias`: used to
  CHOOSE only); the experts in `n_group` groups, a group's score the
  sum of its two largest s'; the `topk_group` best groups stay; S =
  the `num_experts_per_tok` largest s' among their experts (ties to
  the lower index); w_e = routed_scaling_factor * s_e / sum_(e in S)
  s_e; y = E_shared(h) + sum_(e in S) w_e E_e(h), every E a SwiGLU of
  `moe_intermediate_size`.
`held` = (first, count) gives ONE CHIP'S SHARE of a layer that 16 (or
however many) chips divide: the router ranks every expert as above,
the weights are normalised over all the chosen, and the sum runs over
the chosen experts inside the share only, plus the shared expert;
what the others would add is left out, and that partial result goes
on to the next layer. `shared=False` leaves the shared expert out
(the other shares of a layer, when their parts are added up).

Departures from the published files, none of which seeded random
weights can tell from the original: RoPE rotates the two HALVES of a
vector (the published pairs are interleaved: a fixed permutation of
W_qb's and W_kva's rope columns); `num_nextn_predict_layers` (the
multi-token-prediction module) is not part of the forward for
generation and is not here.

`precision` other than "f32" is a CONTROL: every matmul operand
rounded to bfloat16 ("bf16") or to 4 exponent and 3 mantissa bits
under a per-tensor scale ("fp8").
"""

import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def shapes(model: dict, experts=None) -> dict:
    """The weight tree: `dense_layers` (the leading dense layers,
    stacked) and `layers` (the expert layers, stacked). `experts`:
    how many routed experts the tree holds (all of them by default)."""
    D, H = model["hidden_size"], model["num_attention_heads"]
    qr, cr = model["q_lora_rank"], model["kv_lora_rank"]
    nope, rope, vd = (
        model["qk_nope_head_dim"], model["qk_rope_head_dim"],
        model["v_head_dim"],
    )
    E = model["n_routed_experts"]
    held = E if experts is None else experts
    M, W, V = (
        model["moe_intermediate_size"], model["intermediate_size"],
        model["vocab_size"],
    )
    S = model["n_shared_experts"] * M
    L0 = model["first_k_dense_replace"]
    L1 = model["num_hidden_layers"] - L0

    def attention(L):
        return {
            "attn_norm": (L, D),
            "wq_a": (L, D, qr), "q_norm": (L, qr),
            "wq_b": (L, qr, H * (nope + rope)),
            "wkv_a": (L, D, cr + rope), "kv_norm": (L, cr),
            "wkv_b": (L, cr, H * (nope + vd)),
            "wo": (L, H * vd, D),
            "mlp_norm": (L, D),
        }

    return {
        "embed": {"weight": (V, D)},
        "dense_layers": {
            **attention(L0),
            "w_gate": (L0, D, W), "w_up": (L0, D, W), "w_down": (L0, W, D),
        },
        "layers": {
            **attention(L1),
            "router": (L1, D, E), "router_bias": (L1, E),
            "ws_gate": (L1, D, S), "ws_up": (L1, D, S),
            "ws_down": (L1, S, D),
            "we_gate": (L1, held, D, M), "we_up": (L1, held, D, M),
            "we_down": (L1, held, M, D),
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
        scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
        return jax.lax.reduce_precision(x / scale, 4, 3) * scale
    raise ValueError(f"unknown precision {precision!r}")


def mm(x, w, precision="f32"):
    return jnp.matmul(
        _operand(x.astype(jnp.float32), precision),
        _operand(w.astype(jnp.float32), precision), precision=HIGHEST,
    )


def rms_norm(x, scale, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps
    ) * scale.astype(jnp.float32)


def yarn(model: dict):
    """(inverse frequencies [rope / 2], factor on cos and sin, the
    softmax scale) of the configuration's `rope_scaling`."""
    rs = model["rope_scaling"]
    d = model["qk_rope_head_dim"]
    theta = float(model["rope_theta"])
    factor, orig = float(rs["factor"]), rs["original_max_position_embeddings"]
    freqs = [theta ** (-2.0 * i / d) for i in range(d // 2)]

    def turns_dim(rotations):
        return (
            d * math.log(orig / (rotations * 2 * math.pi))
            / (2 * math.log(theta))
        )

    lo = max(math.floor(turns_dim(rs["beta_fast"])), 0)
    hi = min(math.ceil(turns_dim(rs["beta_slow"])), d - 1)
    out = []
    for i, f in enumerate(freqs):
        ramp = min(max((i - lo) / max(hi - lo, 1e-3), 0.0), 1.0)
        out.append(f * (1 - ramp) + f / factor * ramp)

    def m(scale):
        return 0.1 * scale * math.log(factor) + 1.0 if factor > 1 else 1.0

    head = model["qk_nope_head_dim"] + d
    m_all = m(rs["mscale_all_dim"])
    return (
        jnp.asarray(out, jnp.float32), m(rs["mscale"]) / m_all,
        head ** -0.5 * m_all * m_all,
    )


def rope(x, positions, inv_freq, factor):
    """x [S, ..., d] rotated by its position: halves, not pairs."""
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (-1,)
    angles = (positions.astype(jnp.float32)[:, None] * inv_freq).reshape(shape)
    cos, sin = jnp.cos(angles) * factor, jnp.sin(angles) * factor
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(model, lp, x, positions, precision="f32", form="expanded"):
    """x [S, D] -> the block's attention output [S, D] (before the
    residual); `form`: "expanded" or "absorbed"."""
    H = model["num_attention_heads"]
    cr = model["kv_lora_rank"]
    nope, rd, vd = (
        model["qk_nope_head_dim"], model["qk_rope_head_dim"],
        model["v_head_dim"],
    )
    eps = model["rms_norm_eps"]
    inv_freq, factor, scale = yarn(model)
    s = x.shape[0]
    h = rms_norm(x, lp["attn_norm"], eps)
    cq = rms_norm(mm(h, lp["wq_a"], precision), lp["q_norm"], eps)
    q = mm(cq, lp["wq_b"], precision).reshape(s, H, nope + rd)
    q_nope, q_rope = q[..., :nope], rope(q[..., nope:], positions,
                                         inv_freq, factor)
    ckv = mm(h, lp["wkv_a"], precision)
    c = rms_norm(ckv[:, :cr], lp["kv_norm"], eps)
    r = rope(ckv[:, cr:], positions, inv_freq, factor)
    wkv_b = lp["wkv_b"].astype(jnp.float32).reshape(cr, H, nope + vd)
    causal = positions[:, None] >= positions[None, :]
    if form == "expanded":
        kv = mm(c, wkv_b.reshape(cr, -1), precision).reshape(
            s, H, nope + vd)
        k_nope, v = kv[..., :nope], kv[..., nope:]
        scores = (
            jnp.einsum("shd,thd->hst", _operand(q_nope, precision),
                       _operand(k_nope, precision), precision=HIGHEST)
            + jnp.einsum("shd,td->hst", _operand(q_rope, precision),
                         _operand(r, precision), precision=HIGHEST)
        ) * scale
        p = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), -1)
        o = jnp.einsum("hst,thd->shd", _operand(p, precision),
                       _operand(v, precision), precision=HIGHEST)
    else:
        w_uk, w_uv = wkv_b[..., :nope], wkv_b[..., nope:]
        q_lat = jnp.einsum("shd,chd->shc", _operand(q_nope, precision),
                           _operand(w_uk, precision), precision=HIGHEST)
        scores = (
            jnp.einsum("shc,tc->hst", _operand(q_lat, precision),
                       _operand(c, precision), precision=HIGHEST)
            + jnp.einsum("shd,td->hst", _operand(q_rope, precision),
                         _operand(r, precision), precision=HIGHEST)
        ) * scale
        p = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), -1)
        u = jnp.einsum("hst,tc->shc", _operand(p, precision),
                       _operand(c, precision), precision=HIGHEST)
        o = jnp.einsum("shc,chd->shd", _operand(u, precision),
                       _operand(w_uv, precision), precision=HIGHEST)
    return mm(o.reshape(s, H * vd), lp["wo"], precision)


def swiglu(m, w_gate, w_up, w_down, precision="f32"):
    return mm(
        jax.nn.silu(mm(m, w_gate, precision)) * mm(m, w_up, precision),
        w_down, precision,
    )


def routing_weights(model, m, router, bias, precision="f32"):
    """m [S, D] normed tokens -> [S, E] float32: the weight of every
    chosen expert, zero elsewhere."""
    E, k = model["n_routed_experts"], model["num_experts_per_tok"]
    groups, keep = model["n_group"], model["topk_group"]
    s = jax.nn.sigmoid(mm(m, router, precision))
    choice = s + bias.astype(jnp.float32)
    per = E // groups
    grouped = choice.reshape(-1, groups, per)
    group_score = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)
    best = jax.lax.top_k(group_score, keep)[1]
    stays = jnp.zeros(group_score.shape, bool).at[
        jnp.arange(best.shape[0])[:, None], best].set(True)
    choice = jnp.where(jnp.repeat(stays, per, axis=1), choice, -jnp.inf)
    chosen = jax.lax.top_k(choice, k)[1]
    mask = jnp.zeros(s.shape, bool).at[
        jnp.arange(chosen.shape[0])[:, None], chosen].set(True)
    w = jnp.where(mask, s, 0.0)
    return model["routed_scaling_factor"] * w / jnp.sum(w, -1, keepdims=True)


def moe_layer(model, lp, m, held=None, shared=True, precision="f32"):
    """m [S, D] normed tokens -> the feed-forward's output [S, D]:
    the chosen experts inside `held` = (first, count) (all of them by
    default; `lp`'s expert stacks hold exactly those) and the shared
    expert, an expert at a time."""
    E = model["n_routed_experts"]
    first, count = held or (0, E)
    w = routing_weights(
        model, m, lp["router"], lp["router_bias"], precision)
    y = jnp.zeros(m.shape, jnp.float32)
    if shared and model["n_shared_experts"]:
        y = swiglu(m, lp["ws_gate"], lp["ws_up"], lp["ws_down"], precision)
    for e in range(count):
        y = y + w[:, first + e:first + e + 1] * swiglu(
            m, lp["we_gate"][e], lp["we_up"][e], lp["we_down"][e],
            precision,
        )
    return y


def forward(model, params, tokens, held=None, precision="f32",
            form="expanded"):
    """tokens [S] -> logits [S, V] float32."""
    eps = model["rms_norm_eps"]
    positions = jnp.arange(tokens.shape[0])
    x = params["embed"]["weight"].astype(jnp.float32)[tokens]
    L0 = model["first_k_dense_replace"]
    for l in range(model["num_hidden_layers"]):
        group, i = ("dense_layers", l) if l < L0 else ("layers", l - L0)
        lp = {k: v[i] for k, v in params[group].items()}
        x = x + attention(model, lp, x, positions, precision, form)
        m = rms_norm(x, lp["mlp_norm"], eps)
        if l < L0:
            x = x + swiglu(m, lp["w_gate"], lp["w_up"], lp["w_down"],
                           precision)
        else:
            x = x + moe_layer(model, lp, m, held, True, precision)
    x = rms_norm(x, params["final_norm"]["scale"], eps)
    return mm(x, params["lm_head"]["weight"], precision)
