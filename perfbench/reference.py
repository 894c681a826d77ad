"""The plain reference: the Mistral-7B block as its publishers
describe it (huggingface transformers' MistralForCausalLM), in
straightforward jax.numpy and float32 with matrix multiplications at
`highest` precision. No kernel, no cache, no batching tricks, and
nothing imported from the program under test (`weights` is the
benchmark's own file). It reads the weight
tree perfbench/weights.py documents, upcasting one layer at a time
(a scan over the stacked layers), so that it fits on a chip beside
nothing else.

  h      = embed[tokens]
  per layer:
    a    = RMSNorm(h) ; q, k, v = a Wq, a Wk, a Wv ; RoPE(q), RoPE(k)
    h    = h + softmax(q k^T / sqrt(hd) + causal) v  Wo     (GQA: each
           KV head serves H/KV query heads)
    m    = RMSNorm(h) ; h = h + (silu(m Wg) * (m Wu)) Wd
  logits = RMSNorm(h) W_head                                 (untied)

Departures from the published code: none in the mathematics. Dropout
is 0 in the source; the sliding window is null in v0.3.

`precision` is "f32" for the reference itself. The lower settings are
the CONTROLS that `correct` must reject when they stand in the
program's place: "bf16" rounds every matmul operand to bfloat16,
"fp8" to 4 exponent and 3 mantissa bits under a per-tensor scale, the
step below a configuration that states bf16.
"""

import functools
import math

import jax
import jax.numpy as jnp

import weights

HIGHEST = jax.lax.Precision.HIGHEST


def _fake_fp8(x):
    """x rounded to 4 exponent and 3 mantissa bits under a per-tensor
    scale. lax.reduce_precision and not a pair of casts: the TPU
    compiler may drop a cast down and up again as excess precision
    (and did, for bfloat16, in this PR's first chip run)."""
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    scale = amax / 224.0  # the largest finite value of those bits is 240
    return jax.lax.reduce_precision(x / scale, 4, 3) * scale


def _operand(x, precision: str):
    """x as a matmul at `precision` would read it. The rounding is a
    straight-through step, as a real low-precision matmul's is: the
    backward pass sees the rounded forward values and the identity."""
    if precision == "f32":
        return x
    if precision == "bf16":
        low = jax.lax.reduce_precision(x, 8, 7)
    elif precision == "fp8":
        low = _fake_fp8(x)
    else:
        raise ValueError(f"unknown precision {precision!r}")
    return x + jax.lax.stop_gradient(low - x)


def _einsum(spec: str, a, b, precision: str):
    return jnp.einsum(
        spec, _operand(a, precision), _operand(b, precision),
        precision=HIGHEST, preferred_element_type=jnp.float32,
    )


def _rms_norm(x, scale, eps: float):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def _rotate_half(x):
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([-x2, x1], axis=-1)


def _rope(x, positions, theta: float):
    """x [B, S, heads, hd]; the published rotate_half form."""
    hd = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    angles = positions[:, :, None].astype(jnp.float32) * inv_freq
    emb = jnp.concatenate([angles, angles], axis=-1)[:, :, None, :]
    return x * jnp.cos(emb) + _rotate_half(x) * jnp.sin(emb)


def _layer(model: dict, precision: str, h, lp, positions):
    H, KV, hd = (
        model["num_attention_heads"], model["num_key_value_heads"],
        model["head_dim"],
    )
    eps, theta = model["rms_norm_eps"], model["rope_theta"]
    lp = jax.tree_util.tree_map(lambda w: w.astype(jnp.float32), lp)
    b, s, _ = h.shape
    a = _rms_norm(h, lp["attn_norm"], eps)
    q = _einsum("bsd,de->bse", a, lp["wq"], precision).reshape(b, s, H, hd)
    k = _einsum("bsd,de->bse", a, lp["wk"], precision).reshape(b, s, KV, hd)
    v = _einsum("bsd,de->bse", a, lp["wv"], precision).reshape(b, s, KV, hd)
    q, k = _rope(q, positions, theta), _rope(k, positions, theta)
    group = H // KV
    q = q.reshape(b, s, KV, group, hd)
    scores = _einsum("bqkgd,bskd->bkgqs", q, k, precision) / math.sqrt(hd)
    causal = positions[:, None, None, :, None] >= positions[:, None, None, None, :]
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    attn = _einsum("bkgqs,bskd->bqkgd", probs, v, precision)
    h = h + _einsum(
        "bse,ed->bsd", attn.reshape(b, s, H * hd), lp["wo"], precision
    )
    m = _rms_norm(h, lp["mlp_norm"], eps)
    gate = jax.nn.silu(_einsum("bsd,dm->bsm", m, lp["w_gate"], precision))
    up = _einsum("bsd,dm->bsm", m, lp["w_up"], precision)
    return h + _einsum("bsm,md->bsd", gate * up, lp["w_down"], precision)


def forward(model: dict, params, tokens, precision: str = "f32"):
    """tokens [B, S] -> logits [B, S, V] in float32."""
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    h = params["embed"]["weight"].astype(jnp.float32)[tokens]

    @jax.checkpoint
    def body(h, lp):
        return _layer(model, precision, h, lp, positions), None

    h, _ = jax.lax.scan(body, h, params["layers"])
    h = _rms_norm(
        h, params["final_norm"]["scale"].astype(jnp.float32),
        model["rms_norm_eps"],
    )
    return _einsum(
        "bsd,dv->bsv", h, params["lm_head"]["weight"].astype(jnp.float32),
        precision,
    )


def loss(model: dict, params, tokens, precision: str = "f32"):
    """Mean next-token cross entropy over tokens [B, S + 1]."""
    logits = forward(model, params, tokens[:, :-1], precision)
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
    return -jnp.mean(picked)


# ---- training: three plain AdamW steps -------------------------------------

ADAM_B1, ADAM_B2, ADAM_EPS, WEIGHT_DECAY = 0.9, 0.999, 1e-8, 1e-4


@functools.partial(jax.jit, static_argnums=(0, 4), donate_argnums=(1, 2))
def _adamw_step(lr, params, opt, tokens, static):
    model, precision = static
    model = dict(model)
    m, v, t = opt
    value, grads = jax.value_and_grad(
        lambda p: loss(model, p, tokens, precision)
    )(params)
    t = t + 1
    m = jax.tree_util.tree_map(lambda a, g: ADAM_B1 * a + (1 - ADAM_B1) * g, m, grads)
    v = jax.tree_util.tree_map(
        lambda a, g: ADAM_B2 * a + (1 - ADAM_B2) * g * g, v, grads
    )
    c1 = 1 - ADAM_B1 ** t.astype(jnp.float32)
    c2 = 1 - ADAM_B2 ** t.astype(jnp.float32)
    params = jax.tree_util.tree_map(
        lambda p, a, b: p - lr * (
            (a / c1) / (jnp.sqrt(b / c2) + ADAM_EPS) + WEIGHT_DECAY * p
        ),
        params, m, v,
    )
    grad_norms = {
        f"{g}/{n}": jnp.sqrt(jnp.sum(jnp.square(x)))
        for g, leaves in grads.items() for n, x in leaves.items()
    }
    return params, (m, v, t), value, grad_norms


def train_steps(model: dict, seed: int, batches, lr: float,
                precision: str = "f32") -> dict:
    """Follow the job's first len(batches) steps from the float32
    weights of `seed` (made anew, by perfbench/weights.py). Returns each step's
    loss, the first step's gradient norm per leaf, and the norm per
    leaf of the parameters' change over all the steps."""
    params = weights.make_params(model, seed, "float32")
    zeros = lambda: jax.tree_util.tree_map(jnp.zeros_like, params)
    opt = (zeros(), zeros(), jnp.zeros((), jnp.int32))
    static = (weights.hashable(model), precision)
    losses, first_grad = [], None
    for tokens in batches:
        params, opt, value, grad_norms = _adamw_step(
            lr, params, opt, jnp.asarray(tokens), static
        )
        losses.append(float(value))
        if first_grad is None:
            first_grad = {
                k: float(x) for k, x in jax.device_get(grad_norms).items()
            }
    del opt
    change = param_change_norms(params, model, seed)
    return {"losses": losses, "grad_norms": first_grad, "change_norms": change}


def param_change_norms(params, model: dict, seed: int) -> dict:
    """Norm per leaf of params - (the float32 weights of `seed`), the
    second made anew inside the same program so that no second copy
    outlives it. The key is an argument, so one program serves every
    seed."""
    return {
        k: float(v) for k, v in jax.device_get(
            _change_norms(weights.hashable(model), params, weights.seed_key(seed))
        ).items()
    }


@functools.partial(jax.jit, static_argnums=(0,))
def _change_norms(model_items, p, key):
    p0 = weights.init_params(dict(model_items), key, jnp.float32)
    return {
        f"{g}/{n}": jnp.sqrt(jnp.sum(jnp.square(
            p[g][n].astype(jnp.float32) - p0[g][n]
        )))
        for g, leaves in p.items() for n in leaves
    }


# ---- serving: where a served token lies in the reference's logits ----------


def served_token_gaps(model: dict, params, prompt, served, pad_to: int,
                      precision_control: str = ""):
    """One forward over prompt + served tokens. For each served token,
    the gap by which its reference logit lies below that position's
    best, in units of the position's logit scale (max |logit|).
    Returns (gaps [n_served], control_gaps or None): the control is
    the same measure for the token that the lower precision's forward
    puts first at each of those positions."""
    seq = list(prompt) + list(served)
    n, p = len(served), len(prompt)
    if len(seq) - 1 > pad_to:
        raise ValueError(f"sequence of {len(seq)} tokens exceeds {pad_to}")
    padded = seq[:-1] + [0] * (pad_to - (len(seq) - 1))
    gaps, control = _gaps_program(
        weights.hashable(model), precision_control, params,
        jnp.asarray([padded], jnp.int32), jnp.asarray(seq[1:] + [0] * (pad_to - (len(seq) - 1)), jnp.int32),
    )
    lo, hi = p - 1, p - 1 + n
    return (
        jax.device_get(gaps)[lo:hi],
        None if control is None else jax.device_get(control)[lo:hi],
    )


@functools.partial(jax.jit, static_argnums=(0, 1))
def _gaps_program(model_items, precision_control, params, tokens, nxt):
    model = dict(model_items)
    logits = forward(model, params, tokens)[0]          # [S, V]
    best = logits.max(-1)
    scale = jnp.abs(logits).max(-1)
    chosen = jnp.take_along_axis(logits, nxt[:, None], axis=-1)[:, 0]
    gaps = (best - chosen) / scale
    if not precision_control:
        return gaps, None
    low = forward(model, params, tokens, precision_control)[0]
    low_tok = jnp.argmax(low, axis=-1)
    low_chosen = jnp.take_along_axis(logits, low_tok[:, None], axis=-1)[:, 0]
    return gaps, (best - low_chosen) / scale
