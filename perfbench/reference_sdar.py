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


# ---- the same equations in blocks: one jitted program a layer --------------
#
# A served request is checked from its TRAJECTORY: the committed stream
# (prompt + every committed block) and, a denoising forward, the
# block's ids before it and what it unmasked. The reference recomputes
# the committed stream's keys and values under M once (`_stream_layer`,
# a layer a call), then every denoising forward's 4 rows against them
# (`_rows_layer`: a forward's queries see the stream's cells before
# their block and the block's own 4 keys), `ROWS` forwards a call.

ROWS = 256  # denoising forwards a call of the rows' programs

_KEYS = (
    "hidden_size", "num_attention_heads", "num_key_value_heads", "head_dim",
    "num_experts", "num_experts_per_tok", "moe_intermediate_size",
    "norm_topk_prob", "vocab_size", "num_hidden_layers", "rms_norm_eps",
    "rope_theta",
)


def _hash(model: dict) -> tuple:
    return tuple(sorted(
        (k, json.dumps(v, sort_keys=True)) for k, v in model.items()
        if k in _KEYS
    ))


def _unhash(items: tuple) -> dict:
    return {k: json.loads(v) for k, v in items}


def _qkv(model, precision, h, lp, positions):
    """h [S, D] at `positions` [S] -> q [S, H, hd], k, v [S, KV, hd],
    normed and turned."""
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
    return _rope(q, positions, theta), _rope(k, positions, theta), v


def _experts_scanned(model, precision, h, lp):
    """`_experts`, one expert at a time (a scan over the experts'
    stack: one expert's float32 copy lives at once)."""
    m = _rms_norm(h, lp["mlp_norm"], model["rms_norm_eps"])
    w = routing_weights(model, m, lp["router"])

    def one(out, expert):
        wg, wu, wd, we = expert
        gate = jax.nn.silu(
            _einsum("sd,dm->sm", m, wg.astype(jnp.float32), precision))
        up = _einsum("sd,dm->sm", m, wu.astype(jnp.float32), precision)
        y = _einsum("sm,md->sd", gate * up, wd.astype(jnp.float32), precision)
        return out + we[:, None] * y, None

    out, _ = jax.lax.scan(
        one, jnp.zeros_like(h),
        (lp["we_gate"], lp["we_up"], lp["we_down"], w.T),
    )
    return h + out


def _small(lp):
    return {
        k: v.astype(jnp.float32) for k, v in lp.items()
        if not k.startswith("we_")
    }


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _stream_layer(model_items, block, precision, h, lp):
    """One layer over the committed stream h [S, D] under M: (h, the
    layer's keys and values [S, KV, hd])."""
    model = _unhash(model_items)
    small = _small(lp)
    H, KV, hd = (
        model["num_attention_heads"], model["num_key_value_heads"],
        model["head_dim"],
    )
    s = h.shape[0]
    positions = jnp.arange(s)
    q, k, v = _qkv(model, precision, h, small, positions)
    q = q.reshape(s, KV, H // KV, hd)
    scores = _einsum("qkgd,skd->kgqs", q, k, precision) / math.sqrt(hd)
    seen = block_mask(positions, block)[None, None]
    probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
    attn = _einsum("kgqs,skd->qkgd", probs, v, precision)
    h = h + _einsum(
        "se,ed->sd", attn.reshape(s, H * hd), small["wo"], precision)
    return _experts_scanned(model, precision, h, dict(lp, **small)), k, v


@functools.partial(jax.jit, static_argnums=(0, 1))
def _rows_layer(model_items, precision, h, lp, starts, k_stream, v_stream):
    """One layer over ROWS denoising forwards h [F, B, D], forward f's
    block starting at `starts[f]`: its queries see the stream's cells
    before `starts[f]` and the block's own B keys."""
    model = _unhash(model_items)
    small = _small(lp)
    H, KV, hd = (
        model["num_attention_heads"], model["num_key_value_heads"],
        model["head_dim"],
    )
    f, b, d = h.shape
    positions = (starts[:, None] + jnp.arange(b)[None, :]).reshape(f * b)
    q, k, v = _qkv(model, precision, h.reshape(f * b, d), small, positions)
    q = q.reshape(f, b, KV, H // KV, hd)
    k, v = k.reshape(f, b, KV, hd), v.reshape(f, b, KV, hd)
    scale = 1.0 / math.sqrt(hd)
    old = _einsum("fqkgd,skd->fkgqs", q, k_stream, precision) * scale
    own = _einsum("fqkgd,fskd->fkgqs", q, k, precision) * scale
    before = jnp.arange(k_stream.shape[0])[None, :] < starts[:, None]
    old = jnp.where(before[:, None, None, None, :], old, -jnp.inf)
    probs = jax.nn.softmax(jnp.concatenate([old, own], axis=-1), axis=-1)
    n_old = k_stream.shape[0]
    attn = (
        _einsum("fkgqs,skd->fqkgd", probs[..., :n_old], v_stream, precision)
        + _einsum("fkgqs,fskd->fqkgd", probs[..., n_old:], v, precision)
    )
    out = h.reshape(f * b, d) + _einsum(
        "se,ed->sd", attn.reshape(f * b, H * hd), small["wo"], precision)
    out = _experts_scanned(model, precision, out, dict(lp, **small))
    return out.reshape(f, b, d)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _rows_head(model_items, precision, h, scale, head, ids_a, ids_b):
    """The head over ROWS forwards' rows, reduced to what is compared,
    each [F, B]: the best logit, the logit scale (max |logit|), the
    arg-max id, its softmax probability (the position's confidence),
    and the logits of the ids `ids_a` and `ids_b`."""
    model = _unhash(model_items)
    h = _rms_norm(h, scale.astype(jnp.float32), model["rms_norm_eps"])
    logits = _einsum("fbd,dv->fbv", h, head.astype(jnp.float32), precision)
    best = logits.max(-1)

    def at(ids):
        return jnp.take_along_axis(
            logits, jnp.maximum(ids, 0)[..., None], axis=-1)[..., 0]

    return {
        "best": best, "scale": jnp.abs(logits).max(-1),
        "argmax": jnp.argmax(logits, axis=-1),
        "conf": 1.0 / jnp.sum(jnp.exp(logits - best[..., None]), axis=-1),
        "at_a": at(ids_a), "at_b": at(ids_b),
    }


def stream_cells(model, params, ids, block, precision="f32"):
    """The keys and values, a layer, of the committed stream `ids`
    (whole blocks) under M: [(k, v)] with k, v [S, KV, hd]."""
    items = _hash(model)
    h = params["embed"]["weight"][jnp.asarray(ids, jnp.int32)].astype(
        jnp.float32)
    cells = []
    for layer in range(model["num_hidden_layers"]):
        lp = {k: v[layer] for k, v in params["layers"].items()}
        h, k, v = _stream_layer(items, block, precision, h, lp)
        cells.append((k, v))
    return cells


def rows_judged(model, params, cells, starts, ids_in, ids_a, ids_b,
                precision="f32"):
    """Every denoising forward's rows against the stream's `cells`:
    `starts` [F], `ids_in` [F, B] the block's ids before the forward;
    `_rows_head`'s numbers as numpy arrays [F, B]."""
    import numpy as np

    items = _hash(model)
    n = len(starts)
    out = {}
    for lo in range(0, n, ROWS):
        hi = min(lo + ROWS, n)
        pad = ROWS - (hi - lo)

        def padded(x, fill=0):
            x = np.asarray(x[lo:hi], np.int32)
            return jnp.asarray(np.pad(
                x, [(0, pad)] + [(0, 0)] * (x.ndim - 1),
                constant_values=fill))

        st = padded(starts)
        h = params["embed"]["weight"][padded(ids_in)].astype(jnp.float32)
        for layer in range(model["num_hidden_layers"]):
            lp = {k: v[layer] for k, v in params["layers"].items()}
            h = _rows_layer(items, precision, h, lp, st, *cells[layer])
        got = _rows_head(
            items, precision, h, params["final_norm"]["scale"],
            params["lm_head"]["weight"], padded(ids_a), padded(ids_b),
        )
        for name, arr in jax.device_get(got).items():
            out.setdefault(name, []).append(arr[: hi - lo])
    return {name: np.concatenate(parts) for name, parts in out.items()}


# ---- serving: a request's trajectory against the reference -----------------


def trajectory_states(prompt, rows, block: int, mask_id: int):
    """A request's forwards as the engine recorded them (`rows`: in
    order, (the block's first position, 1 a denoising forward or 2 a
    commit, the ids it unmasked with -1 elsewhere or the commit's
    ids)) -> (the committed stream: the prompt's whole blocks and
    every committed block, [the denoising forwards that led to the
    commits: {start, ids_in, masked, taken, toks}]). A block that was
    begun again after a preemption keeps the forwards of its last
    run: they are read back from its commit until every position that
    was masked is covered."""
    p = len(prompt)
    first = p - p % block
    stream = [int(t) for t in prompt[:first]]
    forwards, pending = [], []
    for start, phase, ids in rows:
        if phase == 1:
            pending.append((start, ids))
            continue
        given = max(p - start, 0)
        state = list(ids)
        mine = []
        for s0, took in reversed(pending):
            if s0 != start:
                continue
            idx = [j for j, t in enumerate(took) if t >= 0]
            if any(state[j] == "?" for j in idx):
                break  # an earlier, abandoned run of this block
            mine.append(idx)
            for j in idx:
                state[j] = "?"
            if all(state[j] == "?" for j in range(given, block)):
                break
        if any(state[j] != "?" for j in range(given, block)):
            raise ValueError(
                f"the block at {start} was committed with positions no "
                "recorded forward unmasked"
            )
        masked = [j >= given for j in range(block)]
        ids_in = [
            int(ids[j]) if j < given else mask_id for j in range(block)
        ]
        for idx in reversed(mine):
            forwards.append({
                "start": start, "ids_in": list(ids_in),
                "masked": list(masked),
                "taken": [j in idx for j in range(block)],
                "toks": [int(ids[j]) if j in idx else -1
                         for j in range(block)],
            })
            for j in idx:
                ids_in[j], masked[j] = int(ids[j]), False
        stream.extend(int(t) for t in ids)
        pending = [x for x in pending if x[0] != start]
    return stream, forwards


def _order_gaps(conf, masked, taken):
    """A forward with a choice each (more masked positions than it
    unmasked): the confidence of the best masked position NOT
    unmasked less that of the worst unmasked, floored at 0."""
    import numpy as np

    gaps = []
    for c, m, t in zip(conf, masked, taken):
        left, took = c[m & ~t], c[t]
        if left.size and took.size:
            gaps.append(max(0.0, float(left.max() - took.min())))
    return np.asarray(gaps, np.float64)


def check_request(model: dict, params, prompt, rows, pad_to: int,
                  block: int, mask_id: int, count: int, control: str = ""):
    """One served request against the reference. Returns {"gaps": a
    served (unmasked) token each, the gap by which its reference logit
    lies below that position's best at that forward, over the
    position's logit scale; "order": a forward with a choice each, the
    reference's confidence of the best masked position the program did
    not unmask less that of the worst it did, floored at 0; and with
    `control`, "control_gaps" / "control_order": the same two for what
    the forward at that lower precision would have unmasked in the
    program's place (its `count` most confident masked positions, its
    arg-max ids), judged by the float32 reference}."""
    import numpy as np

    stream, forwards = trajectory_states(prompt, rows, block, mask_id)
    if len(stream) > pad_to:
        raise ValueError(f"a stream of {len(stream)} exceeds {pad_to}")
    ids = stream + [0] * (pad_to - len(stream))
    starts = [f["start"] for f in forwards]
    ids_in = [f["ids_in"] for f in forwards]
    masked = np.asarray([f["masked"] for f in forwards], bool)
    taken = np.asarray([f["taken"] for f in forwards], bool)
    toks = np.asarray([f["toks"] for f in forwards], np.int32)
    low = None
    if control:
        low = rows_judged(
            model, params, stream_cells(model, params, ids, block, control),
            starts, ids_in, toks, toks, control,
        )
    cells = stream_cells(model, params, ids, block)
    got = rows_judged(
        model, params, cells, starts, ids_in, toks,
        toks if low is None else low["argmax"],
    )
    gap = (got["best"] - got["at_a"]) / got["scale"]
    out = {
        "gaps": gap[taken],
        "order": _order_gaps(got["conf"], masked, taken),
    }
    if low is not None:
        # what the lower precision would have unmasked: its `count`
        # most confident masked positions, ties to the lower position
        score = np.where(masked, low["conf"], -1.0)
        order = np.argsort(-score, axis=1, kind="stable")
        rank = np.argsort(order, axis=1, kind="stable")
        taken_low = masked & (rank < count)
        gap_low = (got["best"] - got["at_b"]) / got["scale"]
        out["control_gaps"] = gap_low[taken_low]
        out["control_order"] = _order_gaps(got["conf"], masked, taken_low)
    return out
