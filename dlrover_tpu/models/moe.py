"""Mixture-of-Experts layer with expert parallelism, TPU-first.

Reference parity (SURVEY.md §2.5): ATorch's MoE stack — `MOELayer` with
all-to-all dispatch (atorch/atorch/modules/moe/moe_layer.py:87 `_AllToAll`),
expert process groups (moe_layer.py:29 `set_experts_process_group`),
switch/top-k gating (switch_gating.py), grouped-GEMM experts
(grouped_gemm_moe.py).

TPU design: the torch dispatch/all-to-all machinery collapses into two
einsums against one-hot dispatch/combine tensors (the GShard formulation).
Expert weights carry a leading E axis sharded on the mesh's "expert" axis;
GSPMD turns the dispatch einsum into the all-to-all. Grouped GEMM is what
the MXU does natively with the [E, ...] batched einsum — no custom kernel
needed. Capacity-bounded top-k gating with Switch-style load-balancing
aux loss and router z-loss.
"""

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from dlrover_tpu.parallel.sharding import constrain


@dataclasses.dataclass(frozen=True)
class MoeConfig:
    n_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 1.25
    min_capacity: int = 4
    normalize_topk: bool = True      # Mixtral-style renorm of top-k gates
    aux_loss_weight: float = 0.01    # Switch load-balance loss
    z_loss_weight: float = 1e-3      # router logit z-loss


def capacity(cfg: MoeConfig, seq: int) -> int:
    c = int(math.ceil(cfg.top_k * seq * cfg.capacity_factor / cfg.n_experts))
    return max(c, cfg.min_capacity)


def top_k_gating(
    cfg: MoeConfig,
    router_logits: jax.Array,   # [B, S, E] f32
    cap: int,
) -> Tuple[jax.Array, jax.Array, Dict[str, jax.Array]]:
    """GShard-style capacity-bounded top-k routing.

    Returns (dispatch [B,S,E,C] bool-ish f32, combine [B,S,E,C] f32,
    aux metrics incl. weighted aux_loss ready to add to the train loss).
    """
    b, s, e = router_logits.shape
    logits32 = router_logits.astype(jnp.float32)
    gates = jax.nn.softmax(logits32, axis=-1)  # [B,S,E]

    remaining = gates
    masks = []
    gate_vals = []
    for _ in range(cfg.top_k):
        idx = jnp.argmax(remaining, axis=-1)            # [B,S]
        mask = jax.nn.one_hot(idx, e, dtype=jnp.float32)
        gate_vals.append(jnp.sum(gates * mask, axis=-1))  # [B,S]
        masks.append(mask)
        remaining = remaining * (1.0 - mask)

    if cfg.normalize_topk:
        denom = jnp.maximum(sum(gate_vals), 1e-9)
        gate_vals = [g / denom for g in gate_vals]

    # position-in-expert: priority order = selection order, earlier
    # tokens first (cumsum over S), overflow dropped
    dispatch = jnp.zeros((b, s, e, cap), jnp.float32)
    combine = jnp.zeros((b, s, e, cap), jnp.float32)
    pos_offset = jnp.zeros((b, 1, e), jnp.float32)
    for mask, gv in zip(masks, gate_vals):
        pos = jnp.cumsum(mask, axis=1) - 1.0 + pos_offset  # [B,S,E]
        pos_offset = pos_offset + jnp.sum(mask, axis=1, keepdims=True)
        keep = mask * (pos < cap)
        pos_i = jnp.where(keep > 0, pos, 0).astype(jnp.int32)
        oh = jax.nn.one_hot(pos_i, cap, dtype=jnp.float32) * keep[..., None]
        dispatch = dispatch + oh                      # [B,S,E,C]
        combine = combine + oh * gv[:, :, None, None]

    # Switch aux loss: E * Σ_e (token_frac_e · prob_frac_e)
    me = jnp.mean(gates, axis=(0, 1))                          # [E]
    ce = jnp.mean(masks[0], axis=(0, 1))                       # [E]
    aux = cfg.n_experts * jnp.sum(me * ce)
    z = jnp.mean(jax.scipy.special.logsumexp(logits32, axis=-1) ** 2)
    aux_loss = cfg.aux_loss_weight * aux + cfg.z_loss_weight * z
    dropped = 1.0 - jnp.sum(dispatch) / (b * s * cfg.top_k)
    metrics = {
        "moe_aux_loss": aux_loss,
        "moe_balance": aux,
        "moe_dropped_frac": dropped,
    }
    return dispatch, combine, metrics


def init_moe_mlp(
    key: jax.Array,
    cfg: MoeConfig,
    dim: int,
    mlp_dim: int,
    n_layers: Optional[int] = None,
    param_dtype=jnp.float32,
) -> Dict[str, jax.Array]:
    """Expert-stacked SwiGLU weights (leading [L?, E] axes)."""
    lead = (cfg.n_experts,) if n_layers is None else (n_layers, cfg.n_experts)
    rlead = () if n_layers is None else (n_layers,)
    ks = jax.random.split(key, 4)

    def dense(k, shape, fan_in):
        return jax.random.normal(k, shape, param_dtype) / math.sqrt(fan_in)

    return {
        "router": dense(ks[0], rlead + (dim, cfg.n_experts), dim),
        "we_gate": dense(ks[1], lead + (dim, mlp_dim), dim),
        "we_up": dense(ks[2], lead + (dim, mlp_dim), dim),
        "we_down": dense(ks[3], lead + (mlp_dim, dim), mlp_dim),
    }


def moe_partition_rules():
    """Rules for the expert weights: experts on the "expert" mesh axis,
    TP/FSDP on the matmul dims (leading L axis from the scan stack)."""
    return [
        (r"router$", P("pipe")),
        (r"we_gate", P("pipe", "expert", "fsdp", "tensor")),
        (r"we_up", P("pipe", "expert", "fsdp", "tensor")),
        (r"we_down", P("pipe", "expert", "tensor", "fsdp")),
    ]


def moe_mlp(
    cfg: MoeConfig,
    params: Dict[str, jax.Array],   # router [D,E], we_* [E,D,M]/[E,M,D]
    x: jax.Array,                   # [B, S, D]
    mesh=None,
    compute_dtype=jnp.bfloat16,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Expert-parallel SwiGLU MoE block.

    dispatch einsum → [E, B, C, D] (GSPMD all-to-all over "expert"),
    batched expert GEMMs on the MXU, combine einsum back to [B, S, D].
    """
    b, s, d = x.shape
    cap = capacity(cfg, s)
    router_logits = (
        x.astype(jnp.float32) @ params["router"].astype(jnp.float32)
    )
    dispatch, combine, metrics = top_k_gating(cfg, router_logits, cap)

    xd = x.astype(compute_dtype)
    disp = dispatch.astype(compute_dtype)
    expert_in = jnp.einsum("bsec,bsd->ebcd", disp, xd)
    expert_in = constrain(
        expert_in, mesh, "expert", ("data", "fsdp"), None, None
    )
    wg = params["we_gate"].astype(compute_dtype)
    wu = params["we_up"].astype(compute_dtype)
    wd = params["we_down"].astype(compute_dtype)
    h = jax.nn.silu(jnp.einsum("ebcd,edm->ebcm", expert_in, wg))
    h = h * jnp.einsum("ebcd,edm->ebcm", expert_in, wu)
    h = constrain(h, mesh, "expert", ("data", "fsdp"), None, "tensor")
    out = jnp.einsum("ebcm,emd->ebcd", h, wd)
    out = constrain(
        out, mesh, "expert", ("data", "fsdp"), None, None
    )
    # combine in f32 (GShard formulation): the contraction over the
    # expert axis is where GSPMD inserts the cross-expert all-reduce, so
    # f32 here buys reduction accuracy at negligible cost — and keeps the
    # collective f32, which XLA CPU's AllReducePromotion pass requires
    # (it crashes cloning bf16 all-reduces inside scan bodies)
    y = jnp.einsum(
        "bsec,ebcd->bsd", combine, out.astype(jnp.float32)
    )
    return y.astype(x.dtype), metrics


# ---------------------------------------------------------------------------
# routing that drops no token (the serving path, models/decode.py)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Routing:
    """How `dropless_moe` chooses and weights a token's experts, read
    from the model's config (`LlamaConfig.routing`)."""

    top_k: int
    # "softmax": probabilities over every expert, the top_k largest,
    # divided by their sum. "sigmoid": scores s = sigmoid(logits); the
    # CHOICE is made on s + bias (the bias never reaches a weight),
    # among the `topk_group` best of `n_group` groups of experts where
    # groups are given (a group's score: the sum of its two largest
    # s + bias); weights are `scaling` * s / (the chosen ones' sum).
    scoring: str = "softmax"
    n_group: int = 0
    topk_group: int = 0
    scaling: float = 1.0
    # (first, count): the experts whose matrices are held here; the
    # sum over the chosen runs over those of them only
    held: Tuple[int, ...] = ()


def route(logits: jax.Array, routing: Routing, bias=None):
    """float32 logits [T, E] -> (weights [T, k] float32, chosen [T, k]
    int32). Ties go to the lower index (`lax.top_k`)."""
    k = routing.top_k
    if routing.scoring == "softmax":
        probs = jax.nn.softmax(logits, axis=-1)
        weights, chosen = jax.lax.top_k(probs, k)
        return weights / jnp.sum(weights, axis=-1, keepdims=True), chosen
    t, e = logits.shape
    scores = jax.nn.sigmoid(logits)
    choice = scores if bias is None else scores + bias.astype(jnp.float32)
    if routing.n_group:
        per = e // routing.n_group
        grouped = choice.reshape(t, routing.n_group, per)
        group_score = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)
        _, best = jax.lax.top_k(group_score, routing.topk_group)
        stays = jnp.any(
            best[:, :, None]
            == jnp.arange(routing.n_group, dtype=best.dtype)[None, None, :],
            axis=1,
        )                                                   # [T, n_group]
        choice = jnp.where(
            jnp.repeat(stays, per, axis=1), choice, -jnp.inf
        )
    _, chosen = jax.lax.top_k(choice, k)
    weights = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = routing.scaling * weights / jnp.sum(
        weights, axis=-1, keepdims=True
    )
    return weights, chosen


def dropless_rows(pairs: int, held: int) -> int:
    """Rows of the padded layout `dropless_moe` hands the grouped
    kernels for `pairs` (token, expert) pairs over `held` experts:
    every run is padded to whole sub-tiles (ops/grouped_matmul
    `SUB_ROWS` rows), so at most 15 rows more for each expert that can
    hold a pair at all. Static: the bound holds for any deal."""
    from dlrover_tpu.ops.grouped_matmul import SUB_ROWS

    return (pairs + (SUB_ROWS - 1) * min(pairs, held)) // SUB_ROWS * SUB_ROWS


def dropless_moe(
    h: jax.Array,              # [T, D] normed tokens
    router: jax.Array,         # [D, E]
    w_gate: jax.Array,         # [E_held, D, M], or [L, E_held, D, M] with `layer`
    w_up: jax.Array,
    w_down: jax.Array,         # [E_held, M, D] / [L, E_held, M, D]
    routing,                   # a Routing, or top_k of the softmax router
    layer=None,
    bias=None,                 # [E] float32: the sigmoid router's choice-only bias
) -> Tuple[jax.Array, jax.Array]:
    """Routed experts without capacity: every (token, expert) pair
    whose expert is held here is computed, at any load. Returns
    (y [T, D] in h's dtype, int32[E_held] pairs routed to each held
    expert); T * top_k pairs were routed anywhere.

    The router (`route`: softmax then top-k, or the group-limited
    sigmoid), the weights and the combine are float32. The pairs are
    sorted by expert with a counting sort (a pair's rank inside its
    expert is a running count, stable in token order), laid out with
    every expert's run padded to whole sub-tiles of 16 rows,
    multiplied group by group (ops/grouped_matmul.expert_mlp: a
    Pallas kernel on the chip that walks each run to its length,
    `lax.ragged_dot` elsewhere), and gathered back to their tokens,
    where a token's results are weighted and summed. One code path
    for a prefill of thousands of tokens and a decode batch of tens.

    Where `routing.held` names a share of the experts, the router
    still ranks all E of them and the weights are normalised over all
    the chosen; the pairs that land on experts held elsewhere never
    enter the sort, and what they would add is left out (expert
    parallelism's share of the layer, without its exchange). The rows'
    bound is static and for the worst deal (every pair held here);
    the kernels walk the runs that came and nothing past them."""
    from dlrover_tpu.ops import grouped_matmul as gmm

    if not isinstance(routing, Routing):
        routing = Routing(top_k=routing)
    top_k = routing.top_k
    t, d = h.shape
    e = router.shape[-1]
    first, held = routing.held or (0, e)
    share = held != e
    pairs = t * top_k
    with jax.named_scope("moe_route"):
        logits = jnp.dot(
            h.astype(jnp.float32), router.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST,
        )
        weights, chosen = route(logits, routing, bias)     # [T, k]
        flat = chosen.reshape(pairs)                        # pair -> expert
        if share:
            flat = flat - first
            here = (flat >= 0) & (flat < held)
            weights = jnp.where(here.reshape(t, top_k), weights, 0.0)
        onehot = (
            flat[:, None] == jnp.arange(held, dtype=flat.dtype)[None, :]
        ).astype(jnp.int32)                                 # [pairs, E_held]
        counts = jnp.sum(onehot, axis=0)                    # [E_held]
        rank = jnp.sum(
            (jnp.cumsum(onehot, axis=0) - 1) * onehot, axis=1
        )                                                   # [pairs]
        rows = dropless_rows(pairs, held)
        group_rows = -(-counts // gmm.SUB_ROWS) * gmm.SUB_ROWS
        ends = jnp.cumsum(group_rows)
        if share:
            # a pair held elsewhere lands past the last row: dropped
            # by the scatter, read back as zeros by the gather
            dest = jnp.where(
                here, (ends - group_rows)[jnp.clip(flat, 0, held - 1)]
                + rank, rows,
            )
        else:
            dest = (ends - group_rows)[flat] + rank         # pair -> row
        # row -> token (T: the appended zero row, for padding rows)
        src = jnp.full((rows,), t, jnp.int32).at[dest].set(
            jnp.arange(pairs, dtype=jnp.int32) // top_k,
            **({"mode": "drop"} if share else {}),
        )
        x = jnp.concatenate([h, jnp.zeros((1, d), h.dtype)])[src]
    y = gmm.expert_mlp(x, w_gate, w_up, w_down, group_rows, layer=layer)
    with jax.named_scope("moe_combine"):
        if share:
            y = jnp.take(y, dest, axis=0, mode="fill", fill_value=0)
        else:
            y = y[dest]
        y = y.reshape(t, top_k, d).astype(jnp.float32)
        out = jnp.sum(y * weights[:, :, None], axis=1)
    return out.astype(h.dtype), counts
