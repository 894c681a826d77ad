"""Operations and bytes of what the GigaChat3.1 configuration adds,
from shapes alone (kept with the benchmark, like flops.py). `model`
is the configuration file's dict, in the source's key names, with the
share this chip holds (`n_routed_experts`: the experts held here,
`routed_experts_published`: the router's width). Everything counts
what the ALGORITHM needs.
"""


def latent_row_bytes(model: dict, cell_bytes: int = 2) -> int:
    """What one cached token takes a layer: the latent and the shared
    rotary key (576 numbers, 1152 bytes; the pool's padding to 640 is
    the program's, not the algorithm's)."""
    return (model["kv_lora_rank"] + model["qk_rope_head_dim"]) * cell_bytes


def latent_decode_needs(model: dict, cells: int, cell_bytes: int = 2) -> dict:
    """Decode attention in the absorbed form over `cells` live latent
    rows (a row: one position of one layer, read by one slot's one
    step): each is read ONCE for every head's score (kv_lora_rank +
    qk_rope_head_dim products a head) and value sum (kv_lora_rank a
    head), 2 operations a product."""
    H = model["num_attention_heads"]
    cr, rope = model["kv_lora_rank"], model["qk_rope_head_dim"]
    return {
        "bytes": float(cells) * latent_row_bytes(model, cell_bytes),
        "flops": 2.0 * cells * H * (cr + rope + cr),
    }


def expected_experts_touched(held: int, published: int, pairs: float) -> float:
    """Held experts that get at least one of `pairs` routed pairs, if
    the pairs fell evenly over the `published` experts."""
    return held * (1.0 - (1.0 - 1.0 / published) ** pairs)


def moe_held_grouped_needs(model: dict, touched: float, held_pairs: float,
                           param_bytes: int = 2) -> dict:
    """The held experts' three products: `touched` counts the (layer,
    step, expert) triples in which a held expert got at least one
    pair (each must read that expert's three matrices once; an expert
    no pair lands on is not read), `held_pairs` the pairs that landed
    here (each pair's row goes in and out of both kernels and costs 2
    operations per weight it is multiplied by)."""
    D, M = model["hidden_size"], model["moe_intermediate_size"]
    if touched <= 0 or held_pairs <= 0:
        return {"bytes": 0.0, "flops": 0.0}
    weights = touched * 3 * D * M * param_bytes
    rows = held_pairs * (2 * D + 2 * M) * param_bytes
    return {"bytes": weights + rows, "flops": 6.0 * held_pairs * D * M}


def attention_params(model: dict) -> int:
    D, H = model["hidden_size"], model["num_attention_heads"]
    qr, cr = model["q_lora_rank"], model["kv_lora_rank"]
    nope, rope, vd = (
        model["qk_nope_head_dim"], model["qk_rope_head_dim"],
        model["v_head_dim"],
    )
    return (D * qr + qr * H * (nope + rope) + D * (cr + rope)
            + cr * H * (nope + vd) + H * vd * D)


def matmul_params(model: dict) -> dict:
    """Parameters of the matrices held on this chip, by part."""
    D, M, W = (
        model["hidden_size"], model["moe_intermediate_size"],
        model["intermediate_size"],
    )
    L0 = model["first_k_dense_replace"]
    L1 = model["num_hidden_layers"] - L0
    return {
        "attention": (L0 + L1) * attention_params(model),
        "dense_ffn": L0 * 3 * D * W,
        "shared": L1 * model["n_shared_experts"] * 3 * D * M,
        "router": L1 * D * model["routed_experts_published"],
        "held_experts": L1 * model["n_routed_experts"] * 3 * D * M,
        "embedding": model["vocab_size"] * D,
        "head": model["vocab_size"] * D,
    }


def weight_bytes(model: dict, param_bytes: int = 2) -> int:
    return sum(matmul_params(model).values()) * param_bytes


def decode_step_needs(model: dict, slots: int, live_positions: float,
                      param_bytes: int = 2) -> dict:
    """What one decode step of a full batch must read and compute:
    every matrix a token is multiplied by (no embedding: a gather; all
    the held experts: a batch of 96 routes 768 pairs a layer over 256
    and touches them all but for chance) and the live latent rows of
    every layer (`live_positions`: positions summed over the slots)."""
    parts = matmul_params(model)
    weights = (sum(parts.values()) - parts["embedding"]) * param_bytes
    attn = latent_decode_needs(
        model, live_positions * model["num_hidden_layers"])
    dense = sum(parts.values()) - parts["embedding"] - parts["held_experts"]
    L1 = model["num_hidden_layers"] - model["first_k_dense_replace"]
    held_pairs = (slots * model["num_experts_per_tok"] * L1
                  * model["n_routed_experts"]
                  / model["routed_experts_published"])
    D, M = model["hidden_size"], model["moe_intermediate_size"]
    return {
        "bytes": weights + attn["bytes"],
        "flops": 2.0 * slots * dense + 6.0 * held_pairs * D * M
        + attn["flops"],
    }
