"""Operations and bytes from shapes alone, kept with the benchmark so
that no PR that claims a gain can change them. `model` is a
configuration file's dict. Everything counts what the ALGORITHM needs:
recomputation (activation checkpointing, a kernel that forms the
scores twice) earns nothing.
"""


def matmul_params(model: dict) -> int:
    """Weights that a token is multiplied by: the layers' projections
    and the untied head. The embedding is a gather, not a matmul."""
    D, M = model["hidden_size"], model["intermediate_size"]
    H, KV, hd = (
        model["num_attention_heads"], model["num_key_value_heads"],
        model["head_dim"],
    )
    layer = D * H * hd + 2 * D * KV * hd + H * hd * D + 3 * D * M
    return model["num_hidden_layers"] * layer + D * model["vocab_size"]


def total_params(model: dict) -> int:
    D = model["hidden_size"]
    norms = model["num_hidden_layers"] * 2 * D + D
    return matmul_params(model) + model["vocab_size"] * D + norms


def attention_fwd_flops(model: dict, seq: int, layers: int = None) -> float:
    """Causal attention forward for ONE sequence: QK^T and PV, each
    2 * S^2/2 * H * hd multiply-adds counted as two operations."""
    L = model["num_hidden_layers"] if layers is None else layers
    H, hd = model["num_attention_heads"], model["head_dim"]
    return L * 2 * (2.0 * seq * seq / 2 * H * hd)


def train_step_flops(model: dict, rows: int, seq: int) -> dict:
    """Forward + backward of one step, without recomputation: 6 per
    matmul weight per token, and causal attention at 3x its forward
    (backward is dV, dP, dQ, dK: twice the forward's two matmuls)."""
    tokens = rows * seq
    matmul = 6.0 * matmul_params(model) * tokens
    attention = 3.0 * attention_fwd_flops(model, seq) * rows
    return {"matmul": matmul, "attention": attention,
            "total": matmul + attention}


def flash_kernel_flops(model: dict, rows: int, seq: int) -> float:
    """What the flash kernels of one train step NEED: forward 2
    score-sized matmuls, backward 5 (the scores formed once more, then
    dV, dP, dQ, dK). A backward split into a dq and a dkv kernel forms
    the scores and dP twice (9 in all); the two extra are recomputation
    and earn nothing here."""
    unit = attention_fwd_flops(model, seq) / 2 * rows  # one matmul
    return 7.0 * unit


def kv_bytes_per_token(model: dict, cell_bytes: int = 2) -> int:
    """K and V of one token over all the layers."""
    return (
        2 * model["num_key_value_heads"] * model["head_dim"] * cell_bytes
        * model["num_hidden_layers"]
    )


def weight_bytes(model: dict, param_bytes: int = 2) -> int:
    return total_params(model) * param_bytes


def paged_decode_needs(model: dict, live_tokens: int, live_slots: int,
                       cell_bytes: int = 2) -> dict:
    """One decode step of the paged-attention kernel over all layers:
    it must read every live token's K and V once and each slot's query,
    and write each slot's output; 4 operations per cached cell per
    query head (QK^T and PV)."""
    H, hd = model["num_attention_heads"], model["head_dim"]
    L = model["num_hidden_layers"]
    kv = live_tokens * kv_bytes_per_token(model, cell_bytes)
    qo = 2 * live_slots * H * hd * cell_bytes * L
    return {"bytes": kv + qo, "flops": 4.0 * live_tokens * H * hd * L}


def roofline_seconds(flops: float, nbytes: float, peaks: dict) -> dict:
    """The least time the chip could take, and which peak bounds it."""
    t_f = flops / peaks["bf16_flops_per_s"]
    t_b = nbytes / peaks["hbm_bytes_per_s"]
    return {"seconds": max(t_f, t_b),
            "bound": "flops" if t_f >= t_b else "bytes"}
