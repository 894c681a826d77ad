"""Operations and bytes of the kernels the Mellum2 configuration adds,
from shapes alone (kept with the benchmark, like flops.py). `model`
is the configuration file's dict. Everything counts what the
ALGORITHM needs.
"""


def layer_kinds(model: dict) -> list:
    return list(model["layer_types"][: model["num_hidden_layers"]])


def hybrid_paged_decode_needs(model: dict, full_cells: int,
                              window_cells: int, queries: int,
                              cell_bytes: int = 2) -> dict:
    """Decode attention over the two classes of pages: a full layer
    must read every live position's K and V once a step (`full_cells`:
    positions summed over slots and steps), a window layer only a
    slot's last `sliding_window` of them (`window_cells`: min(context,
    window) summed likewise); each layer reads a query and writes an
    output per slot and step (`queries`); 4 operations per cached cell
    per query head (QK^T and PV)."""
    H, KV, hd = (
        model["num_attention_heads"], model["num_key_value_heads"],
        model["head_dim"],
    )
    kinds = layer_kinds(model)
    n_full = kinds.count("full_attention")
    n_win = kinds.count("sliding_attention")
    cells = n_full * full_cells + n_win * window_cells
    kv = cells * 2 * KV * hd * cell_bytes
    qo = 2 * queries * H * hd * cell_bytes * len(kinds)
    return {"bytes": kv + qo, "flops": 4.0 * cells * H * hd}


def expected_experts_touched(model: dict, pairs: float) -> float:
    """Experts that get at least one of `pairs` pairs, if the pairs
    fell evenly: uneven routing touches fewer, which only lowers a
    share computed from this."""
    E = model["num_experts"]
    return E * (1.0 - (1.0 - 1.0 / E) ** pairs)


def moe_grouped_needs(model: dict, calls: float, pairs: float,
                      param_bytes: int = 2) -> dict:
    """The experts' three products over `calls` layer-steps that route
    `pairs` (token, expert) pairs in all: each call must read the
    matrices of the experts it touches once, each pair's row in and
    out of both kernels, and costs 2 operations per weight it is
    multiplied by."""
    D, M = model["hidden_size"], model["moe_intermediate_size"]
    if calls <= 0 or pairs <= 0:
        return {"bytes": 0.0, "flops": 0.0}
    touched = expected_experts_touched(model, pairs / calls)
    weights = calls * touched * 3 * D * M * param_bytes
    rows = pairs * (2 * D + 2 * M) * param_bytes
    return {"bytes": weights + rows, "flops": 6.0 * pairs * D * M}


def weight_bytes(model: dict, param_bytes: int = 2) -> int:
    """All the parameters held on the chip."""
    D, V = model["hidden_size"], model["vocab_size"]
    H, KV, hd = (
        model["num_attention_heads"], model["num_key_value_heads"],
        model["head_dim"],
    )
    E, M = model["num_experts"], model["moe_intermediate_size"]
    layer = 2 * D * H * hd + 2 * D * KV * hd + D * E + 3 * E * D * M + 2 * D
    return (model["num_hidden_layers"] * layer + 2 * V * D + D) * param_bytes
