"""Operations and bytes of what the SDAR configuration adds, from
shapes alone (kept with the benchmark, like flops.py). `model` is the
configuration file's dict. Everything counts what the ALGORITHM needs.
"""


def block_paged_needs(model: dict, cells: int, cell_bytes: int = 2) -> dict:
    """The block's attention over the paged pool: a forward of one
    block reads each cell of its slot up to the block's end ONCE for
    all the block's queries (`cells`: block ends summed over the live
    forwards, times the layers: the program's `diff_cells`), K and V
    for 4 K/V heads of 128 (2048 bytes a cell), and costs QK^T and PV
    for the block's 4 positions x 32 heads x 128 numbers (4 x 32 x 128
    x 2 x 2 operations a cell). The queries in and the results out are
    a cell's worth a forward and a layer and are left out."""
    H, KV, hd = (
        model["num_attention_heads"], model["num_key_value_heads"],
        model["head_dim"],
    )
    block = model["generation"]["block_length"]
    return {
        "bytes": float(cells) * 2 * KV * hd * cell_bytes,
        "flops": float(cells) * block * H * hd * 2 * 2,
    }


def layer_params(model: dict) -> int:
    D = model["hidden_size"]
    H, KV, hd = (
        model["num_attention_heads"], model["num_key_value_heads"],
        model["head_dim"],
    )
    E, M = model["num_experts"], model["moe_intermediate_size"]
    return (2 * D * H * hd + 2 * D * KV * hd + 2 * hd + D * E
            + 3 * E * D * M + 2 * D)


def weight_bytes(model: dict, param_bytes: int = 2) -> int:
    """All the parameters held on the chip."""
    D, V = model["hidden_size"], model["vocab_size"]
    return (model["num_hidden_layers"] * layer_params(model)
            + 2 * V * D + D) * param_bytes


def forward_bytes(model: dict, slots: int, live_cells: float) -> float:
    """What ONE forward of a full batch must read: every layer (all
    128 experts: 96 slots x 4 positions route 3072 pairs a layer and
    touch them all), the head, no embedding (a gather), and each live
    cell's K and V in every layer."""
    embedding = 2 * model["vocab_size"] * model["hidden_size"]
    return (weight_bytes(model) - embedding
            + block_paged_needs(
                model, slots * live_cells * model["num_hidden_layers"]
            )["bytes"])
