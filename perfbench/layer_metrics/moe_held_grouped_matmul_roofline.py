"""Kernels: the least time the chip could take for the HELD experts'
grouped products of the traced slice
(flops_gigachat3.moe_held_grouped_needs: each held expert that gets a
pair in a layer-step is read once, and one that gets none is not; 6
operations per held pair per weight column) over the summed device time of the two
grouped kernels (`moe_grouped_gate_up`, `moe_grouped_down`,
ops/grouped_matmul.py). moe_grouped_matmul_roofline's reader counts
every expert as held and reads other key names, so this cell has its
own. The counts are the program's own: `moe_experts_touched` (the
(layer, step, expert) triples that got a pair: the router's bias
loads the held experts unevenly, and a decode step of 96 slots leaves
some without a row) and `moe_held_pairs` on the `engine.step` spans
(decode), and `bucket` on the `engine.admit` spans (a prefill routes
top_k pairs a token of its bucket in every expert layer; the held
share of them is expected here, and the experts they touch by
`expected_experts_touched`). None on a program without them."""

import flops
import flops_gigachat3
import lib
import trace_reduce

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "serve_tokens_per_s"
KERNELS = ("moe_grouped_",)


def read(run):
    trace = run["trace"]
    if trace is None or run["rehearsal"]:
        return None
    seconds = trace_reduce.kernel_seconds(trace, KERNELS)
    if seconds <= 0:
        return None
    try:
        from dlrover_tpu.common import trace as ring
    except ImportError:
        return None
    model = run["cell"]["model"]
    layers = model["num_hidden_layers"] - model["first_k_dense_replace"]
    top_k = model["num_experts_per_tok"]
    share = model["n_routed_experts"] / model["routed_experts_published"]
    need_bytes = need_flops = 0.0
    found = False
    for r in ring.snapshot(trace["t0"], trace["t1"]):
        counts = r[ring.COUNTS]
        if r[ring.NAME] == "engine.step" and counts.get("moe_experts_touched"):
            need = flops_gigachat3.moe_held_grouped_needs(
                model, counts["moe_experts_touched"],
                counts["moe_held_pairs"])
        elif r[ring.NAME] == "engine.admit" and "bucket" in counts:
            routed = counts["bucket"] * top_k
            touched = flops_gigachat3.expected_experts_touched(
                model["n_routed_experts"],
                model["routed_experts_published"], routed)
            need = flops_gigachat3.moe_held_grouped_needs(
                model, layers * touched, layers * routed * share)
        else:
            continue
        found = True
        need_bytes += need["bytes"]
        need_flops += need["flops"]
    if not found:
        return None
    least = flops.roofline_seconds(
        need_flops, need_bytes, lib.peaks_for(run["device_kind"])
    )["seconds"]
    return 100.0 * least / seconds
