"""Kernels: the least time the chip could take for the experts'
grouped products of the traced slice (flops_mellum2.py: each
layer-step reads the matrices of the experts it touches once; 6
operations per pair per weight column) over the summed device time of
the two grouped kernels (`moe_grouped_gate_up`, `moe_grouped_down`,
ops/grouped_matmul.py). The pairs come from the program's own counts:
`moe_pairs` and `moe_steps` on the `engine.step` spans (decode) and
`prompt_tokens` on the `engine.admit` spans (a prefill routes top_k
pairs a token of its bucket in every layer)."""

import flops
import flops_mellum2
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
    layers, top_k = model["num_hidden_layers"], model["num_experts_per_tok"]
    need_bytes = need_flops = 0.0
    found = False
    for r in ring.snapshot(trace["t0"], trace["t1"]):
        counts = r[ring.COUNTS]
        if r[ring.NAME] == "engine.step" and counts.get("moe_steps"):
            need = flops_mellum2.moe_grouped_needs(
                model, counts["moe_steps"] * layers, counts["moe_pairs"])
        elif r[ring.NAME] == "engine.admit" and "bucket" in counts:
            need = flops_mellum2.moe_grouped_needs(
                model, layers, counts["bucket"] * top_k * layers)
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
