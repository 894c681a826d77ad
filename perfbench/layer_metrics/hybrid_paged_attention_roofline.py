"""Kernels: the least time the chip could take for the decode
attention of the traced slice over the two classes of pages
(flops_mellum2.py: a full layer reads every live position's K and V
once a step, a window layer min(context, sliding_window) of them;
bytes-bound) over the summed device time of the two paged kernels
(`paged_attention_decode` on the full layers,
`paged_attention_decode_window` on the window layers; one kernel with
a static window). The cells come from the driver's record of every
engine step (the live slots' positions after the dispatch)."""

import flops
import flops_mellum2
import lib
import trace_reduce

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "serve_tokens_per_s"
KERNELS = ("paged_attention_decode",)  # a prefix: both kernels


def read(run):
    trace = run["trace"]
    cells = run["window"].get("step_cells")
    if trace is None or run["rehearsal"] or not cells:
        return None
    seconds = trace_reduce.kernel_seconds(trace, KERNELS)
    if seconds <= 0:
        return None
    model = run["cell"]["model"]
    need_bytes = need_flops = 0.0
    for (t0, dur, live, _), (full, window, queries) in zip(
        run["window"]["steps"], cells
    ):
        if not (trace["t0"] <= t0 and t0 + dur <= trace["t1"]) or not live:
            continue
        need = flops_mellum2.hybrid_paged_decode_needs(
            model, full, window, queries)
        need_bytes += need["bytes"]
        need_flops += need["flops"]
    least = flops.roofline_seconds(
        need_flops, need_bytes, lib.peaks_for(run["device_kind"])
    )["seconds"]
    return 100.0 * least / seconds
