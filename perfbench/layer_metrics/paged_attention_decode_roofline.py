"""Kernels: the least time the chip could take for the paged decode
attention of the traced slice (flops.py: every live token's K and V
read once per decode step; bytes-bound) over the summed device time
of the paged_attention_decode kernel in the trace. The live tokens
come from the benchmark's wrapper around engine.step: after a
dispatch of k steps the live slots' positions sum to P, so the k
steps read k*P - live*k*(k+1)/2 cells (a slot that ended inside the
dispatch is left out, which only lowers the share)."""

import flops
import lib
import trace_reduce

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "serve_tokens_per_s"
KERNELS = ("paged_attention_decode",)


def read(run):
    trace = run["trace"]
    if trace is None or run["rehearsal"]:
        return None
    seconds = trace_reduce.kernel_seconds(trace, KERNELS)
    if seconds <= 0:
        return None
    model = run["cell"]["model"]
    k = model["run"]["chunk"]
    need_bytes = need_flops = 0.0
    for t0, dur, live, positions in run["window"]["steps"]:
        if not (trace["t0"] <= t0 and t0 + dur <= trace["t1"]) or not live:
            continue
        cells = k * positions - live * k * (k + 1) // 2
        need = flops.paged_decode_needs(model, cells, live * k)
        need_bytes += need["bytes"]
        need_flops += need["flops"]
    least = flops.roofline_seconds(
        need_flops, need_bytes, lib.peaks_for(run["device_kind"])
    )["seconds"]
    return 100.0 * least / seconds
