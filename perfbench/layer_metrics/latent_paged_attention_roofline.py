"""Kernels: the least time the chip could take for the decode
attention over the latent pool in the traced slice
(flops_gigachat3.latent_decode_needs: each live latent row, 1152
bytes, read ONCE for all 64 heads' scores and value sums, 64 x (576 +
512) x 2 operations a row: whichever of the two bounds is the larger)
over the summed device time of the operations that compute it
(`paged_attention_decode_latent`, ops/paged_attention.py). The rows
are the program's own count: `latent_cells` on the `engine.step`
spans (positions read by the live slots' steps of the harvested
dispatch, times the layers). None on a program without them."""

import flops
import flops_gigachat3
import lib
import trace_reduce

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "serve_tokens_per_s"
KERNELS = ("paged_attention_decode_latent",)


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
    cells = sum(
        r[ring.COUNTS].get("latent_cells", 0)
        for r in ring.snapshot(trace["t0"], trace["t1"])
        if r[ring.NAME] == "engine.step"
    )
    if not cells:
        return None
    need = flops_gigachat3.latent_decode_needs(run["cell"]["model"], cells)
    least = flops.roofline_seconds(
        need["flops"], need["bytes"], lib.peaks_for(run["device_kind"])
    )["seconds"]
    return 100.0 * least / seconds
