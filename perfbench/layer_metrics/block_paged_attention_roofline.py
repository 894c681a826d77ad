"""Kernels: the least time the chip could take for the blocks'
attention over the paged pool in the traced slice
(flops_sdar.block_paged_needs: each cell up to a block's end, 2048
bytes of K and V, read ONCE for the block's 4 positions x 32 heads,
4 x 32 x 128 x 2 x 2 operations a cell: whichever of the two bounds is
the larger) over the summed device time of the operations that compute
it (`paged_attention_decode_block`, ops/paged_attention.py). The cells
are the program's own count: `diff_cells` on the `engine.step` spans
(block ends summed over the live forwards of the harvested dispatch,
times the layers). None on a program without them."""

import flops
import flops_sdar
import lib
import trace_reduce

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "serve_tokens_per_s"
KERNELS = ("paged_attention_decode_block",)


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
        r[ring.COUNTS].get("diff_cells", 0)
        for r in ring.snapshot(trace["t0"], trace["t1"])
        if r[ring.NAME] == "engine.step"
    )
    if not cells:
        return None
    need = flops_sdar.block_paged_needs(run["cell"]["model"], cells)
    least = flops.roofline_seconds(
        need["flops"], need["bytes"], lib.peaks_for(run["device_kind"])
    )["seconds"]
    return 100.0 * least / seconds
