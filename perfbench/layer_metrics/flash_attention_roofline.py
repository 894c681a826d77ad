"""Kernels: the least time the chip could take for the flash
attention the traced steps need (flops.py: forward 2 score-sized
matmuls, backward 5; compute-bound at these shapes) over the summed
device time of the three flash kernels in the trace."""

import flops
import lib
import trace_reduce

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "train_tokens_per_s"
KERNELS = ("flash_attention_fwd", "flash_attention_bwd")


def read(run):
    trace = run["trace"]
    if trace is None or run["rehearsal"]:
        return None
    seconds = trace_reduce.kernel_seconds(trace, KERNELS)
    if seconds <= 0:
        return None
    up = [e for e in run["events"] if e["event"] == "worker_up"][0]
    steps = int(run["cell"]["mix"]["trace_steps"])
    need = flops.flash_kernel_flops(
        run["cell"]["model"], up["rows"], up["seq"]
    ) * steps
    least = flops.roofline_seconds(
        need, 0.0, lib.peaks_for(run["device_kind"])
    )["seconds"]
    return 100.0 * least / seconds
