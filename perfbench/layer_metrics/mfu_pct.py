"""Train step: model FLOP/s utilization of the steps alone (saves
left out): flops.py's operations of a step without recomputation,
times the window's steps, over the summed step seconds and the chip's
bf16 peak from peaks.json."""

import flops
import lib

LAYER = "train step"
UNIT = "%"
SOURCE = "host_clock"
MOVES = "train_tokens_per_s"


def read(run):
    if run["rehearsal"]:
        return None  # a CPU has no entry in the table of peaks
    window = [e for e in run["events"] if e["event"] == "window"][0]
    up = [e for e in run["events"] if e["event"] == "worker_up"][0]
    per_step = flops.train_step_flops(
        run["cell"]["model"], up["rows"], up["seq"]
    )["total"]
    peak = lib.peaks_for(run["device_kind"])["bf16_flops_per_s"]
    achieved = per_step * window["steps"] / sum(window["step_seconds"])
    return 100.0 * achieved / peak
