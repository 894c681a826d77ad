"""Flash checkpoint: how long training stands still at a save, the
median over the window's saves of the host clock around
Checkpointer.save_checkpoint(step, state, MEMORY)."""

import statistics

LAYER = "flash checkpoint"
UNIT = "ms"
SOURCE = "host_clock"
MOVES = "train_tokens_per_s"


def read(run):
    window = [e for e in run["events"] if e["event"] == "window"][0]
    if not window["saves"]:
        return None
    return statistics.median(s["stall_s"] for s in window["saves"]) * 1e3
