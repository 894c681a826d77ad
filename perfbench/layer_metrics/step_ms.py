"""Train step: the median over the window's steps of the host clock
around one step, feed to fetched loss."""

import statistics

LAYER = "train step"
UNIT = "ms"
SOURCE = "host_clock"
MOVES = "train_tokens_per_s"


def read(run):
    window = [e for e in run["events"] if e["event"] == "window"][0]
    return statistics.median(window["step_seconds"]) * 1e3
