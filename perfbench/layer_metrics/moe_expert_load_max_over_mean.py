"""Experts: how unevenly the router loads the experts. The median
over the window's `engine.step` spans of `moe_max_load` over
`moe_mean_load` (the program's counts: the (token, expert) pairs the
newest dispatch routed to its busiest expert and to the mean one,
summed over its layers and steps). 1 is an even load; the grouped
kernels' time follows the busiest expert's run of rows."""

import statistics

import program_trace

LAYER = "experts"
UNIT = "ratio"
SOURCE = "program_counter"
MOVES = "tpot_p95_ms"


def read(run):
    steps, trace = program_trace.records(run, "engine.step")
    if steps is None:
        return None
    ratios = [
        s[trace.COUNTS]["moe_max_load"] / s[trace.COUNTS]["moe_mean_load"]
        for s in steps if s[trace.COUNTS].get("moe_mean_load")
    ]
    return statistics.median(ratios) if ratios else None
