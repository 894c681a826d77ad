"""Serving engine: the median over the window's `engine.step` spans of
the step's extent less its `wait_s` (the time it stood blocked on the
device's results): the host's own work in a step, harvest, admissions
and dispatch, which sets the pace once the device's share falls."""

import program_trace

LAYER = "serving engine"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "tpot_p95_ms"


def read(run):
    steps, trace = program_trace.records(run, "engine.step")
    if steps is None:
        return None
    return program_trace.median_ms([
        s[trace.DUR] - s[trace.COUNTS]["wait_s"] for s in steps
        if "wait_s" in s[trace.COUNTS]
    ])
