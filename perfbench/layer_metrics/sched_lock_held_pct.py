"""Front end: the share of wall time in which pump() holds the
scheduler's lock: the sum of `held_s` over the program's `sched.pump`
spans that start inside the window, over the time from the first of
them to the end of the last (pumps follow one another without a pause
while there is work, and the one the window's edge cuts is left out of
both). What is left is all the time submit() and cancel() have to get
in; the number the lock's repair moves."""

import program_trace

LAYER = "front end"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "serve_tokens_per_s"


def read(run):
    pumps, trace = program_trace.records(run, "sched.pump")
    if pumps is None:
        return None
    last = pumps[-1]
    spanned = last[trace.WALL] + last[trace.DUR] - pumps[0][trace.WALL]
    if spanned <= 0:
        return None
    held = sum(p[trace.COUNTS].get("held_s", 0.0) for p in pumps)
    return 100.0 * held / spanned
