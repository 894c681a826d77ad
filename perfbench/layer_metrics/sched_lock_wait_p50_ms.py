"""Front end: the median over the window of `lock_wait_s` on the
program's own `sched.submit` span, from the entry of
RequestScheduler.submit to the moment it holds the scheduler's lock
(pump() holds that lock through every engine step). The first of a
request's four legs to its first token; submit_wait_p50_ms is the
benchmark's clock around the whole call, lock wait and work lumped."""

import program_trace

LAYER = "front end"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "serve_tokens_per_s"


def read(run):
    spans, trace = program_trace.records(run, "sched.submit")
    if spans is None:
        return None
    return program_trace.median_ms([
        s[trace.COUNTS]["lock_wait_s"] for s in spans
        if "lock_wait_s" in s[trace.COUNTS]
    ])
