"""Serving engine: the share of the window's engine-step time that
admissions took: the sum of `admit_s` (the host clock inside _admit:
bookkeeping and the enqueue of the prompt's forward pass, during which
no decode step is dispatched) over the summed extents of the
`engine.step` spans. The device's part of a prefill that the enqueue
does not wait for shows in the next harvest's `wait_s`, not here."""

import program_trace

LAYER = "serving engine"
UNIT = "%"
SOURCE = "program_span"
MOVES = "tpot_p95_ms"


def read(run):
    steps, trace = program_trace.records(run, "engine.step")
    if steps is None:
        return None
    total = sum(s[trace.DUR] for s in steps)
    if total <= 0:
        return None
    admit = sum(s[trace.COUNTS].get("admit_s", 0.0) for s in steps)
    return 100.0 * admit / total
