"""Front end: the median, over the window's pumps that ran an engine
step, of `held_s` less the extent of the `engine.step` span inside
that pump: what pump() does under the scheduler's lock besides the
step (shedding and admission, delivery to the streams, the journal,
the metrics it publishes). The spans `sched.admit`, `sched.deliver`
and `sched.publish` say which part."""

import program_trace

LAYER = "front end"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "serve_tokens_per_s"


def read(run):
    pumps, trace = program_trace.records(run, "sched.pump")
    steps, _ = program_trace.records(run, "engine.step")
    if pumps is None or steps is None:
        return None
    step_of = {s[trace.PARENT]: s[trace.DUR] for s in steps}
    return program_trace.median_ms([
        p[trace.COUNTS]["held_s"] - step_of[p[trace.ID]] for p in pumps
        if p[trace.ID] in step_of and "held_s" in p[trace.COUNTS]
    ])
