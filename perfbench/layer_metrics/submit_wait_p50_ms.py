"""Front end: the median over the window of the host clock around
RequestScheduler.submit, the gateway's hand-over of one request to
the scheduler (the benchmark's wrapper around it). The call takes the
scheduler's lock, which pump() holds through every engine step, so
this is how long a request stands at the front door before it is
even queued."""

import statistics

LAYER = "front end"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "serve_tokens_per_s"


def read(run):
    waits = run.get("window", {}).get("submit_wait_s")
    if not waits:
        return None
    return statistics.median(waits) * 1e3
