"""Serving engine: the median over the window of the host clock
around ContinuousBatcher.step (harvest, admissions, one dispatch of
`chunk` decode steps), from the benchmark's wrapper around it."""

import statistics

LAYER = "serving engine"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "tpot_p95_ms"


def read(run):
    steps = run.get("window", {}).get("steps")
    if not steps:
        return None
    return statistics.median(s[1] for s in steps) * 1e3
