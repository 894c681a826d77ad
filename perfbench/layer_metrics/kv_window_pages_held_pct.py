"""KV management: the pages the window class holds over the pages the
same slots would hold with every layer full, which is what the full
class holds for them (the program's counts `pages_window` and
`pages_full` on the `engine.step` spans; median over the window's
steps). 100 would mean the window layers free nothing behind the
window."""

import statistics

import program_trace

LAYER = "KV management"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "serve_tokens_per_s"


def read(run):
    steps, trace = program_trace.records(run, "engine.step")
    if steps is None:
        return None
    shares = [
        100.0 * s[trace.COUNTS]["pages_window"] / s[trace.COUNTS]["pages_full"]
        for s in steps if s[trace.COUNTS].get("pages_full")
    ]
    return statistics.median(shares) if shares else None
