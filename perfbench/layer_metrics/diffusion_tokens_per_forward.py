"""Serving engine: the ids handed to the clients' streams over the
live slot-forwards that made them, where the model generates by
diffusion over blocks. Over the window's `engine.step` spans, the
program's `diff_tokens` over its `diff_forwards` (a forward of a live
slot runs one block of 4 positions; a block takes its denoising
forwards and one commit). 4 / 3 = 1.33 for the published loop at 2
denoising steps of a block of 4; 2.0 once a block's commit rides with
the next block's first forward. None on a program without the counts."""

import program_trace

LAYER = "serving engine"
UNIT = "tokens/forward"
SOURCE = "program_counter"
MOVES = "serve_tokens_per_s"


def read(run):
    steps, trace = program_trace.records(run, "engine.step")
    if steps is None:
        return None
    tokens = forwards = 0
    for s in steps:
        counts = s[trace.COUNTS]
        if counts.get("diff_forwards"):
            tokens += counts["diff_tokens"]
            forwards += counts["diff_forwards"]
    if not forwards:
        return None
    return tokens / forwards
