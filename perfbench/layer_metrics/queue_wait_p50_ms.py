"""Front end: the median of `t_admitted - t_queued` over the requests
whose first tokens went out inside the window: from the push on the
scheduler's waiting heap to the hand-over to the engine, the third of
a request's four legs (the program's `request` event). In a closed
loop with more clients than slots this is the backlog, by design."""

import program_trace

LAYER = "front end"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "serve_tokens_per_s"


def read(run):
    legs = program_trace.first_token_legs(run)
    if legs is None:
        return None
    return program_trace.median_ms(
        [c["t_admitted"] - c["t_queued"] for c in legs])
