"""Serving engine: the median of `t_first - t_admitted` over the
requests whose first tokens went out inside the window: from the
scheduler's hand-over to the engine until the first tokens are on the
request's stream, the last of a request's four legs (the program's
`request` event): the rest of the step that was running, one prefill
and one dispatch of `chunk` decode steps."""

import program_trace

LAYER = "serving engine"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "serve_tokens_per_s"


def read(run):
    legs = program_trace.first_token_legs(run)
    if legs is None:
        return None
    return program_trace.median_ms(
        [c["t_first"] - c["t_admitted"] for c in legs])
