"""Front end: client send -> first streamed tokens, the median over
the requests the window finished. In a closed loop with more clients
than slots this is the hand-over at the front door (see
submit_wait_p50_ms), the wait in the scheduler's queue for a slot,
and one prefill that blocks the batch."""

import statistics

LAYER = "front end"
UNIT = "ms"
SOURCE = "host_clock"
MOVES = "serve_tokens_per_s"


def read(run):
    ttft = run.get("window", {}).get("ttft_ms")
    if not ttft:
        return None
    return statistics.median(ttft)
