"""Device: the share of the traced slice of the window in which no
operation ran on the chip (trace_reduce.py: 1 - busy / window). A
half-depth model makes the host's share larger than a deployment's."""

LAYER = "device"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "serve_tokens_per_s"


def read(run):
    trace = run["trace"]
    if trace is None or run["rehearsal"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
