"""Device: the share of the traced window in which no operation ran
on the chip (trace_reduce.py: 1 - busy / window). In the elastic cell
the traced window is a cycle's last steps and its save."""

LAYER = "device"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "train_tokens_per_s"


def read(run):
    trace = run["trace"]
    if trace is None or run["rehearsal"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
