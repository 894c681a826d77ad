"""The program's own spans, events and counts, as the readers under
layer_metrics/ take them: the records that dlrover_tpu/common/trace.py
keeps in its ring, cut to the serving window. The window is the one
the benchmark's own step records span: from the start of the first
entry of run["window"]["steps"] to the end of the last, on
time.time(), the clock both sides stamp.

A program from before that module (a parent commit), a run with no
window, or a ring that holds no such record inside it gives None, and
the metric is left out of the line.
"""

import statistics


def window(run):
    """(since, until) of the serving window, or None."""
    steps = run.get("window", {}).get("steps")
    if not steps:
        return None
    return steps[0][0], steps[-1][0] + steps[-1][1]


def records(run, name):
    """The program's records called `name` that start inside the
    window, oldest first, with the trace module whose indices read
    them: (records, trace), or (None, None) where there are none."""
    try:
        from dlrover_tpu.common import trace
    except ImportError:
        return None, None
    span = window(run)
    if span is None:
        return None, None
    found = [r for r in trace.snapshot(*span) if r[trace.NAME] == name]
    return (found, trace) if found else (None, None)


def first_token_legs(run):
    """The counts of the `request` events left when a request's first
    tokens went on its stream inside the window (the event left at a
    request's end carries t_end), or None."""
    found, trace = records(run, "request")
    if found is None:
        return None
    stamps = ("t_submit", "t_locked", "t_queued", "t_admitted", "t_first")
    legs = [
        r[trace.COUNTS] for r in found
        if r[trace.COUNTS].get("t_end") is None
        and all(r[trace.COUNTS].get(k) is not None for k in stamps)
    ]
    return legs or None


def median_ms(seconds):
    return statistics.median(seconds) * 1e3 if seconds else None
