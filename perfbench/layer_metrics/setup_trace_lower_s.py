"""Start-up: Python's share of building the programs. The seconds
covered by the `trace` and `lower` legs of the program's `compile`
records (dlrover_tpu/common/trace.py, one for every leg of jax's
compile path) from the ring's oldest record to the window's opening:
their union, since a function traced inside another lies inside its
leg. What a kernel's body, or work added to the tracing of the shared
forward, costs every start (PR 40, PR 42). None on a program without
such records, on a run without a window, and on a full ring (the
set-up's records may have been pushed out)."""

import program_trace

LAYER = "start-up"
UNIT = "s"
SOURCE = "program_span"
MOVES = "setup_s"


def read(run):
    span = program_trace.window(run)
    try:
        from dlrover_tpu.common.trace import compile_totals
    except ImportError:
        return None
    totals = compile_totals(until=span[0]) if span else None
    return None if totals is None else totals["trace_lower_s"]
