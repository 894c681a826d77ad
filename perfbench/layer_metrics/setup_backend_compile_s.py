"""Start-up: XLA compiling, or the persistent cache being read. The
seconds covered by the `backend` legs of the program's `compile`
records (dlrover_tpu/common/trace.py) from the ring's oldest record
to the window's opening, as a union. A few seconds of cache reads on
a machine that has run the cell before; tens of seconds more on one
that has not. None on a program without such records, on a run
without a window, and on a full ring."""

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
    return None if totals is None else totals["backend_s"]
