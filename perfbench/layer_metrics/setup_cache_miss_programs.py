"""Start-up: the programs the persistent compile cache did not hold.
Of the `backend` legs of the program's `compile` records from the
ring's oldest record to the window's opening, those the cache was
asked for and missed AND that took the second or more that makes a
program worth storing (a faster one is never stored, so it misses on
every start and says nothing). 0 on a machine that has run the cell
before, the cell's count of programs on one that has not. None on a
program without such records, on a run without a window, and on a
full ring."""

import program_trace

LAYER = "start-up"
UNIT = "programs"
SOURCE = "program_counter"
MOVES = "setup_s"


def read(run):
    span = program_trace.window(run)
    try:
        from dlrover_tpu.common.trace import compile_totals
    except ImportError:
        return None
    totals = compile_totals(until=span[0]) if span else None
    return None if totals is None else totals["cache_misses"]
