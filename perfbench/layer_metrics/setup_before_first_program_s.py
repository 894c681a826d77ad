"""Start-up: from the start of the process to the first leg of the
program's first `compile` record (dlrover_tpu/common/trace.py): the
interpreter, the imports and the TPU runtime answering, before
anything of the cell is traced. The start of the process is the
kernel's: field 22 of /proc/self/stat over SC_CLK_TCK is its start in
seconds since boot, and /proc/uptime says how long ago boot is (to
10 ms; /proc/stat's `btime` is whole seconds). None elsewhere than
Linux, on a program without such records, on a run without a window,
and on a full ring."""

import os
import time

import program_trace

LAYER = "start-up"
UNIT = "s"
SOURCE = "host_clock"
MOVES = "setup_s"


def process_start():
    """When this process was started, on time.time(), or None."""
    try:
        with open("/proc/self/stat") as f:
            # the fields after the name, which may hold spaces: the
            # first of them is field 3
            started = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime_s = float(f.read().split()[0])
        since_boot_s = started / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return None
    return time.time() - (uptime_s - since_boot_s)


def read(run):
    span = program_trace.window(run)
    try:
        from dlrover_tpu.common.trace import compile_totals
    except ImportError:
        return None
    totals = compile_totals(until=span[0]) if span else None
    started = process_start()
    if totals is None or started is None:
        return None
    return totals["first_wall"] - started
