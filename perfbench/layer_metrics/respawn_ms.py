"""Launcher: SIGKILL of the worker -> the respawned worker's first
line (its Python has started; it has not touched jax yet). Holds the
agent's failure path: noticing the exit, persisting the shared-memory
checkpoint to storage, starting the process."""

LAYER = "launcher"
UNIT = "ms"
SOURCE = "host_clock"
MOVES = "resume_s"


def read(run):
    killed = [e for e in run["events"] if e["event"] == "sigkill"]
    starts = [
        e for e in run["events"]
        if e["event"] == "worker_start" and e["restart"] == 1
    ]
    if not killed or not starts:
        return None
    return (starts[0]["wall"] - killed[0]["wall"]) * 1e3
