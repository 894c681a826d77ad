"""Flash checkpoint: the respawned worker's load_checkpoint onto the
step's shardings, to block_until_ready (shared memory read and the
copy to the device)."""

LAYER = "flash checkpoint"
UNIT = "ms"
SOURCE = "host_clock"
MOVES = "resume_s"


def read(run):
    ups = [
        e for e in run["events"]
        if e["event"] == "worker_up" and e["restart"] == 1 and e["restored"]
    ]
    if not ups:
        return None
    return ups[0]["state_ready_s"] * 1e3
