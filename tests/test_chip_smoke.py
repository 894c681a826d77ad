"""chip_smoke.py's contract, as far as a CPU can show it.

The script is the repo's proof that both main paths start on the
chip; the driver runs it on a TPU after every PR. Here: it must
REFUSE a CPU unless told this is a rehearsal (a platform other than
tpu is a failure, not a smaller run), and the rehearsal must drive
every phase — elastic_run + agent + SIGKILL + shm resume, then the
HTTP gateway under all four engine settings — at tiny size, naming
the platform it ran on."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _smoke(*argv):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    # the script pins its own children; a forced-CPU parent env must
    # not be what keeps the no-option run off a chip
    env.pop("DLROVER_TPU_FORCE_CPU", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), *argv],
        capture_output=True, text=True, timeout=600, cwd=REPO, env=env,
    )
    lines = proc.stdout.strip().splitlines()
    assert lines, proc.stderr[-2000:]
    return proc, lines


def test_cpu_without_the_option_fails():
    proc, lines = _smoke()
    assert proc.returncode != 0
    last = json.loads(lines[-1])
    assert last["ok"] is False
    assert last["device"]["platform"] == "cpu"
    assert not any('"ok": true' in ln for ln in lines)


def test_cpu_rehearsal_runs_every_phase():
    proc, lines = _smoke("--cpu-rehearsal")
    assert proc.returncode == 0, (
        proc.stdout[-3000:] + proc.stderr[-2000:]
    )
    last = json.loads(lines[-1])
    # the last line names the platform it ran on — never tpu
    assert last["ok"] is True
    assert last["device"]["platform"] == "cpu"
    assert set(last) == {"ok", "device"}
    out = proc.stdout
    # train: a SIGKILL, a respawn, a resume from the shm checkpoint
    assert '"event": "sigkill"' in out
    assert '"resumed_step": 3' in out
    assert "[train] ok" in out
    # serve: all four engine settings answered and matched generate
    for setting in ("dense", "paged-bf16", "paged-int8kv", "int8-weights"):
        assert f'[serve] ok {{"setting": "{setting}"' in out, setting
