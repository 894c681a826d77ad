"""Driver of the training cells: the parent side. It never imports
jax (the worker under the agent holds the chip). It launches the job
as a user does,

    python -m dlrover_tpu.trainer.elastic_run ... drivers/train_worker.py

reads the worker's event lines, turns them into metrics, and decides
`correct`. A copy of chip_smoke.py's run_train_phase and
check_train_events, with the benchmark's loop and output.
"""

import json
import math
import os
import shutil
import signal
import statistics
import subprocess
import sys
import tempfile
import threading
import time

import lib

FLASH_KERNELS = (
    "flash_attention_fwd", "flash_attention_bwd_dq", "flash_attention_bwd_dkv",
)
JOB_TIMEOUT_S = 1100.0  # under the 1200 s a checkout's first run may take


def launch(cell, args, work_dir):
    """Run the job to its end; returns (exit code, events)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = lib.ROOT + os.pathsep + env.get("PYTHONPATH", "")
    job = f"pb{os.getpid()}"
    sock_dir = socket_dir(job)
    env["DLROVER_TPU_SOCK_DIR"] = sock_dir
    cmd = [
        sys.executable, "-m", "dlrover_tpu.trainer.elastic_run",
        "--nnodes=1", "--max-restarts=1", f"--job-name={job}",
        os.path.join(lib.BENCH, "drivers", "train_worker.py"),
        "--config-file", os.path.join(lib.ROOT, cell["config_entry"]["file"]),
        "--traffic-file",
        os.path.join(lib.BENCH, "traffic", cell["traffic"] + ".json"),
        "--seed", str(args.seed),
        "--seconds", str(args.seconds), "--trace", str(args.trace),
        "--work-dir", work_dir,
    ]
    if args.keep_trace:
        cmd += ["--keep-trace", os.path.abspath(args.keep_trace)]
    if args.rehearsal:
        cmd.append("--rehearsal")
        env["DLROVER_TPU_FORCE_CPU"] = "1"
        env["JAX_PLATFORMS"] = "cpu"
    lib.log(f"[train] t+{time.time() - args.t_start:.1f}s "
            + " ".join(cmd[1:]))
    events = []
    # its own session: whatever the launcher starts (master threads,
    # agent, saver, workers) can be stopped as one group afterwards
    proc = subprocess.Popen(
        cmd, cwd=lib.ROOT, env=env, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True, start_new_session=True,
    )

    def kill_group():
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            pass

    watchdog = threading.Timer(JOB_TIMEOUT_S, kill_group)
    watchdog.daemon = True
    watchdog.start()
    try:
        for line in proc.stdout:
            line = line.rstrip("\n")
            event = lib.parse_event(line)
            if event is not None:
                events.append(event)
                lib.log("[train] " + json.dumps(brief(event)))
            elif any(
                key in line for key in
                ("Error", "error", "Traceback", "restart", "exited",
                 "WARNING", "  File ")
            ):
                lib.log("[train:log] " + line[:300])
        rc = proc.wait(timeout=60)
    finally:
        watchdog.cancel()
        kill_group()  # nothing the launcher started outlives the run
        proc.wait()
        remove_job_files(job, sock_dir)
    return rc, events


def socket_dir(job: str) -> str:
    """A directory of this run's own for the agent's unix socket. A
    socket's path holds 107 bytes, so it cannot sit deep: straight
    under the temporary directory the run was given (TMPDIR) or, where
    that is too deep, under the run's cache directory, its HOME, or
    the checkout. Never anywhere else."""
    bases = [tempfile.gettempdir(), os.environ.get("XDG_CACHE_HOME"),
             os.environ.get("HOME"), lib.ROOT]
    for base in filter(None, bases):
        longest = os.path.join(base, "s12345678", job + ".sock")
        if len(os.fsencode(longest)) <= 107 and os.path.isdir(base):
            return tempfile.mkdtemp(prefix="s", dir=base)
    raise RuntimeError(
        f"no place for the agent's socket: under each of {bases} its path "
        "would be over the 107 bytes a unix socket's path holds; give the "
        "run a shorter TMPDIR"
    )


def brief(event: dict) -> dict:
    """An event for the log: long lists cut to their ends."""
    return {
        k: (v[:2] + ["...%d in all" % len(v)] if isinstance(v, list)
            and len(v) > 6 else v)
        for k, v in event.items()
    }


def remove_job_files(job: str, sock_dir: str) -> None:
    """The agent leaves its shared-memory checkpoint segment (the
    whole train state, GiBs) and its socket behind on purpose, for a
    later incarnation; the run is done with them."""
    import glob

    from dlrover_tpu.common.multi_process import SHM_DIR

    shutil.rmtree(sock_dir, ignore_errors=True)
    for path in glob.glob(os.path.join(SHM_DIR, f"dlrover_tpu_ckpt_{job}_*")):
        try:
            os.remove(path)
        except OSError:
            pass


def of(events, kind, restart=None):
    return [
        e for e in events
        if e["event"] == kind
        and (restart is None or e.get("restart") == restart)
    ]


def worst_leaf_gap(program: dict, reference: dict) -> float:
    """The widest gap between the program's norm of a leaf and the
    reference's, against the reference's norm of that leaf or of the
    median leaf, whichever is larger (some gradients are all but
    zero)."""
    median = statistics.median(reference.values())
    return max(
        abs(program[k] - reference[k]) / max(reference[k], median)
        for k in reference
    )


def compare_with_reference(first: dict, ref: dict) -> dict:
    """The numbers `correct` holds against the plain reference."""
    out = {
        f"loss_step{i + 1}_rel": abs(a - b) / abs(b)
        for i, (a, b) in enumerate(zip(first["losses"], ref["losses"]))
    }
    out["grad_norm_worst_leaf"] = worst_leaf_gap(
        first["grad_norms"], ref["grad_norms"]
    )
    out["change_norm_worst_leaf"] = worst_leaf_gap(
        first["change_norms"], ref["change_norms"]
    )
    return out


def limit_of(limits: dict, name: str):
    """The limit a compared number is held to (the three losses share
    one)."""
    return limits["loss_rel" if name.startswith("loss_") else name]["limit"]


def judge(cell, events, rehearsal: bool) -> lib.Checks:
    mix, limits = cell["mix"], cell["model"]["limits"]
    checks = lib.Checks()
    window = of(events, "window")[0]
    first = of(events, "first_steps")[0]
    ref = of(events, "reference")[-1]
    for name, value in compare_with_reference(first, ref).items():
        checks.at_most(name, value, limit_of(limits, name))
    checks.at_most("window_compilations", window["compilations"], 0)
    checks.at_most("nonfinite_losses", window["losses_nonfinite"], 0)
    if not rehearsal:
        held = of(events, "kernels")[0]["kernels"]
        checks.require(
            "flash_kernels_in_step", set(FLASH_KERNELS) <= set(held), str(held)
        )
    if mix["kill"]:
        saved = window["saves"][-1]["step"]
        ups = of(events, "worker_up")
        checks.require(
            "two_incarnations", [u["restart"] for u in ups] == [0, 1], str(ups)
        )
        second = of(events, "worker_up", 1)[0]
        checks.require(
            "resumed_from_the_save",
            second["restored"] and second["resumed_step"] == saved
            and second["state_step"] == saved,
            f"saved {saved}, restored {second['resumed_step']}",
        )
        before = {e["step"]: e["loss"] for e in of(events, "step", 0)}
        after = of(events, "step", 1)[0]
        checks.require(
            "resumed_step_follows_the_save", after["step"] == saved + 1,
            f"step {after['step']}",
        )
        checks.at_most(
            "resumed_loss_abs_gap",
            abs(after["loss"] - before.get(after["step"], math.nan)),
            limits["resumed_loss_abs_gap"]["limit"],
        )
    return checks


def run(cell, args, t_start: float) -> dict:
    work_dir = tempfile.mkdtemp(prefix="perfbench_")
    try:
        rc, events = launch(cell, args, work_dir)
    finally:
        shutil.rmtree(work_dir, ignore_errors=True)
    if rc != 0:
        raise RuntimeError(f"elastic_run exited with code {rc}")
    return result(cell, args, t_start, events)


def result(cell, args, t_start, events) -> dict:
    mix = cell["mix"]
    up = of(events, "worker_up", 0)[0]
    window = of(events, "window")[0]
    tokens_per_step = up["rows"] * up["seq"]
    window_s = window["t_end"] - window["t_begin"]
    steps_after = of(events, "step")
    losses = [e["loss"] for e in steps_after]
    failed = window["losses_nonfinite"] + sum(
        not math.isfinite(x) for x in losses
    )
    end_to_end = {
        "setup_s": window["t_begin"] - t_start,
        "train_tokens_per_s": window["steps"] * tokens_per_step / window_s,
    }
    if mix["kill"]:
        killed = of(events, "sigkill")[0]
        resumed = of(events, "step", 1)[0]
        end_to_end["resume_s"] = resumed["wall"] - killed["wall"]
    peaks = [window["memory_peak_bytes"]] + [
        e["memory_peak_bytes"] for e in of(events, "resumed")
    ]
    device = dict(up["device"], memory_peak_bytes=max(peaks))
    run_view = {
        "cell": cell, "events": events, "trace": window["trace"],
        "tokens_per_step": tokens_per_step, "rehearsal": args.rehearsal,
        "device_kind": up["device"]["kind"],
    }
    checks = judge(cell, events, args.rehearsal)
    out = {
        "correct": checks.ok, "checks": checks.compared,
        "attempted": window["steps"] + len(steps_after),
        "failed": failed,
        "device": device,
    }
    if args.trace:
        trace = window["trace"]
        device.update(busy_s=trace["busy_s"], window_s=trace["window_s"])
        out["breakdown"] = {
            "device_ops": trace["device_ops"], "idle_gaps": trace["idle_gaps"],
        }
        out["metrics"] = lib.layer_metrics(cell, run_view)
    else:
        out["metrics"] = lib.end_to_end_metrics(cell, end_to_end)
    lib.log("[train] reference took "
            f"{of(events, 'reference')[-1]['seconds']:.1f} s; window "
            f"{window_s:.1f} s, {window['steps']} steps")
    # a step far over the median is the host's doing (the device's step
    # is the same every time): say where in the window it fell
    median = statistics.median(window["step_seconds"])
    slow = [(i, round(s, 3)) for i, s in enumerate(window["step_seconds"])
            if s > 1.5 * median]
    lib.log(f"[train] steps over 1.5 x the median {median:.4f} s: "
            f"{slow[:20]} ({len(slow)} in all, "
            f"{sum(s - median for _, s in slow):.2f} s over)")
    return out
