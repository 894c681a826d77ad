"""The seven per-layer metrics that read the program's own spans and
events (dlrover_tpu/common/trace.py through program_trace.py): None on
an empty ring, None when the ring holds only records from outside the
window, and the right number on a hand-made ring."""

import json
import os
import subprocess
import sys

import pytest

import lib
from dlrover_tpu.common import trace

READERS = {
    "sched_lock_wait_p50_ms": 40.0,     # median of 20, 40, 90 ms
    "sched_lock_held_pct": 100.0 * 0.91 / 0.971,  # first pump -> last's end
    "queue_wait_p50_ms": 3000.0,        # median of 1, 3, 8 s
    "admit_to_first_token_p50_ms": 500.0,   # median of 0.4, 0.5, 0.9 s
    "engine_host_ms": 20.0,             # median of 0.44-0.42, 0.45-0.43
    "step_prefill_share_pct": 100.0 * 0.05 / 0.89,
    "pump_outside_step_ms": 10.0,       # 0.45-0.44 and 0.46-0.45
}
T0 = 1000.0  # the window: two engine steps, 1000.0 .. 1001.0


def run_view(steps=((T0, 0.5, 48, 9000), (T0 + 0.5, 0.5, 48, 9100))):
    return {"window": {"steps": list(steps), "n_slots": 48}}


def hand_made_ring(at):
    """Two pumps with an engine step each, three submits and three
    requests' first-token events, all starting at or after `at`."""
    ring = [
        # name, wall, dur_s, id, parent, req, counts
        ("engine.step", at + 0.01, 0.44, 11, 10, None,
         {"wait_s": 0.42, "admit_s": 0.05, "alive": 48}),
        ("sched.pump", at, 0.46, 10, 0, None, {"held_s": 0.45}),
        ("engine.step", at + 0.51, 0.45, 21, 20, None,
         {"wait_s": 0.43, "admit_s": 0.0, "alive": 48}),
        ("sched.pump", at + 0.5, 0.47, 20, 0, None, {"held_s": 0.46}),
        ("sched.pump", at + 0.97, 0.001, 30, 0, None, {}),  # crashed: no count
    ]
    for k, wait in enumerate((0.02, 0.04, 0.09)):
        ring.append(("sched.submit", at + 0.1 * k, wait + 0.001, 40 + k, 0,
                     k, {"lock_wait_s": wait}))
    for k, (queue, first) in enumerate(((1.0, 0.4), (3.0, 0.5), (8.0, 0.9))):
        stamps = {
            "submit_wall": at - 10, "t_submit": 5.0, "t_locked": 5.5,
            "t_queued": 5.6, "t_admitted": 5.6 + queue,
            "t_first": 5.6 + queue + first, "t_end": None, "tokens": 8,
        }
        ring.append(("request", at + 0.2 * k, 0.0, 50 + k, 0, k, stamps))
        # the event left at the request's end is not counted twice
        ring.append(("request", at + 0.2 * k + 0.05, 0.0, 60 + k, 0, k,
                     dict(stamps, t_end=99.0, tokens=300)))
    return ring


@pytest.fixture(autouse=True)
def empty_ring():
    trace.clear()
    yield
    trace.clear()


@pytest.mark.parametrize("name", sorted(READERS))
def test_none_on_an_empty_ring_and_without_a_window(name):
    assert lib.read_layer_metric(name, run_view()) is None
    trace._ring.extend(hand_made_ring(T0))
    assert lib.read_layer_metric(name, {"window": {}}) is None
    assert lib.read_layer_metric(name, {}) is None


@pytest.mark.parametrize("name", sorted(READERS))
def test_none_when_every_record_lies_outside_the_window(name):
    trace._ring.extend(hand_made_ring(T0 - 50.0))
    trace._ring.extend(hand_made_ring(T0 + 50.0))
    assert lib.read_layer_metric(name, run_view()) is None


@pytest.mark.parametrize("name", sorted(READERS))
def test_the_right_number_on_a_hand_made_ring(name):
    trace._ring.extend(hand_made_ring(T0 - 50.0))  # outside: not read
    trace._ring.extend(hand_made_ring(T0))
    value = lib.read_layer_metric(name, run_view())
    assert value == pytest.approx(READERS[name], rel=1e-9)


def test_the_manifest_lists_each_of_the_seven_once_for_every_serving_cell():
    """True as cells and metrics are added: each reader has exactly
    one entry, and that entry's cells are the cells a serving driver
    runs (the readers read the serving path's spans)."""
    manifest = lib.read_json(os.path.join(lib.ROOT, "BENCHMARK.json"))
    serving = [
        w["name"] for w in manifest["workloads"]
        if lib.fill_cell(manifest, dict(w))["model"]["driver"].startswith("serve")
    ]
    assert "mistral7b_serve_decode" in serving
    for name in READERS:
        (metric,) = [m for m in manifest["per_layer"] if m["name"] == name]
        assert metric["workloads"] == serving, name


def test_a_traced_rehearsal_of_the_serving_cell_reports_all_seven():
    """One whole run of the cell on the CPU at tiny sizes: the line of
    CPU readings carries the seven beside the benchmark's own."""
    out = subprocess.run(
        [sys.executable, os.path.join(lib.BENCH, "run.py"),
         "--workload", "mistral7b_serve_decode", "--seed", str(2 ** 31 + 5),
         "--seconds", "3", "--trace", "1", "--rehearsal"],
        capture_output=True, text=True, timeout=600, cwd=lib.ROOT,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    marker = "[rehearsal: CPU readings, not device numbers] "
    (line,) = [l for l in out.stdout.splitlines() if l.startswith(marker)]
    readings = json.loads(line[len(marker):])
    for name in READERS:
        assert readings[name]["value"] >= 0.0, name
    for name in ("engine_step_ms", "submit_wait_p50_ms"):
        assert name in readings
    result = json.loads(out.stdout.splitlines()[-1])
    assert result["correct"] and set(READERS) <= set(result["metrics"])
