"""The four per-layer metrics under `setup_s` (layer "start-up"),
which read the program's `compile` records from the ring's oldest
record to the window's opening: None on an empty ring, without a
window and on a full ring; the right number on a hand-made ring with
nested and overlapping legs; their entries in BENCHMARK.json; and one
traced rehearsal that reports all four."""

import os
import time

import pytest

import lib
from dlrover_tpu.common import trace

READERS = {
    "setup_trace_lower_s": ("s", "program_span"),
    "setup_backend_compile_s": ("s", "program_span"),
    "setup_cache_miss_programs": ("programs", "program_counter"),
    "setup_before_first_program_s": ("s", "host_clock"),
}
OPENS = time.time()  # the window's opening; the set-up lies before it


def run_view():
    return {"window": {"steps": [(OPENS, 0.5, 48, 9000)], "n_slots": 48}}


def leg(kind, start, dur, program="jit(chunk)", **counts):
    trace.record("compile", OPENS + start, dur, leg=kind, program=program,
                 **counts)


def hand_made_ring():
    """A start-up of three programs 60 s before the window opens:
    the chunk program traced with a helper inside it, lowered while a
    second thread still traces, compiled on a miss; an eager primitive
    that misses on every start; a prefill read back from the cache;
    and, inside the window, an admission that met a new bucket."""
    leg("trace", -60.0, 4.0, program="chunk")
    leg("trace", -59.0, 1.0, program="helper")  # inside the first
    leg("lower", -56.5, 1.5)  # overlaps the trace's end: 5.0 s covered
    leg("backend", -55.0, 12.0, cache="miss")
    leg("backend", -42.0, 0.25, program="jit(add)", cache="miss")
    leg("trace", -41.0, 2.0, program="prefill")
    leg("lower", -39.0, 1.0, program="jit(prefill)")
    leg("backend", -38.0, 0.5, program="jit(prefill)", cache="hit")
    with trace.span("engine.step"):
        pass
    leg("trace", 3.0, 2.0, program="prefill")
    leg("backend", 5.0, 9.0, program="jit(prefill)", cache="miss")


@pytest.fixture(autouse=True)
def empty_ring():
    trace.clear()
    yield
    trace.clear()


def load(name):
    return lib.load_module(
        os.path.join(lib.BENCH, "layer_metrics", name + ".py"),
        "test_setup_legs_" + name)


@pytest.mark.parametrize("name", sorted(READERS))
def test_none_on_an_empty_ring_and_without_a_window(name):
    assert lib.read_layer_metric(name, run_view()) is None
    with trace.span("engine.step"):  # a program from before the records
        pass
    assert lib.read_layer_metric(name, run_view()) is None
    hand_made_ring()
    assert lib.read_layer_metric(name, {"window": {}}) is None
    assert lib.read_layer_metric(name, {}) is None
    assert lib.read_layer_metric(name, run_view()) is not None


@pytest.mark.parametrize("name", sorted(READERS))
def test_none_on_a_full_ring(name):
    hand_made_ring()
    for _ in range(trace.RING_SIZE - len(trace.snapshot()) - 1):
        trace.event("filler")
    assert lib.read_layer_metric(name, run_view()) is not None
    trace.event("the last free place")
    assert len(trace.snapshot()) == trace.RING_SIZE
    # nothing was pushed out yet, and nobody can tell: None
    assert lib.read_layer_metric(name, run_view()) is None


def test_the_legs_before_the_window_as_unions():
    hand_made_ring()
    read = lambda name: lib.read_layer_metric(name, run_view())  # noqa: E731
    assert read("setup_trace_lower_s") == pytest.approx(5.0 + 3.0)
    assert read("setup_backend_compile_s") == pytest.approx(12.75)
    # the eager primitive missed too, in a quarter of a second: such a
    # program is never stored and says nothing
    assert read("setup_cache_miss_programs") == 1


def test_before_the_first_program_counts_from_the_process_start():
    reader = load("setup_before_first_program_s")
    started = reader.process_start()
    if started is None:
        pytest.skip("no /proc here")
    # this process: started before this module was imported, not long ago
    assert OPENS - 3600.0 < started < OPENS
    assert reader.process_start() == pytest.approx(started, abs=0.05)
    hand_made_ring()
    assert reader.read(run_view()) == pytest.approx(
        OPENS - 60.0 - started, abs=0.05)


def test_the_manifest_lists_each_once_for_every_serving_cell():
    manifest = lib.read_json(os.path.join(lib.ROOT, "BENCHMARK.json"))
    serving = [
        w["name"] for w in manifest["workloads"]
        if lib.read_json(os.path.join(
            lib.ROOT, next(c["file"] for c in manifest["configs"]
                           if c["name"] == w["config"])))["driver"] != "train"
    ]
    assert len(serving) == 4
    assert not [m for m in manifest["per_layer"][:-4]
                if m["moves"] == "setup_s"]
    for name, (unit, source) in READERS.items():
        (entry,) = [m for m in manifest["per_layer"] if m["name"] == name]
        assert entry == {
            "name": name, "unit": unit, "better": "lower", "source": source,
            "layer": "start-up", "moves": "setup_s", "workloads": serving,
        }
        module = load(name)
        assert (module.LAYER, module.UNIT, module.SOURCE, module.MOVES) == (
            "start-up", unit, source, "setup_s")
    assert [m["name"] for m in manifest["per_layer"][-4:]] == list(READERS)


def test_a_traced_rehearsal_reports_all_four():
    """One whole run of the Mistral serving cell on the CPU at tiny
    sizes, through the driver as run.py would call it."""
    import argparse

    import jax

    # a rehearsal of this cell earlier in the same process (a whole run
    # of perfbench/tests has two) leaves every program in jax's own
    # caches, and a start-up that compiles nothing leaves no legs
    jax.clear_caches()
    driver = lib.load_driver("serve")
    args = argparse.Namespace(
        rehearsal=True, seed=2 ** 31 + 46, seconds=3.0, trace=1, control="",
        keep_trace="", dump="", t_start=time.time())
    out = driver.run(lib.load_cell("mistral7b_serve_decode"), args,
                     args.t_start)
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 0
    got = {name: out["metrics"][name]["value"] for name in READERS}
    assert got["setup_trace_lower_s"] > 0.0
    assert got["setup_backend_compile_s"] > 0.0
    assert got["setup_cache_miss_programs"] >= 0
    if load("setup_before_first_program_s").process_start() is not None:
        assert got["setup_before_first_program_s"] > 0.0
    # the legs lie inside the run they are the legs of
    assert (got["setup_trace_lower_s"] + got["setup_backend_compile_s"]
            < time.time() - args.t_start)
