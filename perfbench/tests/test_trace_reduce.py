"""trace_reduce.py on a small recorded trace: the first two steps of
mistral7b_train_steady's traced slice on a TPU v5 lite (my chip run,
PR 27), kept as the plain tuples read_planes() gives."""

import os

import pytest

import lib
import trace_reduce

RECORDED = os.path.join(
    os.path.dirname(__file__), "data", "train_steady_two_steps.planes.json")


def test_recorded_trace():
    r = trace_reduce.reduce_planes(lib.read_json(RECORDED))
    assert r["n_devices"] == 1
    assert r["window_s"] == pytest.approx(0.3051, abs=1e-3)
    assert r["busy_s"] == pytest.approx(0.2994, abs=1e-3)
    # self times: every busy nanosecond belongs to exactly one op
    assert sum(r["op_seconds"].values()) == pytest.approx(r["busy_s"])
    assert len(r["device_ops"]) == 10
    assert r["device_ops"][0][0].startswith("fusion")
    flash = trace_reduce.kernel_seconds(
        r, ("flash_attention_fwd", "flash_attention_bwd"))
    assert flash == pytest.approx(0.01706, abs=1e-4)
    assert r["idle_gaps"][0][0] == "perfbench:loss_fetch"
    assert sum(g for _, g in r["idle_gaps"]) == pytest.approx(
        r["window_s"] - r["busy_s"])


def test_self_time_of_nested_events():
    # a while of 10 around two ops of 3 and 4; then a lone op of 5
    events = [("while.1", 0, 10), ("a", 1, 3), ("b", 5, 4), ("c", 12, 5)]
    out = trace_reduce.self_seconds(events)
    assert out == {"while.1": pytest.approx(3e-9), "a": pytest.approx(3e-9),
                   "b": pytest.approx(4e-9), "c": pytest.approx(5e-9)}


def test_gaps_go_to_the_innermost_span():
    planes = {
        "devices": {"/device:TPU:0": [("x", 0, 10), ("y", 30, 10)]},
        "spans": [("perfbench:outer", 0, 40), ("perfbench:inner", 12, 10)],
    }
    r = trace_reduce.reduce_planes(planes)
    assert r["busy_s"] == pytest.approx(20e-9)
    assert r["idle_gaps"] == [["perfbench:inner", pytest.approx(20e-9)]]
    assert trace_reduce.op_name(
        "%fusion.7 = bf16[8]{0} fusion(bf16[8]{0} %p)") == "fusion.7"
    with pytest.raises(RuntimeError):
        trace_reduce.reduce_planes({"devices": {}, "spans": []})
