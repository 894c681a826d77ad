"""BENCHMARK.json against the contract's mechanical rules, and every
name in it against the file it should resolve to."""

import os
import re

import lib

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def manifest():
    return lib.read_json(os.path.join(lib.ROOT, "BENCHMARK.json"))


def test_keys_and_limits():
    m = manifest()
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= m["run_seconds"] <= 51
    assert m["paths"] == ["perfbench"]
    four = sum(w["chips"] == 4 for w in m["workloads"])
    assert four <= max(1, len(m["workloads"]) // 4)
    names = [x["name"] for key in ("configs", "workloads", "end_to_end",
                                   "per_layer") for x in m[key]]
    assert all(NAME.match(n) for n in names), names
    for key in ("configs", "workloads"):
        group = [x["name"] for x in m[key]]
        assert len(group) == len(set(group))
    metrics = [x["name"] for x in m["end_to_end"] + m["per_layer"]]
    assert len(metrics) == len(set(metrics))
    for metric in m["end_to_end"] + m["per_layer"]:
        assert UNIT.match(metric["unit"]) and metric["source"] in SOURCES
        assert metric["better"] in ("lower", "higher")
    for metric in m["end_to_end"]:
        assert 0.01 <= metric["bound"] <= 0.1
        assert metric["source"] in ("host_clock", "device_trace")
    assert "setup_s" in [x["name"] for x in m["end_to_end"]]


def test_every_name_resolves_to_a_file():
    m = manifest()
    for config in m["configs"]:
        path = os.path.join(lib.ROOT, config["file"])
        model = lib.read_json(path)
        assert model["name"] == config["name"]
        assert model["source"] == config["source"]
        assert sorted(model["reduced"]) == sorted(config["reduced"])
        assert os.path.exists(os.path.join(
            lib.BENCH, "drivers", model["driver"] + ".py"))
        assert model["limits"]
    for metric in m["per_layer"]:
        path = os.path.join(lib.BENCH, "layer_metrics", metric["name"] + ".py")
        module = lib.load_module(path, "m_" + re.sub(r"\W", "_", metric["name"]))
        assert module.LAYER == metric["layer"]
        assert module.UNIT == metric["unit"]
        assert module.SOURCE == metric["source"]
        assert module.MOVES == metric["moves"]
        assert callable(module.read)


def test_every_cell_loads_and_reports_what_it_must():
    m = manifest()
    e2e = {x["name"] for x in m["end_to_end"]}
    for w in m["workloads"]:
        cell = lib.load_cell(w["name"])
        assert len(w["why"]) <= 200 and cell["mix"]["why"]
        reported = {x["name"] for x in cell["end_to_end"]}
        assert "setup_s" in reported and len(reported) >= 2
        assert cell["per_layer"]
        for metric in cell["per_layer"]:
            assert metric["moves"] in reported & e2e


def test_the_elastic_job_is_the_steady_one_with_another_traffic_file():
    """mistral7b_train_elastic is out of the manifest (PERF.md, Open
    questions, row 0); its traffic file stays, and adding the cell
    back is one entry that names the same configuration."""
    steady = lib.read_json(os.path.join(lib.BENCH, "traffic", "train_steady.json"))
    elastic = lib.read_json(
        os.path.join(lib.BENCH, "traffic", "train_save_kill_resume.json"))
    assert set(steady) == set(elastic)
    assert steady["save_every_steps"] == 0 and not steady["kill"]
    assert elastic["save_every_steps"] == 64 and elastic["kill"]
