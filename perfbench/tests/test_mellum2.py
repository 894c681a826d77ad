"""The yardstick of the Mellum2 cell: its entries in BENCHMARK.json
against its files, its configuration against the catalog's row, its
config object, weights and reference at the rehearsal's size, its
operation counts, its metric readers on a program without what they
read, and one whole rehearsal."""

import json
import os

import pytest

import lib

CELL = "mellum2_serve_context_decode"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW_METRICS = (
    "hybrid_paged_attention_roofline", "moe_grouped_matmul_roofline",
    "moe_expert_load_max_over_mean", "kv_window_pages_held_pct",
)


def load_cell() -> dict:
    return lib.load_cell(CELL)


def test_the_entries_name_the_cells_files():
    manifest = lib.read_json(os.path.join(lib.ROOT, "BENCHMARK.json"))
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [x["name"] for x in manifest[key]]
        assert len(names) == len(set(names)), key
    # the entries stand together, in the order they were appended
    # (later PRs append after them)
    assert "mellum2-12b-a2.5b.serve-1chip" in [
        c["name"] for c in manifest["configs"]]
    assert CELL in [w["name"] for w in manifest["workloads"]]
    names = [m["name"] for m in manifest["per_layer"]]
    at = names.index(NEW_METRICS[0])
    assert names[at:at + len(NEW_METRICS)] == list(NEW_METRICS)
    cell = load_cell()
    assert {m["name"] for m in cell["end_to_end"]} == {
        "setup_s", "serve_tokens_per_s", "tpot_p95_ms"}
    assert cell["model"]["name"] == cell["config"] == cell["config_entry"]["name"]
    assert list(cell["model"]["reduced"]) == cell["config_entry"]["reduced"]
    assert cell["model"]["driver"] == "serve_mellum2"
    listed = {m["name"]: m for m in cell["per_layer"]}
    # the serving per-layer metrics there were, but for the one whose
    # reader counts every layer as keeping every position
    assert {"engine_step_ms", "engine_host_ms", "step_prefill_share_pct",
            "batch_occupancy_pct", "device_idle_pct.serve"} <= set(listed)
    assert "paged_attention_decode_roofline" not in listed
    for name in NEW_METRICS:
        metric = listed[name]
        path = os.path.join(lib.BENCH, "layer_metrics", name + ".py")
        module = lib.load_module(path, "m_" + name)
        assert (module.LAYER, module.UNIT, module.SOURCE, module.MOVES) == (
            metric["layer"], metric["unit"], metric["source"], metric["moves"])
        assert CELL in metric["workloads"]
        assert metric["moves"] in ("serve_tokens_per_s", "tpot_p95_ms")


def test_configuration_holds_the_catalog_rows_numbers():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        rows = [json.loads(line) for line in f]
    row = next(r for r in rows if r["name"] == "Mellum2-12B-A2.5B-Instruct")
    model = load_cell()["model"]
    assert model["source"] == row["source_url"]
    differs = [k for k, v in row["config"].items() if model.get(k) != v]
    assert differs == ["num_hidden_layers"] == list(model["reduced"])
    assert model["num_hidden_layers"] % 4 == 0
    assert model["num_hidden_layers"] in (8, 12)


def test_config_object_and_reference_agree_at_the_rehearsals_size():
    import jax
    import jax.numpy as jnp
    import numpy as np

    import reference_mellum2
    import weights_mellum2

    from dlrover_tpu.models import decode

    driver = lib.load_driver("serve_mellum2")
    cell = load_cell()
    model, run, _ = driver.rehearsal_sizes(
        cell["model"], cell["model"]["run"], cell["mix"])
    cfg = driver.mellum2_config(model, run)
    assert cfg.period == ("window", "window", "window", "full")
    assert cfg.head_dim == 32 != cfg.dim // cfg.n_heads
    params = weights_mellum2.make_params(model, 2**31 + 11, "float32")
    tokens = np.random.RandomState(0).randint(1, 256, (1, 40))
    with jax.default_matmul_precision("highest"):
        want = reference_mellum2.forward(model, params, jnp.asarray(tokens))
        out = decode.generate(cfg, params, jnp.asarray(tokens[:, :30]), 4)
        nxt = int(jnp.argmax(
            reference_mellum2.forward(
                model, params, jnp.asarray(tokens[:, :30]))[0, -1]))
        low = reference_mellum2.forward(
            model, params, jnp.asarray(tokens), "fp8")
    assert int(out[0, 30]) == nxt
    assert want.shape == (1, 40, 256)
    # the control one precision lower is another forward
    assert float(jnp.abs(low - want).max()) > 1e-2


def test_full_config_object_at_the_published_widths():
    driver = lib.load_driver("serve_mellum2")
    model = load_cell()["model"]
    cfg = driver.mellum2_config(model, model["run"])
    assert (cfg.dim, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == (
        2304, 32, 4, 128)
    assert (cfg.n_experts, cfg.moe_top_k, cfg.mlp_dim) == (64, 8, 896)
    assert cfg.sliding_window == 1024 and cfg.vocab_size == 98304
    assert cfg.rope_full.yarn_factor == 16 and cfg.rope_of("window").theta == 5e5
    assert cfg.layers_of("full") * 4 == cfg.n_layers


def test_operation_counts():
    import flops_mellum2

    model = load_cell()["model"]
    layers = model["num_hidden_layers"]
    need = flops_mellum2.hybrid_paged_decode_needs(model, 2000, 1024, 1)
    kv = 2 * 4 * 128 * 2
    assert need["bytes"] == (
        (layers // 4) * 2000 * kv + (layers * 3 // 4) * 1024 * kv
        + 2 * 32 * 128 * 2 * layers
    )
    moe = flops_mellum2.moe_grouped_needs(model, 1, 512)
    assert 63.9 < flops_mellum2.expected_experts_touched(model, 512) < 64
    assert moe["flops"] == 6.0 * 512 * 2304 * 896
    assert moe["bytes"] > 63.9 * 3 * 2304 * 896 * 2
    # 417.7 M parameters a layer, 453 M in embedding and head
    assert flops_mellum2.weight_bytes(model) == 2 * (
        layers * 417_747_456 + 2 * 98304 * 2304 + 2304)


@pytest.mark.parametrize("name", NEW_METRICS)
def test_readers_return_none_where_there_is_nothing_to_read(name):
    run = {
        "cell": load_cell(), "window": {"steps": []}, "trace": None,
        "device_kind": "TPU v5 lite", "rehearsal": False, "events": [],
    }
    assert lib.read_layer_metric(name, run) is None


def test_a_traced_rehearsal_reports_the_cells_metrics(capsys):
    """One whole run of the cell on the CPU at tiny sizes, through the
    driver as run.py would call it."""
    import argparse
    import time

    driver = lib.load_driver("serve_mellum2")
    args = argparse.Namespace(
        rehearsal=True, seed=2 ** 31 + 31, seconds=3.0, trace=1, control="",
        keep_trace="", dump="", t_start=time.time())
    out = driver.run(load_cell(), args, args.t_start)
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 0
    assert {"engine_step_ms", "engine_host_ms", "step_prefill_share_pct",
            "moe_expert_load_max_over_mean", "kv_window_pages_held_pct",
            "batch_occupancy_pct"} <= set(out["metrics"])
