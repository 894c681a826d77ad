"""The yardstick of the GigaChat3.1 cell: its entries in
BENCHMARK.json against its files, its configuration against the
catalog's row, its config object, weights and reference at the
rehearsal's size, its operation counts, its metric readers on a
program without what they read, and one whole rehearsal."""

import json
import os

import pytest

import lib

CELL = "gigachat3_serve_latent_decode"
CONFIG = "gigachat3.1-702b-a36b.serve-1chip-ep16"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW_METRICS = (
    "latent_paged_attention_roofline", "moe_held_pairs_per_token",
    "moe_held_grouped_matmul_roofline",
)
REDUCED = [
    "num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
    "vocab_size", "num_nextn_predict_layers",
]


def load_cell() -> dict:
    return lib.load_cell(CELL)


def test_the_entries_name_the_cells_files():
    manifest = lib.read_json(os.path.join(lib.ROOT, "BENCHMARK.json"))
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [x["name"] for x in manifest[key]]
        assert len(names) == len(set(names)), key
    # the entries stand together, in the order they were appended
    # (later PRs append after them)
    names = [m["name"] for m in manifest["per_layer"]]
    at = names.index(NEW_METRICS[0])
    assert names[at:at + len(NEW_METRICS)] == list(NEW_METRICS)
    cell = load_cell()
    assert cell["chips"] == 1
    assert cell["traffic"] == "latent_context_decode_closed288"
    assert {m["name"] for m in cell["end_to_end"]} == {
        "setup_s", "serve_tokens_per_s", "tpot_p95_ms"}
    assert cell["model"]["name"] == cell["config"] == CONFIG
    assert cell["config_entry"]["name"] == CONFIG
    assert list(cell["model"]["reduced"]) == cell["config_entry"]["reduced"]
    assert cell["model"]["driver"] == "serve_gigachat3"
    listed = {m["name"]: m for m in cell["per_layer"]}
    # the twelve front-end, engine and device metrics of the serving
    # cells, and the experts' load as the accepted reader reads it
    assert {
        "engine_step_ms", "engine_host_ms", "step_prefill_share_pct",
        "batch_occupancy_pct", "device_idle_pct.serve",
        "submit_wait_p50_ms", "ttft_p50_ms.closed",
        "sched_lock_wait_p50_ms", "sched_lock_held_pct",
        "queue_wait_p50_ms", "admit_to_first_token_p50_ms",
        "pump_outside_step_ms", "moe_expert_load_max_over_mean",
    } <= set(listed)
    # readers that count k and v pages, window rings or every expert
    for name in ("paged_attention_decode_roofline",
                 "hybrid_paged_attention_roofline",
                 "moe_grouped_matmul_roofline", "kv_window_pages_held_pct"):
        assert name not in listed
    for name in NEW_METRICS:
        metric = listed[name]
        path = os.path.join(lib.BENCH, "layer_metrics", name + ".py")
        module = lib.load_module(path, "m_" + name)
        assert (module.LAYER, module.UNIT, module.SOURCE, module.MOVES) == (
            metric["layer"], metric["unit"], metric["source"], metric["moves"])
        assert metric["workloads"] == [CELL]
    assert all("workloads" in m for m in manifest["per_layer"])


def test_the_mix_is_the_issues():
    mix = load_cell()["mix"]
    assert (mix["loop"], mix["clients"]) == ("closed", 288)
    assert mix["prompt_tokens"] == {
        "min": 1536, "max": 3072, "distribution": "log_uniform"}
    assert mix["output_tokens"] == {
        "min": 384, "max": 768, "distribution": "log_uniform"}
    assert mix["requests_per_client"] % 4 == 0
    # ISSUE 52: from the `course` line, 12 s if that opens on the steady
    # loop and 24 at most (setup_s holds ramp_s whole)
    assert 12.0 <= mix["ramp_s"] <= 24.0
    assert mix["deal"] == "fixed_order"
    run = load_cell()["model"]["run"]
    assert (run["n_slots"], run["max_len"], run["chunk"]) == (96, 4096, 8)
    assert mix["clients"] == 3 * run["n_slots"]
    assert (mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"]
            <= run["max_len"])


def test_requests_per_client_holds_at_one_and_a_half_times_the_roofline():
    """closed_loop.py's rule for this cell: no client runs out at 1.5
    times the roofline rate in the model of the loop (since PR 52 the
    mix's ONE deal, whatever the seed), and four requests a client
    fewer would fail it."""
    import closed_loop

    cell = load_cell()
    mix, slots = cell["mix"], cell["model"]["run"]["n_slots"]
    roof = closed_loop.roofline_tokens_per_s(cell)
    assert 7900 < roof < 8250, roof
    manifest = lib.read_json(os.path.join(lib.ROOT, "BENCHMARK.json"))
    until = closed_loop.horizon_s(mix, manifest["run_seconds"])

    def ran_out(per_client):
        m = dict(mix, requests_per_client=per_client)
        return sum(
            bool(closed_loop.run_dry(
                deal, slots, closed_loop.HEADROOM * roof, until)[0])
            for deal in closed_loop.deals(m, slots, 24))

    assert ran_out(mix["requests_per_client"]) == 0
    assert ran_out(mix["requests_per_client"] - 4) > 0


def test_configuration_holds_the_catalog_rows_numbers():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        rows = [json.loads(line) for line in f]
    row = next(r for r in rows if r["name"] == "GigaChat3.1-702B-A36B")
    model = load_cell()["model"]
    assert model["source"] == row["source_url"]
    differs = [k for k, v in row["config"].items() if model.get(k) != v]
    assert sorted(differs) == sorted(REDUCED) == sorted(model["reduced"])
    # the floors: a whole period and four layers after the leading
    # dense one, at least 8 routed experts, an eighth of the vocabulary
    assert model["num_hidden_layers"] - model["first_k_dense_replace"] >= 4
    assert model["n_routed_experts"] >= 8
    assert model["vocab_size"] * 8 >= row["config"]["vocab_size"]
    assert model["routed_experts_published"] == 256
    assert model["experts_held"] == [0, model["n_routed_experts"]]
    assert model["vocab_size_published"] == row["config"]["vocab_size"]
    for key in ("limit", "why"):
        assert key in model["limits"]["served_token_mean_gap_over_scale"]


def test_config_object_and_reference_agree_at_the_rehearsals_size():
    import jax
    import jax.numpy as jnp
    import numpy as np

    import reference_gigachat3
    import weights_gigachat3

    from dlrover_tpu.models import decode

    driver = lib.load_driver("serve_gigachat3")
    cell = load_cell()
    model, run, _ = driver.rehearsal_sizes(
        cell["model"], cell["model"]["run"], cell["mix"])
    cfg = driver.gigachat3_config(model, run)
    assert cfg.latent and cfg.first_k_dense == 1
    assert cfg.held == (8, 8) and cfg.n_experts == 32
    params = weights_gigachat3.make_params(model, 2**31 + 11, "float32")
    tokens = np.random.RandomState(0).randint(1, 256, (1, 40))
    with jax.default_matmul_precision("highest"):
        want = reference_gigachat3.forward(
            model, params, jnp.asarray(tokens[0]))
        out = decode.generate(cfg, params, jnp.asarray(tokens[:, :30]), 4)
        nxt = int(jnp.argmax(reference_gigachat3.forward(
            model, params, jnp.asarray(tokens[0, :30]))[-1]))
        low = reference_gigachat3.forward(
            model, params, jnp.asarray(tokens[0]), "fp8")
        # prefill (expanded) then one absorbed step through the cache
        cache = decode.init_kv_cache(cfg, 1, 64)
        _, cache = decode.prefill(cfg, params, jnp.asarray(tokens[:, :39]), cache)
        got, _ = decode.decode_step(
            cfg, params, jnp.asarray(tokens[:, 39]), cache, jnp.asarray([39]))
    assert int(out[0, 30]) == nxt
    assert want.shape == (40, 256)
    assert float(jnp.abs(got[0] - want[39]).max()) < 1e-4
    # the control one precision lower is another forward
    assert float(jnp.abs(low - want).max()) > 1e-2


def test_the_routers_bias_is_drawn_as_the_program_draws_it():
    """One spread in the benchmark's weights and in the program's own
    init: the bias moves choices, and how far is an assumption that
    the two must share."""
    import jax
    import numpy as np

    import weights_gigachat3

    from dlrover_tpu.models import llama

    driver = lib.load_driver("serve_gigachat3")
    cell = load_cell()
    model, run, _ = driver.rehearsal_sizes(
        cell["model"], cell["model"]["run"], cell["mix"])
    cfg = driver.gigachat3_config(model, run)
    ours = weights_gigachat3.make_params(model, 2**31 + 12, "float32")
    theirs = llama.init_params(cfg, jax.random.PRNGKey(12))
    a, b = (np.asarray(p["layers"]["router_bias"]) for p in (ours, theirs))
    assert a.shape == b.shape and a.size >= 64
    assert abs(a.std() / b.std() - 1.0) < 0.4
    assert 0.05 < b.std() < 0.2


def rehearsal_parts():
    driver = lib.load_driver("serve_gigachat3")
    cell = load_cell()
    model, run, _ = driver.rehearsal_sizes(
        cell["model"], cell["model"]["run"], cell["mix"])
    return driver, model, run


@pytest.mark.parametrize("seed", [2 ** 31 + 200 + s for s in range(8)])
def test_the_held_experts_take_the_even_share_and_the_bias_keeps_its_spread(seed):
    """PR 52: one scalar a layer on the held block's values puts the
    held experts' share of the pairs at the even one (4 x 8 / 32 = 1
    pair a token at the rehearsal's size) on rows the bisection did
    not read, by the REFERENCE's choice and by the PROGRAM's; the
    bias still moves choices (its spread), the 16 values keep their
    differences and the others their draw; two calls give one bias."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import reference_gigachat3
    import weights_gigachat3

    from dlrover_tpu.models import moe

    driver, model, run = rehearsal_parts()
    cfg = driver.gigachat3_config(model, run)
    first, held = model["experts_held"]
    even = model["num_experts_per_tok"] * held / model["routed_experts_published"]
    assert even == 1.0
    params = weights_gigachat3.make_params(model, seed, "float32")
    again = weights_gigachat3.make_params(model, seed, "float32")
    bias = np.asarray(params["layers"]["router_bias"])
    assert np.array_equal(bias, np.asarray(again["layers"]["router_bias"]))
    assert bias.dtype == np.float32 and bias.std() >= 0.05
    rows = np.random.RandomState(seed % 1000).randn(
        8192, model["hidden_size"]).astype(np.float32)
    rows /= np.sqrt(np.mean(rows * rows, -1, keepdims=True))
    for layer in range(bias.shape[0]):
        router = jnp.asarray(params["layers"]["router"][layer], jnp.float32)
        with jax.default_matmul_precision("highest"):
            _, chosen = reference_gigachat3.routing_weights(
                model, jnp.asarray(rows), router, jnp.asarray(bias[layer]))
            _, theirs = moe.route(
                jnp.asarray(rows) @ router, cfg.routing, jnp.asarray(bias[layer]))
        chosen = np.asarray(chosen)
        on_chip = (chosen >= first) & (chosen < first + held)
        for here in (on_chip, (np.asarray(theirs) >= first)
                     & (np.asarray(theirs) < first + held)):
            assert abs(here.sum(-1).mean() / even - 1.0) < 0.1
        # within the held block the loads stay uneven: the offset is one
        # scalar, so the block's values differ as they were drawn
        block = bias[layer, first:first + held]
        assert block.std() > 0.03
        loads = np.bincount(chosen[on_chip], minlength=first + held)[first:]
        assert loads.max() > 1.3 * loads.mean()


def test_the_offset_is_one_scalar_a_layer_on_the_held_block():
    """The bias is the draw plus one number a layer on the held
    block: take the block's offset away and what is left is N(0, 0.1)
    drawn from the configuration's `router_bias_seed`, the same for
    every --seed, as the other experts' values are."""
    import jax
    import numpy as np

    import weights_gigachat3

    _, model, _ = rehearsal_parts()
    seed = 2 ** 31 + 77
    first, held = model["experts_held"]
    items = weights_gigachat3.hashable(dict(
        {k: v for k, v in model.items() if k in weights_gigachat3.KEYS},
        held_first=first))
    drawn = 0.1 * jax.random.normal(
        jax.random.PRNGKey(model["router_bias_seed"]),
        weights_gigachat3.shapes(model)["layers"]["router_bias"],
        "float32")
    bias = np.asarray(weights_gigachat3.make_params(
        model, seed, "float32")["layers"]["router_bias"])
    other = weights_gigachat3.make_params(model, seed + 1, "float32")
    # another seed: other matrices, the same draw of the bias
    assert not np.array_equal(
        np.asarray(other["layers"]["router"]),
        np.asarray(weights_gigachat3.make_params(
            model, seed, "float32")["layers"]["router"]))
    assert np.abs(np.delete(
        np.asarray(other["layers"]["router_bias"]) - bias,
        np.s_[first:first + held], axis=1)).max() < 1e-7
    delta = bias - np.asarray(drawn)
    outside = np.delete(delta, np.s_[first:first + held], axis=1)
    assert np.abs(outside).max() < 1e-7  # the draw, to rounding under jit
    inside = delta[:, first:first + held]
    assert np.allclose(inside, inside[:, :1], atol=1e-6)
    assert 0.0 < np.abs(inside[:, 0]).max() < 0.5
    assert dict(items)["held_first"] == first


def test_full_config_object_at_the_published_widths():
    driver = lib.load_driver("serve_gigachat3")
    model = load_cell()["model"]
    cfg = driver.gigachat3_config(model, model["run"])
    assert (cfg.dim, cfg.n_heads, cfg.head_dim, cfg.v_head_dim) == (
        7168, 64, 192, 192)
    assert (cfg.q_lora_rank, cfg.kv_lora_rank, cfg.latent_width) == (
        1536, 512, 640)
    assert (cfg.n_experts, cfg.held, cfg.moe_top_k, cfg.mlp_dim) == (
        256, (0, 16), 8, 2048)
    assert (cfg.moe_n_group, cfg.moe_topk_group, cfg.moe_routed_scaling) == (
        8, 4, 2.5)
    assert (cfg.first_k_dense, cfg.dense_mlp_dim, cfg.n_layers) == (
        1, 18432, 5)
    assert cfg.vocab_size == 16032 and cfg.n_shared_experts == 1
    assert abs(cfg.attn_scale - 0.14468) < 1e-5
    assert cfg.rope_full.attention_factor == 1.0


def test_operation_counts():
    import flops_gigachat3

    model = load_cell()["model"]
    assert flops_gigachat3.latent_row_bytes(model) == 1152
    need = flops_gigachat3.latent_decode_needs(model, 1000)
    assert need["bytes"] == 1000 * 1152
    assert need["flops"] == 1000 * 64 * (576 + 512) * 2
    assert flops_gigachat3.attention_params(model) == 132_579_328
    parts = flops_gigachat3.matmul_params(model)
    assert parts["held_experts"] == 4 * 16 * 3 * 7168 * 2048
    # 4291 M parameters, 8.58 GB
    assert abs(flops_gigachat3.weight_bytes(model) / 1e9 - 8.58) < 0.01
    step = flops_gigachat3.decode_step_needs(model, 96, 96 * 2500)
    assert abs(step["bytes"] / 1e9 - 9.73) < 0.02
    moe = flops_gigachat3.moe_held_grouped_needs(model, 11, 48)
    assert moe["flops"] == 6.0 * 48 * 7168 * 2048
    assert moe["bytes"] == (
        11 * 3 * 7168 * 2048 * 2 + 48 * (2 * 7168 + 2 * 2048) * 2)
    touched = flops_gigachat3.expected_experts_touched(16, 256, 768)
    assert 15.1 < touched < 15.3


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

    driver = lib.load_driver("serve_gigachat3")
    args = argparse.Namespace(
        rehearsal=True, seed=2 ** 31 + 39, seconds=3.0, trace=1, control="",
        keep_trace="", dump="", t_start=time.time())
    out = driver.run(load_cell(), args, args.t_start)
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 0
    assert {"engine_step_ms", "engine_host_ms", "step_prefill_share_pct",
            "moe_expert_load_max_over_mean", "moe_held_pairs_per_token",
            "batch_occupancy_pct"} <= set(out["metrics"])
    # 8 of 32 experts held, 4 a token: one pair a token and layer
    assert 0.5 < out["metrics"]["moe_held_pairs_per_token"]["value"] < 1.5
