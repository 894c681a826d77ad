"""The yardstick of the SDAR cell: its entries in BENCHMARK.json
against its files, its configuration against the catalog's row, its
config object, weights and reference at the rehearsal's size, its
operation counts, the trajectory check on a trajectory it made itself,
its metric readers on a program without what they read, and one whole
rehearsal."""

import json
import os

import pytest

import lib

CELL = "sdar_serve_block_diffusion"
CONFIG = "sdar-30b-a3b-chat.serve-1chip"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW_METRICS = (
    "diffusion_tokens_per_forward", "block_paged_attention_roofline",
)


def load_cell() -> dict:
    return lib.load_cell(CELL)


def test_the_entries_name_the_cells_files():
    manifest = lib.read_json(os.path.join(lib.ROOT, "BENCHMARK.json"))
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [x["name"] for x in manifest[key]]
        assert len(names) == len(set(names)), key
    names = [m["name"] for m in manifest["per_layer"]]
    at = names.index(NEW_METRICS[0])
    assert names[at:at + len(NEW_METRICS)] == list(NEW_METRICS)
    cell = load_cell()
    assert cell["chips"] == 1
    assert cell["traffic"] == "block_diffusion_closed288"
    assert {m["name"] for m in cell["end_to_end"]} == {
        "setup_s", "serve_tokens_per_s", "tpot_p95_ms"}
    assert cell["model"]["name"] == cell["config"] == CONFIG
    assert cell["config_entry"]["reduced"] == ["num_hidden_layers"]
    assert list(cell["model"]["reduced"]) == ["num_hidden_layers"]
    assert cell["model"]["driver"] == "serve_sdar"
    listed = {m["name"]: m for m in cell["per_layer"]}
    # the twelve front-end, engine and device metrics of the serving
    # cells, and the accepted reader of the experts' load; NOT
    # `moe_grouped_matmul_roofline`, whose reckoning reads 104.9 and
    # 105.4% here (PERF.md section 7: over 105 a run is refused)
    assert {
        "engine_step_ms", "engine_host_ms", "step_prefill_share_pct",
        "batch_occupancy_pct", "device_idle_pct.serve",
        "submit_wait_p50_ms", "ttft_p50_ms.closed",
        "sched_lock_wait_p50_ms", "sched_lock_held_pct",
        "queue_wait_p50_ms", "admit_to_first_token_p50_ms",
        "pump_outside_step_ms", "moe_expert_load_max_over_mean",
    } | set(NEW_METRICS) <= set(listed)
    # later PRs list the cell under metrics of their own (PR 46: the
    # four setup_* legs); the readers that do not fit it stay out
    for name in ("moe_grouped_matmul_roofline",
                 "paged_attention_decode_roofline",
                 "hybrid_paged_attention_roofline",
                 "latent_paged_attention_roofline"):
        assert name not in listed
    for name in NEW_METRICS:
        metric = listed[name]
        path = os.path.join(lib.BENCH, "layer_metrics", name + ".py")
        module = lib.load_module(path, "m_" + name)
        assert (module.LAYER, module.UNIT, module.SOURCE, module.MOVES) == (
            metric["layer"], metric["unit"], metric["source"], metric["moves"])
        assert metric["workloads"] == [CELL]


def test_the_mix_is_the_issues():
    cell = load_cell()
    mix, run = cell["mix"], cell["model"]["run"]
    assert (mix["loop"], mix["clients"]) == ("closed", 288)
    assert mix["prompt_tokens"] == {
        "min": 128, "max": 448, "distribution": "log_uniform"}
    assert mix["output_tokens"] == {
        "min": 512, "max": 1536, "distribution": "log_uniform"}
    assert mix["requests_per_client"] % 4 == 0
    assert mix["ramp_s"] == 24.0
    assert (mix["check_requests"], mix["trace_s"]) == (6, 3.0)
    assert (run["n_slots"], run["max_len"], run["page_size"]) == (
        96, 2048, 16)
    assert mix["clients"] == 3 * run["n_slots"]
    assert (mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"]
            <= 1984 <= run["max_len"])
    gen = cell["model"]["generation"]
    assert (gen["block_length"], gen["denoising_steps"]) == (4, 2)
    assert run["page_size"] % gen["block_length"] == 0
    import generate

    sizes = generate.request_sizes(mix)
    assert 250 < sum(p for p, _ in sizes) / len(sizes) < 260
    assert 920 < sum(n for _, n in sizes) / len(sizes) < 940
    # one warm prompt a prefill bucket the prompts touch, and an
    # output whose forwards (3 a block) end on 8, 4, 2, 1
    assert mix["warm_prompt_tokens"] == [128, 256, 512]
    forwards = 3 * -(-mix["warm_output_tokens"] // 4)
    assert forwards % run["chunk"] == run["chunk"] - 1


def test_requests_per_client_holds_at_one_and_a_half_times_the_roofline():
    """closed_loop.py's rule for this cell: no client runs out at 1.5
    times the roofline rate in 24 seeds of the model of the loop, and
    four requests a client fewer would leave a client with no request
    unsent. The roofline counts a
    TOKEN'S bytes in the fastest loop the chip allows (commit fused: 2
    forwards a block of 4)."""
    import closed_loop

    cell = load_cell()
    mix, slots = cell["mix"], cell["model"]["run"]["n_slots"]
    roof = closed_loop.roofline_tokens_per_s(cell)
    assert 17000 < roof < 17900, roof
    manifest = lib.read_json(os.path.join(lib.ROOT, "BENCHMARK.json"))
    until = closed_loop.horizon_s(mix, manifest["run_seconds"])

    def dry(per_client):
        m = dict(mix, requests_per_client=per_client)
        runs = [
            closed_loop.run_dry(
                deal, slots, closed_loop.HEADROOM * roof, until)
            for deal in closed_loop.deals(m, slots, 24)]
        return sum(bool(out) for out, _ in runs), min(n for _, n in runs)

    # 12 a client: no client runs out and none is down to its last
    # request; 8 leave one client with nothing unsent, which
    # test_closed_loop.py refuses; 4 run clients out
    assert dry(mix["requests_per_client"])[0] == 0
    assert dry(mix["requests_per_client"])[1] >= 1
    assert dry(mix["requests_per_client"] - 4) == (0, 0)
    assert dry(mix["requests_per_client"] - 8)[0] > 0


def test_configuration_holds_the_catalog_rows_numbers():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        rows = [json.loads(line) for line in f]
    row = next(r for r in rows if r["name"] == "SDAR-30B-A3B-Chat")
    model = load_cell()["model"]
    assert model["source"] == row["source_url"]
    differs = [k for k, v in row["config"].items() if model.get(k) != v]
    assert differs == ["num_hidden_layers"] == list(model["reduced"])
    assert model["num_hidden_layers"] >= 4
    for name in ("qk_norm", "block_length", "mask_token_id", "remasking",
                 "denoising_steps", "logit_shift"):
        assert model["assumed"][name]
    for limit in ("served_token_mean_gap_over_scale",
                  "unmask_order_mean_confidence_gap"):
        for key in ("limit", "why"):
            assert key in model["limits"][limit]


def test_full_config_object_at_the_published_widths():
    driver = lib.load_driver("serve_sdar")
    model = load_cell()["model"]
    cfg = driver.sdar_config(model, model["run"])
    assert (cfg.dim, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == (
        2048, 32, 4, 128)
    assert (cfg.n_experts, cfg.held, cfg.moe_top_k, cfg.mlp_dim) == (
        128, (0, 128), 8, 768)
    assert (cfg.moe_routing, cfg.moe_scoring) == ("dropless", "softmax")
    assert (cfg.qk_norm, cfg.block_length, cfg.mask_token_id) == (
        True, 4, 151669)
    assert (cfg.vocab_size, cfg.n_layers, cfg.rope_theta) == (
        151936, 6, 1e6)
    assert not cfg.tie_embeddings and cfg.norm_eps == 1e-6


def test_operation_counts():
    import flops_sdar

    model = load_cell()["model"]
    need = flops_sdar.block_paged_needs(model, 1000)
    assert need["bytes"] == 1000 * 2048
    assert need["flops"] == 1000 * 4 * 32 * 128 * 2 * 2
    # a layer 1.246 GB, embedding and head 1.245 GB, 8.72 GB in all
    assert abs(flops_sdar.layer_params(model) * 2 / 1e9 - 1.246) < 0.001
    assert abs(flops_sdar.weight_bytes(model) / 1e9 - 8.72) < 0.01
    forward = flops_sdar.forward_bytes(model, 96, 721.0)
    assert abs(forward / 1e9 - 8.95) < 0.02
    driver = lib.load_driver("serve_sdar")
    assert driver.decode_step_bytes(model, 96, [(721.0, 1.0)]) == (
        forward * 2 / 4)


def _served_at_the_rehearsals_size(steps=2):
    """A request served by the engine at the rehearsal's size, with
    its recorded trajectory: (model, params, prompt, out, rows)."""
    import weights_sdar

    from dlrover_tpu.serving.engine import ContinuousBatcher

    driver = lib.load_driver("serve_sdar")
    cell = load_cell()
    model, run, _ = driver.rehearsal_sizes(
        cell["model"], cell["model"]["run"], cell["mix"])
    cfg = driver.sdar_config(model, run)
    params = weights_sdar.make_params(model, 2**31 + 43, "float32")
    eng = ContinuousBatcher(
        cfg, params, n_slots=2, max_len=64, max_new_tokens=32, chunk=4,
        pad_id=-1, kv_layout="paged", page_size=8, denoising_steps=steps,
        async_depth=0,
    )
    eng.record_blocks = True
    prompt = list(range(3, 14))
    idx = eng.submit(prompt, max_new=18)
    out = eng.generate_all([])[0].tolist()
    return model, params, prompt, out, eng.block_trajectories()[idx]


def test_config_object_and_reference_agree_at_the_rehearsals_size():
    import jax

    import reference_sdar

    model, params, prompt, out, _ = _served_at_the_rehearsals_size()
    with jax.default_matmul_precision("highest"):
        want = reference_sdar.block_diffusion_generate(
            model, params, prompt, 18, 4, 2, 255)
    assert out == want


def test_the_trajectory_check_reads_zero_on_a_sound_run_and_more_on_fp8():
    """The check as the driver runs it, in blocks, on a trajectory the
    engine recorded in float32: every served id is the reference's
    best at its forward (gap 0) and the program unmasked the
    reference's most confident positions (order gap 0); the fp8
    control would have served other ids."""
    import jax
    import numpy as np

    import reference_sdar

    model, params, prompt, out, rows = _served_at_the_rehearsals_size()
    stream, forwards = reference_sdar.trajectory_states(prompt, rows, 4, 255)
    assert stream[:8] == prompt[:8] and stream[11:11 + 18] == out
    # 11 prompt tokens: the first block opens with 3 given, 1 masked
    assert forwards[0]["masked"] == [False, False, False, True]
    assert forwards[0]["ids_in"] == prompt[8:] + [255]
    assert [f["start"] for f in forwards] == [8] + [
        s for s in (12, 16, 20, 24, 28) for _ in range(2)]
    with jax.default_matmul_precision("highest"):
        got = reference_sdar.check_request(
            model, params, prompt, rows, 64, 4, 255, 2, control="fp8")
    assert got["gaps"].size == 1 + 5 * 4 and got["order"].size == 5
    assert float(np.max(got["gaps"])) == 0.0
    assert float(np.max(got["order"])) == 0.0
    assert float(np.mean(got["control_gaps"])) > 1e-3
    # a block begun again after a preemption (its first denoising
    # forward recorded twice) keeps the forwards of its last run
    assert [r[:2] for r in rows[:5]] == [
        (8, 1), (8, 2), (12, 1), (12, 1), (12, 2)]
    again = rows[:3] + rows[2:]
    assert reference_sdar.trajectory_states(prompt, again, 4, 255) == (
        stream, forwards)


@pytest.mark.parametrize("name", NEW_METRICS)
def test_readers_return_none_where_there_is_nothing_to_read(name):
    run = {
        "cell": load_cell(), "window": {"steps": []}, "trace": None,
        "device_kind": "TPU v5 lite", "rehearsal": False, "events": [],
    }
    assert lib.read_layer_metric(name, run) is None


def test_readers_return_none_on_a_program_without_the_counts():
    """A parent commit's spans carry no `diff_*` counts: both readers
    leave the metric out and do not raise."""
    import time

    from dlrover_tpu.common import trace

    trace.clear()
    t0 = time.time()
    with trace.span("engine.step", alive=3, live_tokens=40):
        pass
    t1 = time.time()
    run = {
        "cell": load_cell(), "rehearsal": False, "events": [],
        "device_kind": "TPU v5 lite",
        "window": {"steps": [(t0 - 1.0, t1 - t0 + 2.0, 3, 40)]},
        "trace": {
            "t0": t0 - 1.0, "t1": t1 + 1.0,
            "op_seconds": {"paged_attention_decode_block.3": 0.5},
        },
    }
    assert lib.read_layer_metric("diffusion_tokens_per_forward", run) is None
    assert lib.read_layer_metric(
        "block_paged_attention_roofline", run) is None
    trace.clear()


def test_a_traced_rehearsal_reports_the_cells_metrics(capsys):
    """One whole run of the cell on the CPU at tiny sizes, through the
    driver as run.py would call it."""
    import argparse
    import time

    driver = lib.load_driver("serve_sdar")
    args = argparse.Namespace(
        rehearsal=True, seed=2 ** 31 + 43, seconds=3.0, trace=1, control="",
        keep_trace="", dump="", t_start=time.time())
    out = driver.run(load_cell(), args, args.t_start)
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 0
    assert {"engine_step_ms", "engine_host_ms", "step_prefill_share_pct",
            "moe_expert_load_max_over_mean", "batch_occupancy_pct",
            "diffusion_tokens_per_forward"} <= set(out["metrics"])
    assert 0.8 < out["metrics"]["diffusion_tokens_per_forward"]["value"] < 1.34
    assert out["checks"]["served_token_mean_gap_over_scale"]["value"] < 1e-6
