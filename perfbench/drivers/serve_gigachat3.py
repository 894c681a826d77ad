"""Driver of the GigaChat3.1 serving cell: drivers/serve.py's control
flow (the process that holds the chip, the load generator as a child,
the scheduler stopped until the backlog stands, the reference only
after the engine is freed) for a configuration with latent attention
(one cached row a token and layer, a latent page pool, an expanded
prefill and an absorbed decode), one leading dense layer, and one
chip's share of the routed experts (16 of 256, routed by a
group-limited sigmoid, beside a shared expert). What differs from
drivers/serve_mellum2.py: the program's config, the weights and the
reference (weights_gigachat3.py, reference_gigachat3.py), the
admission program that is watched (`_paged_cold_fn`) and the kernels
the resident programs must hold. The cells a step read and the pairs
it routed are the PROGRAM's own counts on its `engine.step` spans
(`latent_cells`, `moe_held_pairs`, `moe_routed_pairs`), so no record
is kept here beside them. Everything else is imported from
drivers/serve.py as it stands.
"""

import gc
import json
import math
import os
import shutil
import statistics
import subprocess
import sys
import tempfile
import time

import flops_gigachat3
import generate
import lib
import weights_gigachat3

serve = lib.load_driver("serve")

LATENT_KERNEL = "paged_attention_decode_latent"
MOE_KERNEL = "moe_grouped_gate_up"


def gigachat3_config(model: dict, run: dict):
    """The program's config object for the configuration file."""
    import jax.numpy as jnp

    from dlrover_tpu.models.llama import LlamaConfig, RopeSpec

    dtypes = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    rs = model["rope_scaling"]
    if rs["rope_type"] != "yarn" or model["scoring_func"] != "sigmoid":
        raise ValueError("the driver knows YaRN and a sigmoid router")
    if model["topk_method"] != "noaux_tc" or not model["norm_topk_prob"]:
        raise ValueError("the driver knows noaux_tc with norm_topk_prob")
    if model["moe_layer_freq"] != 1 or model["num_nextn_predict_layers"]:
        raise ValueError(
            "every layer past the leading dense ones is sparse, and no "
            "prediction module is served")

    def m(scale):
        return 0.1 * scale * math.log(rs["factor"]) + 1.0

    return LlamaConfig(
        vocab_size=model["vocab_size"], dim=model["hidden_size"],
        n_layers=model["num_hidden_layers"],
        n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"],
        q_lora_rank=model["q_lora_rank"],
        kv_lora_rank=model["kv_lora_rank"],
        qk_nope_head_dim=model["qk_nope_head_dim"],
        qk_rope_head_dim=model["qk_rope_head_dim"],
        v_head_dim=model["v_head_dim"],
        first_k_dense=model["first_k_dense_replace"],
        dense_mlp_dim=model["intermediate_size"],
        mlp_dim=model["moe_intermediate_size"],
        n_experts=model["routed_experts_published"],
        experts_held=tuple(model["experts_held"]),
        moe_top_k=model["num_experts_per_tok"], moe_routing="dropless",
        n_shared_experts=model["n_shared_experts"],
        moe_scoring=model["scoring_func"],
        moe_n_group=model["n_group"], moe_topk_group=model["topk_group"],
        moe_routed_scaling=float(model["routed_scaling_factor"]),
        rope_theta=float(model["rope_theta"]),
        rope_full=RopeSpec(
            theta=float(model["rope_theta"]),
            yarn_factor=float(rs["factor"]),
            original_len=rs["original_max_position_embeddings"],
            beta_fast=float(rs["beta_fast"]),
            beta_slow=float(rs["beta_slow"]),
            attention_factor=m(rs["mscale"]) / m(rs["mscale_all_dim"]),
        ),
        rope_mscale_all_dim=float(rs["mscale_all_dim"]),
        norm_eps=float(model["rms_norm_eps"]),
        max_seq_len=run["max_seq_len"],
        dtype=dtypes[run["compute_dtype"]],
        param_dtype=dtypes[run["param_dtype"]],
        tie_embeddings=bool(model["tie_word_embeddings"]),
        attn_impl="auto", remat=False,
    )


def decode_step_bytes(model, slots, contexts):
    """serve.decode_step_bytes for this configuration: every matrix a
    token is multiplied by, all 16 held experts of every expert layer
    among them (a batch of 96 slots routes 768 pairs a layer over the
    256), no embedding (a gather), and every live position's ONE
    latent row a layer."""
    live = sum(c * w for c, w in contexts)
    return flops_gigachat3.decode_step_needs(
        model, slots, slots * live)["bytes"]


def rehearsal_sizes(model, run, mix):
    """Tiny sizes with the same control flow, for the CPU."""
    model = weights_gigachat3.tiny_model(model)
    run = dict(run, n_slots=6, max_len=96, chunk=4, max_seq_len=256,
               param_dtype="float32", compute_dtype="float32")
    mix = dict(
        mix, clients=18, requests_per_client=200,
        prompt_tokens=dict(mix["prompt_tokens"], min=20, max=60),
        output_tokens=dict(mix["output_tokens"], min=6, max=20),
        warm_prompt_tokens=[16, 32, 64], warm_output_tokens=7, ramp_s=1.0,
        trace_s=0.5,
    )
    return model, run, mix


def check_served(args, model, mix, n_slots, good, limits, checks):
    """serve.check_served with this configuration's weights and
    reference (computed in blocks, a layer a call, given the same
    share of the experts and of the vocabulary). What is held to
    the limit is the MEAN of the served tokens' gaps, over every
    served token of the sample: with experts a single position's gap
    swings with the router's near-ties (the reference takes another
    set of experts in one (position, layer) pair in six once its
    operands are rounded to bfloat16), so a maximum over a thousand
    tokens is an extreme value and a mean is not. With --control the
    reference at that precision is held to the same limit in the
    program's place, and has to come out as not correct."""
    import jax
    import numpy as np

    import reference_gigachat3 as reference

    params = weights_gigachat3.make_params(
        model, args.seed, "float32" if args.rehearsal else "bfloat16")
    longest = max(
        range(len(good)),
        key=lambda i: good[i]["prompt_tokens"] + len(good[i]["tokens"]),
    )
    picked = generate.sample_indices(
        args.seed, len(good), int(mix["check_requests"]), longest)
    pad_to = mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"]
    gaps, control = [], []
    t0 = time.time()
    requests = dict(enumerate(
        generate.client_requests(
            args.seed, mix, model["vocab_size"], n_slots)))
    with jax.default_matmul_precision("highest"):
        r = good[picked[0]]
        flips = reference.routing_choice_differs_share(
            model, params, requests[r["client"]][r["k"]]["tokens"],
            r["tokens"], pad_to)
        lib.log("[serve] router: the experts chosen differ between the "
                "float32 forward and one with bfloat16 operands in "
                f"{100.0 * flips:.2f}% of (position, layer) pairs of the "
                "longest checked request")
        for i in picked:
            r = good[i]
            prompt = requests[r["client"]][r["k"]]["tokens"]
            g, c = reference.served_token_gaps(
                model, params, prompt, r["tokens"], pad_to, args.control)
            gaps.append(np.asarray(g, np.float64))
            if c is not None:
                control.append(np.asarray(c, np.float64))
    gaps = np.concatenate(gaps)
    lib.log(f"[serve] reference: {len(picked)} requests, {gaps.size} served "
            f"tokens, {time.time() - t0:.1f} s")

    def numbers(g):
        return {"mean": float(g.mean()), "max": float(g.max()),
                "p99": float(np.quantile(g, 0.99)),
                "off_best_share": float(np.mean(g > 0))}

    name = "served_token_mean_gap_over_scale"
    lib.log("[serve] gaps " + json.dumps(
        {"seed": args.seed, "tokens": int(gaps.size), **numbers(gaps)}))
    checks.at_most(name, float(gaps.mean()), limits[name]["limit"])
    if control:
        control = np.concatenate(control)
        lib.log("CONTROL " + json.dumps({
            "seed": args.seed, "precision": args.control,
            "tokens": int(control.size), **numbers(control),
        }))
        checks.at_most(f"{name}[control:{args.control}]",
                       float(control.mean()), limits[name]["limit"])


def run(cell, args, t_start: float) -> dict:
    import jax

    from dlrover_tpu.runtime import enable_compile_cache
    from dlrover_tpu.serving.engine import ContinuousBatcher
    from dlrover_tpu.serving.gateway import ServingGateway
    from dlrover_tpu.serving.scheduler import RequestScheduler, SloConfig

    device = lib.require_device(args.rehearsal, cell["chips"])
    lib.log(f"[serve] t+{time.time() - t_start:.1f}s the device answers")
    enable_compile_cache()
    counter = lib.CompileCounter()
    model, mix = cell["model"], cell["mix"]
    run_ = model["run"]
    limits = model["limits"]
    if args.rehearsal:
        model, run_, mix = rehearsal_sizes(model, run_, mix)
    cfg = gigachat3_config(model, run_)
    params = jax.block_until_ready(weights_gigachat3.make_params(
        model, args.seed, run_["param_dtype"]))
    lib.log(f"[serve] t+{time.time() - t_start:.1f}s weights on the device")

    engine = ContinuousBatcher(
        cfg, params, n_slots=run_["n_slots"], max_len=run_["max_len"],
        max_new_tokens=mix["output_tokens"]["max"], chunk=run_["chunk"],
        pad_id=-1, kv_layout=run_["kv_layout"],
        page_size=run_["page_size"],
    )
    del params
    spies = {}
    for attr, static_numbers in (
        ("_run_chunk", True), ("_paged_cold_fn", False)
    ):
        spies[attr] = serve.ShapeSpy(getattr(engine, attr), static_numbers)
        setattr(engine, attr, spies[attr])
    spans = serve.Spans(engine)
    timeout = 600.0
    sched = RequestScheduler(
        engine, slo=SloConfig(
            max_new_tokens=mix["output_tokens"]["max"],
            default_deadline_s=timeout,
            max_queue_depth=2 * mix["clients"],
        ),
    )
    spans.wrap_submit(sched)
    gateway = ServingGateway(sched, stream_timeout_s=timeout)
    sched.start()
    gateway.start()
    work_dir = tempfile.mkdtemp(prefix="perfbench_")
    trace_out, trace_thread, load_proc, trace = {}, None, None, None
    try:
        serve.warm_up(
            gateway.addr,
            generate.warm_requests(args.seed, mix, model["vocab_size"]))
        lib.log(f"[serve] t+{time.time() - t_start:.1f}s warm")
        open_at = time.time() + mix["ramp_s"]
        close_at = open_at + args.seconds
        out_path = os.path.join(work_dir, "load.json")
        # as in drivers/serve.py: the scheduler stands still until
        # every client's first request is queued
        sched.stop()
        load_proc = subprocess.Popen([
            sys.executable, os.path.join(lib.BENCH, "drivers", "loadgen.py"),
            "--addr", gateway.addr, "--traffic", json.dumps(mix),
            "--seed", str(args.seed), "--vocab", str(model["vocab_size"]),
            "--slots", str(run_["n_slots"]),
            "--open-at", repr(open_at), "--seconds", str(args.seconds),
            "--out", out_path,
        ])
        # three quarters of the ramp: 288 prompts of 1.5-3 k tokens
        # are 4 MB of JSON through one front door
        serve.wait_for_backlog(
            sched, mix["clients"], load_proc, 0.75 * mix["ramp_s"])
        sched.start()
        lib.log(f"[serve] t+{time.time() - t_start:.1f}s the backlog stands, "
                f"{open_at - time.time():.1f} s before the window opens")
        if args.trace:
            trace_thread = serve.trace_slice(
                open_at, args.seconds, mix["trace_s"],
                os.path.join(work_dir, "trace"), trace_out)
        time.sleep(max(0.0, open_at - time.time()))
        counter.count, counter.counting = 0, True
        time.sleep(max(0.0, close_at - time.time()))
        counter.counting = False
        rc = load_proc.wait(timeout=180)
        if rc != 0:
            raise RuntimeError(f"the load generator exited with code {rc}")
        load = lib.read_json(out_path)
        if trace_thread is not None:
            import trace_reduce

            trace_thread.join()
            trace_dir = os.path.join(work_dir, "trace")
            if args.keep_trace:
                os.makedirs(args.keep_trace, exist_ok=True)
                shutil.copy(
                    trace_reduce.find_xplane(trace_dir), args.keep_trace)
            trace = trace_reduce.reduce_dir(trace_dir)
            trace["t0"], trace["t1"] = trace_out["t0"], trace_out["t1"]
    finally:
        if load_proc is not None and load_proc.poll() is None:
            load_proc.kill()
            load_proc.wait()
        gateway.stop()
        # a pump that is admitting the queue the clients left behind
        # can hold its step for many prefills: wait it out, or the
        # thread keeps the engine (and 9 GB of the chip) alive
        sched.stop(timeout=180.0)
        shutil.rmtree(work_dir, ignore_errors=True)
    memory_peak = lib.memory_peak_bytes()

    window = serve.summarize(load, spans, open_at, close_at, run_["n_slots"])
    lib.log("[serve] course " + json.dumps(
        serve.course(load, spans, close_at, run_["n_slots"])))
    if args.dump:
        os.makedirs(args.dump, exist_ok=True)
        with open(os.path.join(args.dump, f"serve_{args.seed}.json"), "w") as f:
            json.dump({"load": load, "steps": spans.steps,
                       "submits": spans.submits}, f)
    tpot_p95 = statistics.quantiles(
        window["tpot_ms"], n=20, method="inclusive")[18]
    cell_view = dict(cell, model=dict(model, run=run_), mix=mix)
    # the router's deal as the program counted it, in untraced runs
    # too (logged: the metrics of these names are a traced run's)
    experts = {
        name: lib.read_layer_metric(
            name, {"cell": cell_view, "window": window})
        for name in ("moe_held_pairs_per_token",
                     "moe_expert_load_max_over_mean")
    }
    lib.log("[serve] " + json.dumps({
        "ended": len(window["ended"]), "good": len(window["good"]),
        "tokens_in_window": window["tokens_in_window"],
        "plain_tokens_per_s": window["tokens_in_window"] / args.seconds,
        "engine_steps": len(window["steps"]),
        "tpot_p95_ms": tpot_p95,
        "compilations": counter.count,
        "send_gap_ms": load["send_gap_ms"],
        "clients_ran_out": load["clients_ran_out"],
        "least_requests_left": load["least_requests_left"],
        "clients_stuck": load["clients_stuck"],
        **serve.deal_census(load, window, mix, run_["n_slots"]),
        **experts,
        "kernel_path": engine.kernel_path,
        "paged": engine.paged_stats(),
    }))
    checks = lib.Checks()
    checks.at_most("window_compilations", counter.count, 0)
    checks.at_most("clients_ran_out", len(load["clients_ran_out"]), 0)
    checks.at_most("clients_stuck", len(load["clients_stuck"]), 0)
    checks.require("requests_finished", len(window["good"]) > 0)
    if not args.rehearsal:
        held = {name: spy.kernels() for name, spy in spies.items()}
        chunk = [k for _, k in held["_run_chunk"]]
        checks.require(
            "latent_paged_and_expert_kernels_in_chunk_programs",
            chunk and all(
                LATENT_KERNEL in k and MOE_KERNEL in k for k in chunk
            ), str(chunk))
        prefill = [k for _, k in held["_paged_cold_fn"]]
        checks.require(
            "flash_and_expert_kernels_in_prefill_programs",
            prefill and all(
                serve.FLASH_KERNEL in k and MOE_KERNEL in k for k in prefill
            ), str(held["_paged_cold_fn"]))
    # free the engine; only then the reference. The gateway's handler
    # threads of the requests the clients left behind still hold the
    # scheduler, and with it the engine, until their streams time out
    # (the runs of PR 31 found 9.36 GB held that way in the Mellum2 cell), so every array
    # that is still live on the device is deleted outright: the memory
    # peak is read, and nothing made before this line is used after it
    del spies, spans.steps, sched, gateway, engine
    gc.collect()
    held = jax.live_arrays()
    lib.log(f"[serve] {sum(a.nbytes for a in held) / 1e9:.2f} GB in "
            f"{len(held)} arrays still live on the device: deleted")
    for array in held:
        array.delete()
    del held
    if window["good"]:
        check_served(
            args, model, mix, run_["n_slots"], window["good"], limits, checks)

    failed = len(window["ended"]) - len(window["good"])
    out = {
        "correct": checks.ok and failed == 0, "checks": checks.compared,
        "attempted": len(window["ended"]), "failed": failed,
        "device": dict(device, memory_peak_bytes=memory_peak),
    }
    run_view = {
        "cell": cell_view, "window": window, "trace": trace,
        "rehearsal": args.rehearsal,
        "device_kind": device["kind"], "events": [],
    }
    if args.trace:
        out["device"].update(
            busy_s=trace["busy_s"], window_s=trace["window_s"])
        out["breakdown"] = {
            "device_ops": trace["device_ops"], "idle_gaps": trace["idle_gaps"],
        }
        out["metrics"] = lib.layer_metrics(cell, run_view)
    else:
        out["metrics"] = lib.end_to_end_metrics(cell, {
            "setup_s": open_at - t_start,
            "serve_tokens_per_s": window["serve_tokens_per_s"],
            "tpot_p95_ms": tpot_p95,
        })
    return out
