"""Driver of the SDAR serving cell: drivers/serve.py's control flow
(the process that holds the chip, the load generator as a child, the
scheduler stopped until the backlog stands, the reference only after
the engine is freed) for a configuration that GENERATES BY DIFFUSION
OVER BLOCKS: a slot's state is a block of 4 positions, a forward
unmasks some of them or commits the block, and a forward does not
yield one token. What differs: the program's config (normalised q and
k, the block length and the mask id, 128 experts routed without
dropping), the denoising steps handed to the engine, the weights and
the reference (weights_sdar.py, reference_sdar.py), the traffic's ids
(below the mask id), the kernels the resident programs must hold, and
what `correct` compares: the engine records every forward
(`record_blocks`), and the reference recomputes each denoising
forward of a sample of finished requests from that trajectory.
Everything else is imported from drivers/serve.py as it stands.
"""

import gc
import json
import os
import shutil
import statistics
import subprocess
import sys
import tempfile
import time

import flops_sdar
import generate
import lib
import weights_sdar

serve = lib.load_driver("serve")

BLOCK_KERNEL = "paged_attention_decode_block"
MOE_KERNEL = "moe_grouped_gate_up"


def sdar_config(model: dict, run: dict):
    """The program's config object for the configuration file."""
    import jax.numpy as jnp

    from dlrover_tpu.models.llama import LlamaConfig

    dtypes = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    if model["decoder_sparse_step"] != 1 or model["mlp_only_layers"]:
        raise ValueError("every layer's feed-forward must be sparse")
    if model["rope_scaling"] is not None or model["use_sliding_window"]:
        raise ValueError("plain rotary positions and full attention only")
    gen = model["generation"]
    return LlamaConfig(
        vocab_size=model["vocab_size"], dim=model["hidden_size"],
        n_layers=model["num_hidden_layers"],
        n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"],
        attn_head_dim=model["head_dim"],
        mlp_dim=model["moe_intermediate_size"],
        n_experts=model["num_experts"],
        moe_top_k=model["num_experts_per_tok"], moe_routing="dropless",
        qk_norm=True, block_length=gen["block_length"],
        mask_token_id=gen["mask_token_id"],
        rope_theta=float(model["rope_theta"]),
        norm_eps=float(model["rms_norm_eps"]),
        max_seq_len=run["max_seq_len"],
        dtype=dtypes[run["compute_dtype"]],
        param_dtype=dtypes[run["param_dtype"]],
        tie_embeddings=bool(model["tie_word_embeddings"]),
        attn_impl="auto", remat=False,
    )


def decode_step_bytes(model, slots, contexts):
    """What a TOKEN of a full batch must read in the fastest program
    the chip allows: a forward's bytes (flops_sdar.forward_bytes: the
    layers with every expert, the head, each live cell's K and V) times
    the denoising steps over the block length, T / B: with a block's
    commit fused into the next block's first forward, T forwards yield
    B tokens a slot. closed_loop.py takes the cell's roofline rate
    from it (the slots over this)."""
    gen = model["generation"]
    live = sum(c * w for c, w in contexts)
    return (flops_sdar.forward_bytes(model, slots, live)
            * gen["denoising_steps"] / gen["block_length"])


def rehearsal_sizes(model, run, mix):
    """Tiny sizes with the same control flow, for the CPU."""
    model = weights_sdar.tiny_model(model)
    run = dict(run, n_slots=6, max_len=96, chunk=4, max_seq_len=256,
               page_size=8, param_dtype="float32", compute_dtype="float32")
    mix = dict(
        mix, clients=18, requests_per_client=200,
        prompt_tokens=dict(mix["prompt_tokens"], min=9, max=40),
        output_tokens=dict(mix["output_tokens"], min=6, max=40),
        warm_prompt_tokens=[16, 32, 64], warm_output_tokens=20, ramp_s=1.0,
        trace_s=0.5,
    )
    return model, run, mix


def committed_tokens(rows, block: int):
    """(the first block's first position, every committed id) of one
    recorded trajectory."""
    commits = [r for r in rows if r[1] == 2]
    if not commits:
        return None, []
    return commits[0][0], [t for r in commits for t in r[2]]


def match_trajectories(good, picked, trajectories, block: int):
    """The recorded trajectory of each picked request: the one whose
    committed ids, from the prompt's end on, are the tokens the client
    was served (the engine's request numbers are not the clients')."""
    streams = {
        idx: committed_tokens(rows, block)
        for idx, rows in trajectories.items()
    }
    found = {}
    for i in picked:
        r = good[i]
        p, served = r["prompt_tokens"], list(r["tokens"])
        first = p - p % block
        hits = [
            idx for idx, (start, ids) in streams.items()
            if start == first
            and ids[p - first: p - first + len(served)] == served
        ]
        if len(hits) != 1:
            raise RuntimeError(
                f"{len(hits)} recorded trajectories stream the "
                f"{len(served)} tokens of client {r['client']}'s request "
                f"{r['k']}"
            )
        found[i] = trajectories[hits[0]]
    return found


def check_served(args, model, mix, good, trajectories, limits, checks):
    """The reference's view of what the window served, FORWARD BY
    FORWARD: for a seeded sample of the finished requests (the longest
    among them) the committed stream's keys and values under the block
    mask once, then every denoising forward's rows against them
    (reference_sdar.check_request). Held to their limits: (a) the MEAN
    over the unmasked tokens of the gap by which the served id's
    reference logit lies below that position's best at that forward,
    over the position's logit scale (a mean and not the widest gap,
    for the reason serve_mellum2.py gives: the router's near-ties);
    (b) the MEAN over the forwards that had a choice of the
    reference's confidence of the best masked position the program
    did NOT unmask less that of the worst it did, floored at 0. With
    --control the reference at that precision is held to the same
    limits in the program's place, and has to come out as not correct
    by one of them."""
    import jax
    import numpy as np

    import reference_sdar as reference

    gen = model["generation"]
    block, mask_id = gen["block_length"], gen["mask_token_id"]
    count = -(-block // gen["denoising_steps"])
    params = weights_sdar.make_params(
        model, args.seed, "float32" if args.rehearsal else "bfloat16")
    longest = max(
        range(len(good)),
        key=lambda i: good[i]["prompt_tokens"] + len(good[i]["tokens"]),
    )
    picked = generate.sample_indices(
        args.seed, len(good), int(mix["check_requests"]), longest)
    rows_of = match_trajectories(good, picked, trajectories, block)
    top = mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"]
    pad_to = -(-top // block) * block
    t0 = time.time()
    requests = dict(enumerate(
        generate.client_requests(args.seed, mix, mask_id)))
    parts = {}
    with jax.default_matmul_precision("highest"):
        for i in picked:
            r = good[i]
            prompt = requests[r["client"]][r["k"]]["tokens"]
            got = reference.check_request(
                model, params, prompt, rows_of[i], pad_to, block, mask_id,
                count, args.control)
            for name, values in got.items():
                parts.setdefault(name, []).append(
                    np.asarray(values, np.float64))
    got = {name: np.concatenate(v) for name, v in parts.items()}
    lib.log(f"[serve] reference: {len(picked)} requests, "
            f"{got['gaps'].size} unmasked tokens in {got['order'].size} "
            f"forwards with a choice, {time.time() - t0:.1f} s")

    def numbers(g):
        return {"n": int(g.size), "mean": float(g.mean()),
                "max": float(g.max()), "p99": float(np.quantile(g, 0.99)),
                "nonzero_share": float(np.mean(g > 0))}

    gap_name = "served_token_mean_gap_over_scale"
    order_name = "unmask_order_mean_confidence_gap"
    lib.log("[serve] gaps " + json.dumps(
        {"seed": args.seed, **numbers(got["gaps"])}))
    lib.log("[serve] order " + json.dumps(
        {"seed": args.seed, **numbers(got["order"])}))
    checks.at_most(
        gap_name, float(got["gaps"].mean()), limits[gap_name]["limit"])
    checks.at_most(
        order_name, float(got["order"].mean()), limits[order_name]["limit"])
    if "control_gaps" in got:
        lib.log("CONTROL " + json.dumps({
            "seed": args.seed, "precision": args.control,
            "gaps": numbers(got["control_gaps"]),
            "order": numbers(got["control_order"]),
        }))
        checks.at_most(
            f"{gap_name}[control:{args.control}]",
            float(got["control_gaps"].mean()), limits[gap_name]["limit"])
        checks.at_most(
            f"{order_name}[control:{args.control}]",
            float(got["control_order"].mean()), limits[order_name]["limit"])


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
    gen = model["generation"]
    cfg = sdar_config(model, run_)
    params = jax.block_until_ready(weights_sdar.make_params(
        model, args.seed, run_["param_dtype"]))
    lib.log(f"[serve] t+{time.time() - t_start:.1f}s weights on the device")

    engine = ContinuousBatcher(
        cfg, params, n_slots=run_["n_slots"], max_len=run_["max_len"],
        max_new_tokens=mix["output_tokens"]["max"], chunk=run_["chunk"],
        pad_id=-1, kv_layout=run_["kv_layout"],
        page_size=run_["page_size"],
        denoising_steps=gen["denoising_steps"],
    )
    del params
    engine.record_blocks = True
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
    # the traffic draws its ids below the mask id
    vocab = gen["mask_token_id"]
    try:
        serve.warm_up(
            gateway.addr, generate.warm_requests(args.seed, mix, vocab))
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
            "--seed", str(args.seed), "--vocab", str(vocab),
            "--open-at", repr(open_at), "--seconds", str(args.seconds),
            "--out", out_path,
        ])
        serve.wait_for_backlog(
            sched, mix["clients"], load_proc, 0.5 * mix["ramp_s"])
        sched.start()
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
        # thread keeps the engine (and 11 GB of the chip) alive
        sched.stop(timeout=180.0)
        shutil.rmtree(work_dir, ignore_errors=True)
    memory_peak = lib.memory_peak_bytes()
    trajectories = engine.block_trajectories()

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
        "kernel_path": engine.kernel_path,
        "paged": engine.paged_stats(),
    }))
    checks = lib.Checks()
    checks.at_most("window_compilations", counter.count, 0)
    checks.at_most("clients_ran_out", len(load["clients_ran_out"]), 0)
    checks.at_most("clients_stuck", len(load["clients_stuck"]), 0)
    checks.require("requests_finished", len(window["good"]) > 0)
    if not args.rehearsal:
        checks.require(
            "kernel_path", engine.kernel_path == "kernel", engine.kernel_path)
        held = {name: spy.kernels() for name, spy in spies.items()}
        chunk = [k for _, k in held["_run_chunk"]]
        checks.require(
            "block_paged_and_expert_kernels_in_chunk_programs",
            chunk and all(
                BLOCK_KERNEL in k and MOE_KERNEL in k for k in chunk
            ), str(chunk))
        prefill = [k for _, k in held["_paged_cold_fn"]]
        checks.require(
            "flash_and_expert_kernels_in_prefill_programs",
            prefill and all(
                serve.FLASH_KERNEL in k and MOE_KERNEL in k for k in prefill
            ), str(held["_paged_cold_fn"]))
    # free the engine; only then the reference (serve_mellum2.py says
    # why every live array is deleted outright)
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
            args, model, mix, window["good"], trajectories, limits, checks)

    failed = len(window["ended"]) - len(window["good"])
    out = {
        "correct": checks.ok and failed == 0, "checks": checks.compared,
        "attempted": len(window["ended"]), "failed": failed,
        "device": dict(device, memory_peak_bytes=memory_peak),
    }
    run_view = {
        "cell": dict(cell, model=dict(model, run=run_), mix=mix),
        "window": window, "trace": trace, "rehearsal": args.rehearsal,
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
