"""Driver of the serving cells. This process holds the chip: it makes
the weights from --seed, builds the engine behind the program's own
front door (ServingGateway -> RequestScheduler -> ContinuousBatcher,
as chip_smoke.py's serve_setting does), warms the cell's shapes, and
starts the load generator as a child that never imports jax (the
scheduler stands still until the clients' first requests are queued,
so that every run starts from the same full batch). After
the window it stops the server, reads the memory peak, frees the
engine, and only then runs perfbench/reference.py over a seeded
sample of the requests the window finished.

The benchmark's own spans and counters sit around the calls into each
layer (engine.step and, where the engine has them, its harvest, admit
and dispatch methods); spans inside the program are a later PR's.
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
import threading
import time
import urllib.request

import flops
import generate
import lib
import weights

SPANNED = ("_harvest", "_admit", "_dispatch_chunk")
PAGED_KERNEL = "paged_attention_decode"
FLASH_KERNEL = "flash_attention_fwd"


def decode_step_bytes(model, slots, contexts):
    """Bytes that one decode step of a full batch has to read: every
    weight a token is multiplied by, in bfloat16, and the K and V of
    every live position (`contexts`: (positions, share of slot time)
    pairs, closed_loop.live_contexts). closed_loop.py takes the cell's
    roofline rate from it."""
    live = sum(c * w for c, w in contexts)
    return (2 * flops.matmul_params(model)
            + flops.paged_decode_needs(model, slots * live, slots)["bytes"])


def rehearsal_sizes(model, run, mix):
    """Tiny sizes with the same control flow, for the CPU."""
    model = weights.tiny_model(model)
    run = dict(run, n_slots=6, max_len=64, chunk=4, max_seq_len=256)
    mix = dict(
        mix, clients=18, requests_per_client=200,
        prompt_tokens=dict(mix["prompt_tokens"], min=8, max=30),
        output_tokens=dict(mix["output_tokens"], min=6, max=20),
        warm_prompt_tokens=[16, 32], warm_output_tokens=7, ramp_s=1.0,
        trace_s=0.5,
    )
    return model, run, mix


class Spans:
    """Wraps methods of the engine with a TraceAnnotation and, for
    step(), a record of when it ran and what the batch held."""

    def __init__(self, engine):
        import jax
        import numpy as np

        self.steps = []  # (wall at start, seconds, active, live_tokens)
        self.submits = []  # (wall at the call, seconds inside it)
        annotate = jax.profiler.TraceAnnotation
        real_step = engine.step

        def step():
            t0 = time.time()
            with annotate("perfbench:engine_step"):
                events = real_step()
            live = ~engine.done
            self.steps.append((
                t0, time.time() - t0, int(live.sum()),
                int(np.asarray(engine.pos)[live].sum()),
            ))
            return events

        engine.step = step
        for attr in SPANNED:
            fn = getattr(engine, attr, None)
            if fn is not None:
                setattr(engine, attr, self._spanned(annotate, attr, fn))

    def wrap_submit(self, sched):
        """The front door's call into the scheduler: how long a
        request's hand-over takes (it waits for the scheduler's lock)."""
        real_submit = sched.submit

        def submit(*a, **kw):
            t0 = time.time()
            try:
                return real_submit(*a, **kw)
            finally:
                self.submits.append((t0, time.time() - t0))

        sched.submit = submit

    @staticmethod
    def _spanned(annotate, attr, fn):
        def wrapped(*a, **kw):
            with annotate("perfbench:engine" + attr):
                return fn(*a, **kw)

        return wrapped


class ShapeSpy:
    """Remembers the argument shapes of a jitted program's calls, so
    that each specialisation can be compiled ahead of time AFTER the
    window and its text searched for the kernels it holds. A Python
    number among the arguments tells specialisations apart only where
    `static_numbers` says it is a static argument (a chunk length);
    elsewhere it is a traced scalar (a slot index)."""

    def __init__(self, fn, static_numbers: bool):
        self.fn, self.static_numbers, self.calls = fn, static_numbers, {}

    def __call__(self, *a, **kw):
        import jax

        def aval(x):
            if hasattr(x, "shape"):
                return jax.ShapeDtypeStruct(x.shape, x.dtype)
            return x

        avals = jax.tree_util.tree_map(aval, (a, kw))
        key = str(jax.tree_util.tree_map(
            lambda x: x if hasattr(x, "shape") or self.static_numbers
            else type(x).__name__, avals))
        self.calls.setdefault(key, avals)
        return self.fn(*a, **kw)

    def __getattr__(self, item):
        return getattr(self.fn, item)

    def kernels(self) -> list:
        """(shapes of the array arguments, kernel names) of each
        specialisation that was called."""
        import jax

        out = []
        for a, kw in self.calls.values():
            text = self.fn.lower(*a, **kw).compile().as_text()
            shapes = [
                tuple(x.shape) for x in jax.tree_util.tree_leaves(a[2:])
                if hasattr(x, "shape") and 0 < len(x.shape) <= 2
            ]
            out.append((shapes, lib.kernel_names(text)))
        return out


def post(addr, request, timeout=600.0):
    body = json.dumps(dict(request, stream=False, deadline_s=timeout)).encode()
    req = urllib.request.Request(
        addr + "/v1/generate", data=body,
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read())


def warm_up(addr, requests):
    """Every prefill bucket the mix touches and every chunk length,
    all at once (so that several slots are live together)."""
    answers, threads = [None] * len(requests), []

    def one(i):
        answers[i] = post(addr, requests[i])

    for i in range(len(requests)):
        threads.append(threading.Thread(target=one, args=(i,)))
        threads[-1].start()
    for t in threads:
        t.join()
    for request, answer in zip(requests, answers):
        if (answer or {}).get("state") != "done" or (
            len(answer["tokens"]) != request["max_new"]
        ):
            raise RuntimeError(f"warm-up request failed: {answer}")


def wait_for_backlog(sched, clients, load_proc, timeout_s):
    """Until every client's first request stands in the scheduler's
    queue (the scheduler is stopped, so nothing leaves it)."""
    deadline = time.time() + timeout_s
    while sched.queue_depth() < clients:
        if load_proc.poll() is not None:
            raise RuntimeError("the load generator ended before the window")
        if time.time() > deadline:
            raise RuntimeError(
                f"{sched.queue_depth()} of {clients} first requests queued "
                f"after {timeout_s:.0f} s")
        time.sleep(0.02)


def trace_slice(open_at, seconds, trace_s, trace_dir, out):
    """Traces trace_s seconds in the middle of the window, from a
    thread of the process that holds the chip."""
    import jax

    import trace_reduce

    def body():
        time.sleep(max(0.0, open_at + (seconds - trace_s) / 2 - time.time()))
        os.makedirs(trace_dir, exist_ok=True)
        jax.profiler.start_trace(
            trace_dir, profiler_options=trace_reduce.profile_options())
        out["t0"] = time.time()
        time.sleep(trace_s)
        out["t1"] = time.time()
        jax.profiler.stop_trace()

    thread = threading.Thread(target=body, name="perfbench-trace")
    thread.start()
    return thread


def tpot_ms(record):
    n = len(record["tokens"])
    first, last = record["chunks"][0][0], record["chunks"][-1][0]
    return (last - first) / (n - 1) * 1e3


DELIVERY_GAP_S = 0.1  # a pause in the clients' chunks that ends a delivery


def delivery_rate(records, open_at, close_at):
    """Output tokens per second streamed to the clients, over the
    window snapped to the engine's deliveries. The engine hands out
    its tokens in bursts (a dispatch of `chunk` steps for every live
    slot, within some milliseconds, then nothing for the length of the
    next dispatch), and a window of fixed length holds one such burst
    more or fewer by where its edges fall: a step of 1.5% at 66 bursts
    in 30 s. So the rate is taken from the first burst that begins
    inside the window to the last one that does: the tokens of all of
    them but the last, over the time between those two beginnings.
    Every pause between them counts in full. Where the chunks come
    with no such pauses (fewer than three bursts), the plain count
    over the window's length is exact enough, and is what is taken."""
    chunks = sorted(
        (t, n) for r in records for t, n in r["chunks"]
        if open_at - 2.0 <= t <= close_at  # 2 s back: the burst open_at cuts
    )
    bursts, last = [], None
    for t, n in chunks:
        if last is None or t - last > DELIVERY_GAP_S:
            bursts.append([t, 0])
        bursts[-1][1] += n
        last = t
    inside = [b for b in bursts if b[0] >= open_at]
    if len(inside) < 3:
        return sum(n for t, n in chunks if t >= open_at) / (close_at - open_at)
    return sum(n for _, n in inside[:-1]) / (inside[-1][0] - inside[0][0])


def summarize(load, spans, open_at, close_at, n_slots):
    """Client-side numbers of the window, nothing rounded."""
    ended = [
        r for r in load["records"]
        if r["state"] != "open_at_close" and open_at <= r["t_end"] <= close_at
    ]
    good = [
        r for r in ended
        if r["state"] == "done" and len(r["tokens"]) == r["max_new"]
    ]
    tokens_in_window = sum(
        n for r in load["records"] for t, n in r["chunks"]
        if open_at <= t <= close_at
    )
    steps = [s for s in spans.steps if open_at <= s[0] <= close_at]
    submits = [w for t, w in spans.submits if open_at <= t <= close_at]
    return {
        "ended": ended, "good": good,
        "tokens_in_window": tokens_in_window,
        "serve_tokens_per_s": delivery_rate(
            load["records"], open_at, close_at),
        "tpot_ms": [tpot_ms(r) for r in good if len(r["tokens"]) > 1],
        "ttft_ms": [
            (r["chunks"][0][0] - r["t_send"]) * 1e3 for r in good
        ],
        "steps": steps, "n_slots": n_slots, "submit_wait_s": submits,
    }


def deal_census(load, window, mix, n_slots):
    """What the deal put into the window, for the `[serve]` line
    (logged: no metric, no limit). Of the first `n_slots` requests to
    get a first token, which are the first admissions (a prefill is
    one prompt a call, so first tokens come in the order of
    admission), how many were cut ones: all `n_slots` where the
    clients that start in a slot are the ones generate.py cut. Then
    the requests that ended in the window, how many of those were cut
    ones, the fewest tokens one of them had, and the loader's own
    count of the seconds it took to start its clients."""
    cut = generate.cut_clients(mix, n_slots)

    def is_cut(r):
        return r["k"] == 0 and r["client"] < cut

    firsts = sorted(
        (r["chunks"][0][0], is_cut(r)) for r in load["records"]
        if r["k"] == 0 and r["chunks"]
    )[:n_slots]
    ended = window["ended"]
    return {
        "first_admissions_cut": sum(c for _, c in firsts),
        "ended_in_window": len(ended),
        "ended_cut": sum(is_cut(r) for r in ended),
        "ended_shortest_tokens": min(
            (len(r["tokens"]) for r in ended), default=None),
        # how long the loader took to queue every first request
        "clients_started_s": load.get("clients_started_s"),
    }


def course(load, spans, close_at, n_slots, bucket_s=2.0):
    """How the batch filled from the clients' start to the window's
    close: per bucket of seconds, the mean share of slots alive after
    a step and the tokens streamed per second."""
    t0 = load["started_at"]
    n = int(math.ceil((close_at - t0) / bucket_s))
    alive, tokens = [[] for _ in range(n)], [0] * n
    for t, _, active, _ in spans.steps:
        if t0 <= t < close_at:
            alive[int((t - t0) / bucket_s)].append(active)
    for r in load["records"]:
        for t, k in r["chunks"]:
            if t0 <= t < close_at:
                tokens[int((t - t0) / bucket_s)] += k
    return {
        "bucket_s": bucket_s, "window_opens_at_s": load["open_at"] - t0,
        "occupancy_pct": [
            round(100.0 * sum(a) / len(a) / n_slots) if a else None
            for a in alive
        ],
        "tokens_per_s": [round(k / bucket_s) for k in tokens],
    }


def check_served(args, model, mix, good, limits, checks):
    """The reference's view of what the window served: one forward
    over prompt + served tokens of a seeded sample of the finished
    requests (the longest among them), after the engine is freed."""
    import jax
    import numpy as np

    import reference

    params = weights.make_params(
        model, args.seed, "float32" if args.rehearsal else "bfloat16")
    longest = max(
        range(len(good)),
        key=lambda i: good[i]["prompt_tokens"] + len(good[i]["tokens"]),
    )
    picked = generate.sample_indices(
        args.seed, len(good), int(mix["check_requests"]), longest)
    pad_to = mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"]
    worst, worst_control, n_tokens = 0.0, None, 0
    t0 = time.time()
    requests = dict(enumerate(
        generate.client_requests(args.seed, mix, model["vocab_size"])))
    with jax.default_matmul_precision("highest"):
        for i in picked:
            r = good[i]
            prompt = requests[r["client"]][r["k"]]["tokens"]
            gaps, control = reference.served_token_gaps(
                model, params, prompt, r["tokens"], pad_to, args.control)
            worst = max(worst, float(np.max(gaps)))
            n_tokens += len(r["tokens"])
            if control is not None:
                worst_control = max(worst_control or 0.0, float(np.max(control)))
    lib.log(f"[serve] reference: {len(picked)} requests, {n_tokens} served "
            f"tokens, {time.time() - t0:.1f} s")
    checks.at_most("served_token_gap_over_scale", worst,
                   limits["served_token_gap_over_scale"]["limit"])
    if worst_control is not None:
        lib.log("CONTROL " + json.dumps({
            "seed": args.seed, "precision": args.control,
            "control_gap_over_scale": worst_control,
            "program_gap_over_scale": worst, "tokens": n_tokens,
        }))


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
    cfg = lib.llama_config(dict(model, run=run_), args.rehearsal)
    params = jax.block_until_ready(weights.make_params(
        model, args.seed, "float32" if args.rehearsal else "bfloat16"))
    lib.log(f"[serve] t+{time.time() - t_start:.1f}s weights on the device")

    engine = ContinuousBatcher(
        cfg, params, n_slots=run_["n_slots"], max_len=run_["max_len"],
        max_new_tokens=mix["output_tokens"]["max"], chunk=run_["chunk"],
        pad_id=-1, kv_layout=run_["kv_layout"],
    )
    del params
    spies = {}
    for attr, static_numbers in (("_run_chunk", True), ("_paged_cold_fn", False)):
        spies[attr] = ShapeSpy(getattr(engine, attr), static_numbers)
        setattr(engine, attr, spies[attr])
    spans = Spans(engine)
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
        warm_up(gateway.addr,
                generate.warm_requests(args.seed, mix, model["vocab_size"]))
        lib.log(f"[serve] t+{time.time() - t_start:.1f}s warm")
        open_at = time.time() + mix["ramp_s"]
        close_at = open_at + args.seconds
        out_path = os.path.join(work_dir, "load.json")
        # The server comes up with its backlog waiting: the scheduler
        # stands still until every client's first request is queued, and
        # its first step then fills the batch at once. Left running, it
        # holds its lock through every step and lets the first requests
        # in by luck: that first fill took 2 to 10 s from run to run.
        sched.stop()
        load_proc = subprocess.Popen([
            sys.executable, os.path.join(lib.BENCH, "drivers", "loadgen.py"),
            "--addr", gateway.addr, "--traffic", json.dumps(mix),
            "--seed", str(args.seed), "--vocab", str(model["vocab_size"]),
            "--open-at", repr(open_at), "--seconds", str(args.seconds),
            "--out", out_path,
        ])
        wait_for_backlog(sched, mix["clients"], load_proc, mix["ramp_s"] / 2)
        sched.start()
        if args.trace:
            trace_thread = trace_slice(
                open_at, args.seconds, mix["trace_s"],
                os.path.join(work_dir, "trace"), trace_out)
        time.sleep(max(0.0, open_at - time.time()))
        counter.count, counter.counting = 0, True
        time.sleep(max(0.0, close_at - time.time()))
        counter.counting = False
        rc = load_proc.wait(timeout=120)
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
        sched.stop()
        shutil.rmtree(work_dir, ignore_errors=True)
    memory_peak = lib.memory_peak_bytes()

    window = summarize(load, spans, open_at, close_at, run_["n_slots"])
    lib.log("[serve] course " + json.dumps(
        course(load, spans, close_at, run_["n_slots"])))
    if args.dump:
        os.makedirs(args.dump, exist_ok=True)
        with open(os.path.join(args.dump, f"serve_{args.seed}.json"), "w") as f:
            json.dump({"load": load, "steps": spans.steps,
                       "submits": spans.submits}, f)
    lib.log("[serve] " + json.dumps({
        "ended": len(window["ended"]), "good": len(window["good"]),
        "tokens_in_window": window["tokens_in_window"],
        "plain_tokens_per_s": window["tokens_in_window"] / args.seconds,
        "engine_steps": len(window["steps"]),
        "compilations": counter.count,
        "send_gap_ms": load["send_gap_ms"],
        "clients_ran_out": load["clients_ran_out"],
        "least_requests_left": load["least_requests_left"],
        "clients_stuck": load["clients_stuck"],
        "submit_wait_ms": {
            "n": len(window["submit_wait_s"]),
            "median": statistics.median(window["submit_wait_s"] or [0]) * 1e3,
            "max": max(window["submit_wait_s"] or [0]) * 1e3,
        },
        "kernel_path": engine.kernel_path,
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
            "paged_kernel_in_chunk_programs",
            chunk and all(PAGED_KERNEL in k for k in chunk), str(chunk))
        # the program's own gate (flash_attention.supports) sends a
        # prompt bucket under 128 tokens to the XLA reference path
        prefill = [k for _, k in held["_paged_cold_fn"]]
        checks.require(
            "flash_kernel_in_prefill_programs",
            sum(FLASH_KERNEL in k for k in prefill) >= len(prefill) - 1 > 0,
            str(held["_paged_cold_fn"]))
    # free the engine; only then the reference
    del spies, spans.steps, sched, gateway, engine
    gc.collect()
    if window["good"]:
        check_served(args, model, mix, window["good"], limits, checks)

    failed = len(window["ended"]) - len(window["good"])
    out = {
        "correct": checks.ok and failed == 0, "checks": checks.compared,
        "attempted": len(window["ended"]), "failed": failed,
        "device": dict(device, memory_peak_bytes=memory_peak),
    }
    run_view = {
        "cell": dict(cell, model=model, mix=mix), "window": window,
        "trace": trace, "rehearsal": args.rehearsal,
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
            # the 95th percentile of all of them, linear between ranks
            "tpot_p95_ms": statistics.quantiles(
                window["tpot_ms"], n=20, method="inclusive")[18],
        })
    return out
