"""chip_smoke.py — the quickest proof that the system still starts on
the chip: both main paths, through the entry points a user calls, at
Llama-2-7B widths (dim 4096, 32 heads x 128, mlp 11008, vocab 32000,
seq 2048), on ONE TPU chip. Depth is the only cut; weights are random,
made from --seed.

  python chip_smoke.py                  one TPU chip (what the driver runs)
  python chip_smoke.py --chips 4        the sharded paths, and only them
  python chip_smoke.py --cpu-rehearsal  tiny shapes on the CPU; finds wrong
                                        paths and arguments, proves nothing
                                        about the chip and says so

Phases, in this order, because a chip belongs to one process at a time:

  train  children hold the chip (this process has not touched jax yet):
         `python -m dlrover_tpu.trainer.elastic_run` starts the local
         master and the agent; the agent supervises a worker (this
         file, --role train-worker) that trains with accelerate() +
         flash attention, stages a flash checkpoint to the agent's shm,
         and SIGKILLs itself mid-run; the agent respawns it, the new
         worker restores from shm and goes on.
  serve  in this process, only after every child above has exited: a
         ServingGateway over one ContinuousBatcher replica answers
         POST /v1/generate under dense, paged (bf16 and int8 KV) and
         int8-weight settings; each continuation is checked against
         models/decode.generate on the same weights.

Any phase that fails makes the exit code non-zero and the last line
`"ok": false`. A platform other than `tpu` is a failure, not a smaller
run. The LAST line of stdout is one JSON object,
`{"ok": ..., "device": {"platform": ..., "kind": ..., "count": ...}}`,
with the device as jax reports it in a process that held the chip.
"""

import argparse
import json
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import threading
import time
import traceback

REPO = os.path.dirname(os.path.abspath(__file__))
EVENT = "SMOKE "  # prefix of the machine-read lines a worker prints

# ---- sizes ---------------------------------------------------------------
# Depth per phase comes from the v5e compiler's memory_analysis() of the
# real step program (rehearsal 3, a described chip): at 7B widths, f32
# params + AdamW moments, one train step at batch 2 x 2048 needs
# 6.5 GiB at 1 layer and 11.9 GiB at 2 layers (7.45 GiB of it state);
# 3 layers cannot fit 16 GB. Serving holds bf16 weights (0.38 GiB a
# layer + 0.49 GiB embedding and head), so it affords "a few more".
REAL = dict(
    train_layers=2, train_batch=2, seq=2048,
    serve_layers=4, slots=4, max_len=1152, max_new=24,
    prompt_lens=(384, 384, 1000, 1000), chunk=8,
)
TINY = dict(
    train_layers=2, train_batch=2, seq=64,
    serve_layers=2, slots=4, max_len=96, max_new=8,
    prompt_lens=(20, 20, 45, 45), chunk=4,
)
TRAIN_STEPS, SAVE_STEP, KILL_AFTER_STEP = 6, 3, 4

# Greedy tokens from two programs that reduce in different orders (a
# Pallas kernel and its XLA reference; a flash prefill over a padded
# bucket and one over the exact prompt) may part ways where the
# reference itself sees a near-tie. The logits leave the unembed as
# bf16 values — 8 bits of mantissa, so neighbours sit 2^-7 to 2^-8 of
# the logit scale apart and the top candidates often tie exactly. A
# disagreement is accepted only where the reference's own gap between
# its choice and the server's is within 2^-5 of that scale (about
# four bf16 steps; the first chip run's worst was 2^-6). A wrong
# program picks tokens whose gap is of the order of the scale itself.
TIE_TOLERANCE = 2.0 ** -5


def log(msg: str) -> None:
    print(msg, flush=True)


def emit(**event) -> None:
    print(EVENT + json.dumps(event), flush=True)


def model_cfg(rehearsal: bool, n_layers: int, **kw):
    """Llama-2-7B widths with `n_layers` layers (the rehearsal: the
    repo's tiny config — control flow only)."""
    import dataclasses

    from dlrover_tpu.models import llama

    if rehearsal:
        return dataclasses.replace(
            llama.LlamaConfig.tiny(), n_layers=n_layers,
            attn_impl="auto", **kw,
        )
    return llama.LlamaConfig.llama2_7b(
        n_layers=n_layers, attn_impl="auto", **kw
    )


FOUND = {"device": None}  # what the last line reports, pass or fail


def require_platform(rehearsal: bool, chips: int) -> dict:
    """The device as jax reports it — and the rule that anything but
    the asked-for TPU is a failure (the rehearsal: anything but cpu)."""
    import jax

    devs = jax.devices()
    device = {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }
    FOUND["device"] = device
    emit(event="device", device=device)
    want = "cpu" if rehearsal else "tpu"
    if device["platform"] != want:
        raise RuntimeError(
            f"needs platform {want!r}, jax found {device['platform']!r} "
            f"({device['kind']})"
        )
    if not rehearsal and device["count"] != chips:
        raise RuntimeError(
            f"needs {chips} chip(s), jax found {device['count']}"
        )
    return device


def serving_cfg(rehearsal: bool, n_layers: int):
    """The served model: bf16 weights at 7B widths (the rehearsal: the
    tiny config in f32, where greedy tokens are exact)."""
    import jax.numpy as jnp

    if rehearsal:
        return model_cfg(
            True, n_layers, param_dtype=jnp.float32, dtype=jnp.float32
        )
    return model_cfg(False, n_layers, param_dtype=jnp.bfloat16)


def kernel_names(compiled_text: str) -> list:
    """Names of the Pallas kernels inside a compiled program (the
    instructions whose target is `tpu_custom_call`)."""
    names = set()
    for line in compiled_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        head = line.strip().split(" = ", 1)[0]
        head = head.replace("ROOT ", "").lstrip("%")
        names.add(head.rsplit(".", 1)[0] if "." in head else head)
    return sorted(names)


# ---------------------------------------------------------------------------
# train phase — the worker (a child of the agent; holds the chip)
# ---------------------------------------------------------------------------


def build_trainer(cfg, n_devices: int, mesh_spec=None):
    import optax

    from dlrover_tpu.models import llama
    from dlrover_tpu.parallel.accelerate import Strategy, accelerate
    from dlrover_tpu.parallel.mesh import MeshSpec

    import jax

    return accelerate(
        init_params=lambda k: llama.init_params(cfg, k),
        loss_fn=lambda p, b, m: llama.loss_fn(cfg, p, b, mesh=m),
        rules=llama.partition_rules(cfg),
        optimizer=optax.adamw(3e-4),
        strategy=Strategy(mesh=mesh_spec or MeshSpec.fit(n_devices)),
        devices=jax.devices()[:n_devices],
    )


def train_batch(acc, cfg, batch: int, seq: int, seed: int):
    import jax

    tokens = jax.random.randint(
        jax.random.PRNGKey(seed + 1), (batch, seq + 1), 0, cfg.vocab_size
    )
    return acc.shard_batch({"tokens": tokens})


def train_worker(args) -> int:
    """One incarnation of the supervised trainer. The first one
    compiles, trains, checkpoints to shm and is SIGKILLed; the second
    (RESTART_COUNT=1) restores and finishes."""
    import dlrover_tpu
    from dlrover_tpu.common.constants import NodeEnv
    from dlrover_tpu.trainer.flash_checkpoint.engine import (
        Checkpointer,
        StorageType,
    )

    t_start = time.time()
    restart = int(os.environ.get(NodeEnv.RESTART_COUNT, "0"))
    dlrover_tpu.init()  # rendezvous world + persistent compile cache
    import jax

    size = TINY if args.cpu_rehearsal else REAL
    device = require_platform(args.cpu_rehearsal, chips=1)
    cfg = model_cfg(args.cpu_rehearsal, size["train_layers"])
    acc = build_trainer(cfg, n_devices=1)
    batch = train_batch(
        acc, cfg, size["train_batch"], size["seq"], args.seed
    )

    ckpt = Checkpointer(args.ckpt_dir)
    # restore onto the shardings, never onto a live state: a second
    # copy of a 7 GiB state does not fit beside the first
    t0 = time.time()
    step0, state = ckpt.load_checkpoint(target=acc.state_shardings)
    if state is None:
        step0, state = 0, acc.init(jax.random.PRNGKey(args.seed))
    jax.block_until_ready(state)
    emit(
        event="worker_up", restart=restart, device=device,
        resumed_step=step0, state_step=int(state["step"]),
        state_ready_s=round(time.time() - t0, 2),
        layers=cfg.n_layers, batch=size["train_batch"], seq=size["seq"],
        params_m=round(
            sum(x.size for x in jax.tree_util.tree_leaves(state["params"]))
            / 1e6, 1,
        ),
        cache_dir=jax.config.jax_compilation_cache_dir,
        shm_free_gib=round(shutil.disk_usage("/dev/shm").free / 2 ** 30, 1),
    )

    # compile once ahead of time: the seconds are the compile alone
    # (cold in the first worker, read back from the persistent cache
    # in the respawned one), and the text says which attention the
    # step took. The jit call below finds the same cache entry.
    t0 = time.time()
    compiled = acc.train_step.lower(state, batch).compile()
    compile_s = time.time() - t0
    kernels = kernel_names(compiled.as_text())
    emit(
        event="compiled", restart=restart,
        compile_s=round(compile_s, 2), kernels=kernels,
    )
    del compiled

    for step in range(step0 + 1, TRAIN_STEPS + 1):
        t0 = time.time()
        state, metrics = acc.train_step(state, batch)
        loss = float(metrics["loss"])  # waits for the device
        emit(
            event="step", restart=restart, step=step, loss=loss,
            step_s=round(time.time() - t0, 3), wall=time.time(),
            since_start_s=round(time.time() - t_start, 2),
        )
        if step == SAVE_STEP and restart == 0:
            blocked = ckpt.save_checkpoint(step, state, StorageType.MEMORY)
            emit(
                event="saved", step=step,
                save_blocking_ms=round(blocked * 1e3, 1),
                state_gib=round(
                    sum(
                        x.nbytes
                        for x in jax.tree_util.tree_leaves(state)
                    ) / 2 ** 30, 2,
                ),
            )
        if step == KILL_AFTER_STEP and restart == 0:
            emit(event="sigkill", step=step, wall=time.time())
            os.kill(os.getpid(), signal.SIGKILL)
    ckpt.close()
    emit(event="worker_done", restart=restart)
    return 0


# ---------------------------------------------------------------------------
# train phase — the parent side (never touches jax)
# ---------------------------------------------------------------------------


def run_train_phase(args, workdir: str) -> dict:
    ckpt_dir = os.path.join(workdir, "ckpt")
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    sock_dir = os.path.join(workdir, "sock")
    if len(sock_dir) < 70:  # a unix socket path holds ~100 bytes
        env["DLROVER_TPU_SOCK_DIR"] = sock_dir
    job = f"chipsmoke{os.getpid()}"
    cmd = [
        sys.executable, "-m", "dlrover_tpu.trainer.elastic_run",
        "--nnodes=1", "--max-restarts=1", f"--job-name={job}",
        os.path.abspath(__file__), "--role", "train-worker",
        "--ckpt-dir", ckpt_dir, "--seed", str(args.seed),
    ]
    if args.cpu_rehearsal:
        cmd.append("--cpu-rehearsal")
        env["DLROVER_TPU_FORCE_CPU"] = "1"
        env["JAX_PLATFORMS"] = "cpu"
    log("[train] " + " ".join(cmd[1:]))
    events = []
    # its own session: whatever the launcher starts (master threads,
    # agent, saver, workers) can be stopped as one group afterwards
    proc = subprocess.Popen(
        cmd, cwd=REPO, env=env, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True, start_new_session=True,
    )
    def _kill_group():
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            pass

    watchdog = threading.Timer(args.train_timeout, _kill_group)
    watchdog.daemon = True
    watchdog.start()
    try:
        for line in proc.stdout:
            line = line.rstrip("\n")
            if line.startswith(EVENT):
                events.append(json.loads(line[len(EVENT):]))
                log("[train] " + line[len(EVENT):])
            elif any(
                key in line
                for key in ("Error", "error", "Traceback", "restart",
                            "exited", "WARNING", "  File ")
            ):
                log("[train:log] " + line[:300])
        rc = proc.wait(timeout=60)
    finally:
        watchdog.cancel()
        _kill_group()  # nothing the launcher started outlives the phase
        proc.wait()
        remove_job_files(job)
    for e in events:
        if e["event"] == "device":
            FOUND["device"] = e["device"]
    if rc != 0:
        raise RuntimeError(
            f"elastic_run exited with code {rc} "
            f"(-9 after {args.train_timeout}s means the phase timed out)"
        )
    return check_train_events(events, args.cpu_rehearsal)


def remove_job_files(job: str) -> None:
    """The agent leaves its shm checkpoint segment (the whole train
    state — GiBs at real size) and its IPC socket behind on purpose,
    for a later incarnation to find; the smoke is done with them."""
    import glob

    from dlrover_tpu.common.multi_process import SHM_DIR, SOCKET_DIR

    leftovers = glob.glob(os.path.join(SHM_DIR, f"dlrover_tpu_ckpt_{job}_*"))
    leftovers += glob.glob(os.path.join(SOCKET_DIR, f"{job}.sock*"))
    for path in leftovers:
        try:
            os.remove(path)
        except OSError:
            pass


def check_train_events(events, rehearsal: bool) -> dict:
    def of(kind, restart=None):
        return [
            e for e in events
            if e["event"] == kind
            and (restart is None or e.get("restart") == restart)
        ]

    ups = of("worker_up")
    if [u["restart"] for u in ups] != [0, 1]:
        raise RuntimeError(
            f"expected two worker incarnations (0 then 1), saw "
            f"{[u['restart'] for u in ups]}"
        )
    first, second = ups
    if first["resumed_step"] != 0:
        raise RuntimeError("the first worker found a checkpoint")
    (saved,) = of("saved")
    (killed,) = of("sigkill")
    if second["resumed_step"] != saved["step"] or (
        second["state_step"] != saved["step"]
    ):
        raise RuntimeError(
            f"resume did not continue from the save: saved step "
            f"{saved['step']}, restored {second['resumed_step']} with "
            f"state.step {second['state_step']}"
        )
    before = {e["step"]: e["loss"] for e in of("step", 0)}
    after = {e["step"]: e["loss"] for e in of("step", 1)}
    losses = list(before.values()) + list(after.values())
    if not all(l == l and abs(l) < 1e4 for l in losses):
        raise RuntimeError(f"non-finite loss: {losses}")
    if sorted(after) != list(range(saved["step"] + 1, TRAIN_STEPS + 1)):
        raise RuntimeError(f"resumed worker took steps {sorted(after)}")
    if not before[1] > before[SAVE_STEP] > after[TRAIN_STEPS]:
        raise RuntimeError(
            f"loss is not falling: {before} then {after}"
        )
    # the step after the save ran twice — once before the kill, once
    # from the restored state, same batch: the losses must agree
    redo = saved["step"] + 1
    if abs(after[redo] - before[redo]) > 1e-3 * abs(before[redo]):
        raise RuntimeError(
            f"step {redo} from the restored state gives loss "
            f"{after[redo]}, the killed worker saw {before[redo]}"
        )
    cold, warm = of("compiled", 0)[0], of("compiled", 1)[0]
    if not rehearsal:
        want = {"flash_attention_fwd", "flash_attention_bwd_dq",
                "flash_attention_bwd_dkv"}
        if not want <= set(cold["kernels"]):
            raise RuntimeError(
                f"attn_impl='auto' did not take the flash kernel fwd "
                f"and bwd: the step holds {cold['kernels']}"
            )
        if warm["compile_s"] > 0.5 * cold["compile_s"]:
            raise RuntimeError(
                f"the respawned worker compiled for "
                f"{warm['compile_s']}s, its predecessor for "
                f"{cold['compile_s']}s: the cache did not hit"
            )
    first_step_after = of("step", 1)[0]
    if not of("worker_done", 1):
        raise RuntimeError("the resumed worker did not finish")
    out = {
        "device": first["device"],
        "layers": first["layers"], "batch": first["batch"],
        "seq": first["seq"], "params_m": first["params_m"],
        "kernels": cold["kernels"],
        "losses_before_kill": before, "losses_after_resume": after,
        "save_blocking_ms": saved["save_blocking_ms"],
        "state_gib": saved["state_gib"],
        "compile_s_cold": cold["compile_s"],
        "compile_s_warm_cache": warm["compile_s"],
        "restore_s": second["state_ready_s"],
        "kill_to_first_step_s": round(
            first_step_after["wall"] - killed["wall"], 2
        ),
        "cache_dir": first["cache_dir"],
    }
    log("[train] ok " + json.dumps(out))
    return out


# ---------------------------------------------------------------------------
# serve phase — in this process
# ---------------------------------------------------------------------------


class ProgramSpy:
    """Wraps one of an engine's jitted programs: before its first
    real call, compiles it ahead of time with the very same arguments
    and records the seconds and the Pallas kernels in the compiled
    text. The jit call that follows finds the executable in the
    compile cache. A quiet reference path cannot pass for a kernel:
    what is recorded is the program the server ran."""

    def __init__(self, fn, name: str, record: dict):
        self.fn, self.name, self.record = fn, name, record
        self.seen = set()

    def __call__(self, *a, **kw):
        import jax

        shapes = str(jax.tree_util.tree_map(
            lambda x: getattr(x, "shape", x), (a, kw)
        ))
        if shapes not in self.seen:  # a new specialization compiles
            self.seen.add(shapes)
            t0 = time.time()
            text = self.fn.lower(*a, **kw).compile().as_text()
            self.record.setdefault(self.name, []).append({
                "compile_s": round(time.time() - t0, 2),
                "kernels": kernel_names(text),
            })
        return self.fn(*a, **kw)

    def __getattr__(self, item):
        return getattr(self.fn, item)


ENGINE_PROGRAMS = (
    "_run_chunk", "_admit_fn", "_admit_cold_fn", "_admit_warm_fn",
    "_paged_cold_fn", "_paged_warm_fn",
)


def post_generate(addr: str, tokens, max_new: int, timeout: float):
    import urllib.request

    body = json.dumps(
        {"tokens": tokens, "max_new": max_new, "stream": False,
         "deadline_s": timeout}
    ).encode()
    req = urllib.request.Request(
        addr + "/v1/generate", data=body,
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read())


def serve_setting(name, cfg, params, prompts, size, engine_kw, timeout):
    """One engine setting behind the HTTP gateway: answer every
    prompt, return the continuations and what the programs held."""
    from dlrover_tpu.serving.engine import ContinuousBatcher
    from dlrover_tpu.serving.gateway import ServingGateway
    from dlrover_tpu.serving.scheduler import RequestScheduler, SloConfig

    t0 = time.time()
    engine = ContinuousBatcher(
        cfg, params, n_slots=size["slots"], max_len=size["max_len"],
        max_new_tokens=size["max_new"], chunk=size["chunk"], pad_id=-1,
        **engine_kw,
    )
    build_s = time.time() - t0
    programs = {}
    for attr in ENGINE_PROGRAMS:
        fn = getattr(engine, attr, None)
        if fn is not None:
            setattr(engine, attr, ProgramSpy(fn, attr, programs))
    sched = RequestScheduler(
        engine,
        slo=SloConfig(
            max_new_tokens=size["max_new"], default_deadline_s=timeout
        ),
    )
    gateway = ServingGateway(sched, stream_timeout_s=timeout)
    sched.start()
    gateway.start()
    try:
        t1 = time.time()
        first = post_generate(
            gateway.addr, prompts[0], size["max_new"], timeout
        )
        first_s = time.time() - t1
        t1 = time.time()
        rest = [
            post_generate(gateway.addr, p, size["max_new"], timeout)
            for p in prompts[1:]
        ]
        rest_s = time.time() - t1
    finally:
        gateway.stop()
        sched.stop()
    answers = [first] + rest
    for a in answers:
        if a.get("state") != "done" or len(a["tokens"]) != size["max_new"]:
            raise RuntimeError(f"[{name}] bad answer: {a}")
    report = {
        "setting": name,
        "kernel_path": engine.kernel_path,
        "weight_quant_path": engine.weight_quant_path,
        "programs": programs,
        "engine_build_s": round(build_s, 2),
        "first_request_s": round(first_s, 2),
        "other_requests_s": round(rest_s, 2),
        "tokens_answered": sum(len(a["tokens"]) for a in answers),
    }
    served_params = engine.params
    del engine, sched, gateway
    return [a["tokens"] for a in answers], report, served_params


def dequantized(params):
    """The dense weights an int8-quantized tree stands for, in the
    model's own [K, O] layout — 'the same weights' for the XLA
    reference, without the kernel under test."""
    import jax
    import jax.numpy as jnp

    from dlrover_tpu.ops.quantization import QuantizedWeight

    def one(w):
        if not isinstance(w, QuantizedWeight):
            return w
        s = jnp.repeat(w.s8, w.block, axis=-1)
        return jnp.swapaxes(w.q8.astype(jnp.float32) * s, -1, -2)

    return jax.tree_util.tree_map(
        one, params, is_leaf=lambda x: isinstance(x, QuantizedWeight)
    )


def reference_tokens(cfg, params, prompts, max_new, kv_quant):
    """decode.generate, greedy, one call per prompt length."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dlrover_tpu.models import decode

    gen = jax.jit(
        lambda p, toks: decode.generate(
            cfg, p, toks, max_new, kv_quant=kv_quant
        )
    )
    out = [None] * len(prompts)
    for n in sorted({len(p) for p in prompts}):
        idx = [i for i, p in enumerate(prompts) if len(p) == n]
        toks = jnp.asarray([prompts[i] for i in idx], jnp.int32)
        full = np.asarray(gen(params, toks))
        for row, i in enumerate(idx):
            out[i] = full[row, n:].tolist()
    return out


def near_tie_report(cfg, params, prompt, served, kv_quant):
    """The reference's view of a continuation that differs from its
    own: generate()'s computation (prefill, then decode_step per
    token) fed the SERVER's tokens. Returns, per position, the gap
    between the reference's best logit and the served token's, over
    the logit scale. All gaps zero <=> generate() emits `served`."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dlrover_tpu.models import decode

    def run(p, prompt, served):
        n = served.shape[1]
        plen = prompt.shape[1]
        cache = decode.init_kv_cache(cfg, 1, plen + n, quant=kv_quant)
        logits, cache = decode.prefill(cfg, p, prompt, cache)

        def step(carry, t):
            logits, cache = carry
            tok = served[:, t]
            gap = logits.max(-1) - jnp.take_along_axis(
                logits, tok[:, None], axis=1
            )[:, 0]
            scale = jnp.abs(logits).max(-1)
            nxt, cache = decode.decode_step(cfg, p, tok, cache, plen + t)
            return (nxt, cache), gap / scale

        _, gaps = jax.lax.scan(step, (logits, cache), jnp.arange(n))
        return gaps[:, 0]

    gaps = jax.jit(run)(
        params,
        jnp.asarray([prompt], jnp.int32),
        jnp.asarray([served], jnp.int32),
    )
    return np.asarray(gaps, np.float64)


def compare(name, cfg, ref_params, prompts, served, max_new, kv_quant,
            always_score):
    """Token equality with decode.generate, or — where they differ —
    every served token within TIE_TOLERANCE of the reference's best."""
    ref = reference_tokens(cfg, ref_params, prompts, max_new, kv_quant)
    exact = sum(r == s for r, s in zip(ref, served))
    worst = 0.0
    for i, (r, s) in enumerate(zip(ref, served)):
        if r == s and not always_score:
            continue
        gaps = near_tie_report(cfg, ref_params, prompts[i], s, kv_quant)
        worst = max(worst, float(gaps.max()))
        if r == s and gaps.max() != 0.0:
            raise RuntimeError(
                f"[{name}] scoring disagrees with generate on prompt {i}"
            )
        if gaps.max() > TIE_TOLERANCE:
            first = next(j for j, (a, b) in enumerate(zip(r, s)) if a != b)
            raise RuntimeError(
                f"[{name}] prompt {i} (len {len(prompts[i])}) leaves "
                f"decode.generate at token {first}: served {s}, "
                f"reference {r}, worst gap {gaps.max():.4f} of the "
                f"logit scale (tolerance {TIE_TOLERANCE})"
            )
    return {
        "prompts": len(prompts), "token_equal": exact,
        "near_tie_accepted": len(prompts) - exact,
        "worst_gap_over_scale": round(worst, 6),
    }


SETTINGS = (
    # name, engine kwargs, reference kv_quant, kernels each program
    # of the setting must hold on the chip
    ("dense", dict(kv_layout="dense"), False,
     {"prefill": "flash_attention_fwd"}),
    ("paged-bf16", dict(kv_layout="paged"), False,
     {"prefill": "flash_attention_fwd",
      "_run_chunk": "paged_attention_decode"}),
    ("paged-int8kv", dict(kv_layout="paged", kv_quant=True), True,
     {"prefill": "flash_attention_fwd",
      "_run_chunk": "paged_attention_decode"}),
    ("int8-weights", dict(kv_layout="dense", weight_quant="int8"), False,
     {"prefill": "int8_dequant_matmul",
      "_run_chunk": "int8_dequant_matmul"}),
)


def check_programs(name, report, want, rehearsal):
    if rehearsal:
        return
    progs = report["programs"]
    for prog, kernel in want.items():
        held = [
            info["kernels"]
            for p, infos in progs.items() for info in infos
            if p == prog or (prog == "prefill" and p != "_run_chunk")
        ]
        if not held or not all(kernel in k for k in held):
            raise RuntimeError(
                f"[{name}] a {prog} program lacks the {kernel} kernel: "
                f"{progs}"
            )
    if "paged" in name and report["kernel_path"] != "kernel":
        raise RuntimeError(f"[{name}] kernel_path {report['kernel_path']}")
    if name == "int8-weights" and (
        report["weight_quant_path"] != "int8:kernel"
    ):
        raise RuntimeError(
            f"[{name}] weight_quant_path {report['weight_quant_path']}"
        )


def make_prompts(size, vocab: int, seed: int):
    import numpy as np

    rng = np.random.default_rng(seed)
    return [
        rng.integers(1, vocab, size=n).tolist()
        for n in size["prompt_lens"]
    ]


def serving_weights(cfg, seed: int):
    import jax

    from dlrover_tpu.models import llama

    params = jax.jit(lambda k: llama.init_params(cfg, k))(
        jax.random.PRNGKey(seed)
    )
    return jax.block_until_ready(params)


def run_serve_phase(args) -> dict:
    import jax

    from dlrover_tpu.runtime import enable_compile_cache

    device = require_platform(args.cpu_rehearsal, chips=1)
    cache_dir = enable_compile_cache()
    size = TINY if args.cpu_rehearsal else REAL
    cfg = serving_cfg(args.cpu_rehearsal, size["serve_layers"])
    params = serving_weights(cfg, args.seed)
    prompts = make_prompts(size, cfg.vocab_size, args.seed)
    log(
        f"[serve] {device} layers={cfg.n_layers} dim={cfg.dim} "
        f"heads={cfg.n_heads} vocab={cfg.vocab_size} prompts="
        f"{[len(p) for p in prompts]} max_new={size['max_new']} "
        f"cache_dir={cache_dir}"
    )
    reports = []
    for name, engine_kw, kv_quant, want in SETTINGS:
        served, report, served_params = serve_setting(
            name, cfg, params, prompts, size, engine_kw,
            args.request_timeout,
        )
        check_programs(name, report, want, args.cpu_rehearsal)
        ref_params = (
            dequantized(served_params)
            if "weight_quant" in engine_kw else params
        )
        report["vs_generate"] = compare(
            name, cfg, ref_params, prompts, served, size["max_new"],
            kv_quant, always_score=args.cpu_rehearsal,
        )
        del served_params, ref_params
        log("[serve] ok " + json.dumps(report))
        reports.append(report)
    return {"device": device, "settings": reports}


# ---------------------------------------------------------------------------
# --chips 4: the sharded paths and what they are compared with, only
# ---------------------------------------------------------------------------


def bytes_per_device(tree) -> dict:
    import jax

    out = {}
    for leaf in jax.tree_util.tree_leaves(tree):
        for shard in leaf.addressable_shards:
            out[shard.device.id] = (
                out.get(shard.device.id, 0) + shard.data.nbytes
            )
    return dict(sorted(out.items()))


def run_four_chip_phase(args) -> dict:
    import gc

    import jax

    from dlrover_tpu.parallel.mesh import MeshSpec
    from dlrover_tpu.runtime import enable_compile_cache

    if args.cpu_rehearsal:
        # four virtual devices; must be set before the backend starts
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=4"
        ).strip()
    device = require_platform(args.cpu_rehearsal, chips=4)
    enable_compile_cache()
    size = TINY if args.cpu_rehearsal else REAL
    out = {"device": device}

    # -- one sharded train step (fsdp x tensor) vs the same on one chip
    cfg = model_cfg(args.cpu_rehearsal, size["train_layers"])
    losses, spread = {}, {}
    for label, n, spec in (
        ("one-chip", 1, None),
        ("fsdp2xtensor2", 4, MeshSpec(fsdp=2, tensor=2)),
    ):
        acc = build_trainer(cfg, n, spec)
        state = acc.init(jax.random.PRNGKey(args.seed))
        batch = train_batch(
            acc, cfg, size["train_batch"], size["seq"], args.seed
        )
        spread[label] = bytes_per_device(state)
        text = acc.train_step.lower(state, batch).compile().as_text()
        got = []
        for _ in range(2):
            state, metrics = acc.train_step(state, batch)
            got.append(float(metrics["loss"]))
        losses[label] = got
        log(
            f"[4chip:train] {label} losses={got} state bytes/device="
            f"{spread[label]} kernels={kernel_names(text)} "
            f"collectives: all-reduce={text.count('all-reduce(')} "
            f"all-gather={text.count('all-gather(')} "
            f"reduce-scatter={text.count('reduce-scatter(')}"
        )
        del acc, state, batch, metrics
        gc.collect()
    tol = 5e-3
    for a, b in zip(losses["one-chip"], losses["fsdp2xtensor2"]):
        if not abs(a - b) <= tol * abs(a):
            raise RuntimeError(
                f"sharded step loss {b} vs one-chip {a}: beyond "
                f"{tol} relative"
            )
    per_dev = spread["fsdp2xtensor2"]
    total = sum(per_dev.values())
    if len(per_dev) != 4 or max(per_dev.values()) > 0.30 * total:
        raise RuntimeError(f"state is not spread over four chips: {per_dev}")
    out["train"] = {
        "losses": losses, "tolerance_rel": tol,
        "state_bytes_per_device": spread,
    }

    # -- a tp=2 paged replica vs tp=1, same prompts, through the gateway
    scfg = serving_cfg(args.cpu_rehearsal, size["serve_layers"])
    params = serving_weights(scfg, args.seed)
    prompts = make_prompts(size, scfg.vocab_size, args.seed)
    served = {}
    for tp in (1, 2):
        name = f"paged-tp{tp}"
        kw = dict(kv_layout="paged")
        if tp > 1:
            kw["mesh_spec"] = tp
        served[tp], report, _ = serve_setting(
            name, scfg, params, prompts, size, kw, args.request_timeout
        )
        check_programs(
            name, report,
            {"prefill": "flash_attention_fwd",
             "_run_chunk": "paged_attention_decode"},
            args.cpu_rehearsal,
        )
        log(f"[4chip:serve] {name} " + json.dumps(report))
        gc.collect()
    equal = sum(a == b for a, b in zip(served[1], served[2]))
    out["serve"] = {"tp2_equals_tp1": equal, "prompts": len(prompts)}
    # where tp=2 parts from tp=1, both must still be continuations the
    # one-chip XLA reference accepts
    for tp in (1, 2):
        out["serve"][f"tp{tp}_vs_generate"] = compare(
            f"paged-tp{tp}", scfg, params, prompts, served[tp],
            size["max_new"], False, always_score=False,
        )
    log("[4chip] ok " + json.dumps(out))
    return out


# ---------------------------------------------------------------------------


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--cpu-rehearsal", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--train-timeout", type=float, default=800.0)
    ap.add_argument("--request-timeout", type=float, default=600.0)
    ap.add_argument("--role", choices=("train-worker",), default=None)
    ap.add_argument("--ckpt-dir", default=None)
    args = ap.parse_args()

    if args.role == "train-worker":
        if args.cpu_rehearsal:
            from dlrover_tpu.utils.platform import ensure_cpu_if_forced

            ensure_cpu_if_forced()
        return train_worker(args)

    if args.cpu_rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
    t0 = time.time()
    ok = False
    workdir = tempfile.mkdtemp(prefix="chip_smoke_")
    try:
        if args.chips == 4:
            run_four_chip_phase(args)
        else:
            train = run_train_phase(args, workdir)
            serve = run_serve_phase(args)
            if train["device"] != serve["device"]:
                raise RuntimeError(
                    f"trainer saw {train['device']}, "
                    f"server {serve['device']}"
                )
        ok = True
    except BaseException:  # noqa: BLE001 — reported, then exit != 0
        traceback.print_exc()
        sys.stderr.flush()
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    log(f"[smoke] {'passed' if ok else 'FAILED'} in {time.time() - t0:.0f}s")
    print(json.dumps({"ok": ok, "device": FOUND["device"]}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
