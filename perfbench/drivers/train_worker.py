"""The training script the elastic agent supervises in both training
cells (a copy of chip_smoke.py's train_worker, with the benchmark's
widths, loop and output). It holds the chip; its parent
(drivers/train.py under run.py) never imports jax and reads the event
lines this prints.

Incarnation 0 (RESTART_COUNT=0):
  set-up   build the step with its state from --seed, take the job's
           first three steps through the window's own call and feed,
           and read from them what `correct` compares with the plain
           reference (each loss; the first gradient's norm per leaf,
           from Adam's first moment; the norm per leaf of the
           parameters' change);
  window   steps until --seconds have passed. Where the traffic file
           saves every N steps, the window is whole cycles of N steps
           and the flash checkpoint to the agent's shared memory that
           follows them;
  kill     where the traffic file says so: `kill_after_steps` more
           steps, then SIGKILL of this process.
Incarnation 1 (the agent's respawn): restore from shared memory, one
step, whose loss the killed worker has printed before.

The last incarnation then frees the program's state and runs
perfbench/reference.py for three steps on the same seed, outside
every timed span and after the memory peak is read.
"""

import argparse
import gc
import math
import os
import shutil
import signal
import sys
import time

T_PROCESS = time.time()
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.dirname(BENCH))

import generate  # noqa: E402
import lib  # noqa: E402
import weights  # noqa: E402

REFERENCE_STEPS = 3
TRACE_AFTER_STEPS = 4  # untraced steps of a steady window before the traced ones
ADAM_B1 = 0.9


class Feed:
    """`batch(seed, step)`, made on the host and put on the device one
    step ahead of the step that takes it."""

    def __init__(self, acc, seed, rows, seq, vocab, first_step):
        self.acc, self.seed = acc, seed
        self.rows, self.seq, self.vocab = rows, seq, vocab
        self.step = first_step
        self.ready = self._put(first_step)

    def _put(self, step):
        tokens = generate.batch(self.seed, step, self.rows, self.seq, self.vocab)
        return self.acc.shard_batch({"tokens": tokens})

    def take(self, step):
        if step != self.step:
            raise RuntimeError(f"feed is at step {self.step}, asked {step}")
        batch, self.step = self.ready, step + 1
        self.ready = self._put(step + 1)
        return batch


class Trainer:
    """The one object set-up builds and the window drives: the
    compiled step, its state, its feed."""

    def __init__(self, acc, state, feed, step):
        import jax

        self.jax = jax
        self.acc, self.state, self.feed, self.step = acc, state, feed, step
        self.records = []  # (step, loss, seconds, wall at the end)

    def take_step(self):
        jax = self.jax
        t0 = time.time()
        with jax.profiler.TraceAnnotation("perfbench:feed"):
            batch = self.feed.take(self.step + 1)
        with jax.profiler.TraceAnnotation("perfbench:train_step"):
            self.state, metrics = self.acc.train_step(self.state, batch)
        with jax.profiler.TraceAnnotation("perfbench:loss_fetch"):
            loss = float(metrics["loss"])  # waits for the device
        t1 = time.time()
        self.step += 1
        self.records.append((self.step, loss, t1 - t0, t1))
        return loss


def leaf_norms_program(tree, scale=1.0):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def norms(t):
        return {
            f"{g}/{n}": jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            * scale
            for g, leaves in t.items() for n, x in leaves.items()
        }

    return {k: float(v) for k, v in jax.device_get(norms(tree)).items()}


def first_moment(opt_state):
    """Adam's first moment inside an optax state, wherever it sits."""
    for part in opt_state:
        if hasattr(part, "mu"):
            return part.mu
    raise RuntimeError("no Adam moment in the optimizer state")


def first_steps(trainer, model, seed) -> dict:
    """The job's first steps, through the window's own call and feed,
    and what `correct` reads from them: each loss, the first
    gradient's norm per leaf as the optimizer got it (Adam's first
    moment after one step is (1 - b1) times it), and the norm per
    leaf of the parameters' change over the steps."""
    import reference

    losses = [trainer.take_step()]
    first_step_wall = time.time()
    grad_norms = leaf_norms_program(
        first_moment(trainer.state["opt_state"]), 1.0 / (1.0 - ADAM_B1)
    )
    losses += [trainer.take_step() for _ in range(REFERENCE_STEPS - 1)]
    change = reference.param_change_norms(
        trainer.state["params"], model, seed)
    return {"event": "first_steps", "losses": losses,
            "grad_norms": grad_norms, "change_norms": change,
            "first_step_wall": first_step_wall, "wall": time.time()}


def build(args, model):
    import jax
    import jax.numpy as jnp
    import optax

    from dlrover_tpu.models import llama
    from dlrover_tpu.parallel.accelerate import Strategy, accelerate
    from dlrover_tpu.parallel.mesh import MeshSpec

    run = model["run"]
    if args.rehearsal:
        model = weights.tiny_model(model)
        run = dict(run, seq=64, batch=2)
    cfg = lib.llama_config(model, args.rehearsal)
    acc = accelerate(
        init_params=lambda k: weights.init_params(model, k, jnp.float32),
        loss_fn=lambda p, b, m: llama.loss_fn(cfg, p, b, mesh=m),
        rules=llama.partition_rules(cfg),
        optimizer=optax.adamw(run["learning_rate"]),
        strategy=Strategy(mesh=MeshSpec.fit(1)),
        devices=jax.devices()[:1],
    )
    return model, run, cfg, acc


def traced(trainer, trace_dir, body, keep=""):
    """Run body() under the profiler and return the reduced trace."""
    import jax

    import trace_reduce

    os.makedirs(trace_dir, exist_ok=True)
    jax.profiler.start_trace(
        trace_dir, profiler_options=trace_reduce.profile_options()
    )
    t0 = time.time()
    try:
        body()
    finally:
        jax.block_until_ready(trainer.state["step"])
        t1 = time.time()
        jax.profiler.stop_trace()
    reduced = trace_reduce.reduce_dir(trace_dir)
    if keep:
        os.makedirs(keep, exist_ok=True)
        shutil.copy(trace_reduce.find_xplane(trace_dir), keep)
    reduced["host_window_s"] = t1 - t0
    return reduced


def run_window(args, mix, trainer, ckpt, counter):
    """The measured window. Returns what the parent turns into
    metrics; nothing here is rounded."""
    from dlrover_tpu.trainer.flash_checkpoint.engine import StorageType
    import jax

    save_every = int(mix["save_every_steps"])
    first = len(trainer.records)
    saves, trace = [], None
    counter.count, counter.counting = 0, True
    t_begin = time.time()

    def steps_then_save(n):
        for _ in range(n):
            trainer.take_step()
        t0 = time.time()
        with jax.profiler.TraceAnnotation("perfbench:save_checkpoint"):
            blocked = ckpt.save_checkpoint(
                trainer.step, trainer.state, StorageType.MEMORY
            )
        saves.append({
            "step": trainer.step, "stall_s": time.time() - t0,
            "reported_s": blocked,
        })

    trace_steps = int(mix["trace_steps"])
    trace_dir = os.path.join(args.work_dir, "trace")

    def elapsed():
        return time.time() - t_begin

    if save_every:  # whole cycles: N steps and the save after them
        if args.trace:  # traced: a cycle's last steps and its save
            for _ in range(save_every - trace_steps):
                trainer.take_step()
            trace = traced(
                trainer, trace_dir, lambda: steps_then_save(trace_steps),
                args.keep_trace,
            )
        else:
            steps_then_save(save_every)
        while elapsed() < args.seconds:
            steps_then_save(save_every)
    else:
        if args.trace:
            for _ in range(TRACE_AFTER_STEPS):
                trainer.take_step()
            trace = traced(
                trainer, trace_dir,
                lambda: [trainer.take_step() for _ in range(trace_steps)],
                args.keep_trace,
            )
        while elapsed() < args.seconds:
            trainer.take_step()
    t_end = time.time()
    counter.counting = False
    records = trainer.records[first:]
    return {
        "event": "window", "t_begin": t_begin, "t_end": t_end,
        "steps": len(records),
        "step_seconds": [r[2] for r in records],
        "losses_nonfinite": sum(not math.isfinite(r[1]) for r in records),
        "loss_first": records[0][1], "loss_last": records[-1][1],
        "saves": saves, "compilations": counter.count,
        "memory_peak_bytes": lib.memory_peak_bytes(),
        "trace": trace,
    }


def run_reference(args, model, run):
    """Three plain AdamW steps in float32 on the job's first batches."""
    import jax

    import reference

    batches = [
        generate.batch(args.seed, s, run["batch"], run["seq"],
                       model["vocab_size"])
        for s in range(1, REFERENCE_STEPS + 1)
    ]
    t0 = time.time()
    with jax.default_matmul_precision("highest"):
        out = reference.train_steps(
            model, args.seed, batches, run["learning_rate"],
        )
    out.update(event="reference", seconds=time.time() - t0)
    lib.emit(**out)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config-file", required=True)
    ap.add_argument("--traffic-file", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--work-dir", required=True)
    ap.add_argument("--keep-trace", default="")
    args = ap.parse_args()

    if args.rehearsal:
        from dlrover_tpu.utils.platform import ensure_cpu_if_forced

        ensure_cpu_if_forced()
    import dlrover_tpu
    from dlrover_tpu.common.constants import NodeEnv
    from dlrover_tpu.trainer.flash_checkpoint.engine import Checkpointer

    restart = int(os.environ.get(NodeEnv.RESTART_COUNT, "0"))
    lib.emit(event="worker_start", restart=restart, wall=T_PROCESS)
    dlrover_tpu.init()  # rendezvous world + persistent compile cache
    import jax

    walls = {"process": T_PROCESS, "worker_start": time.time()}
    device = lib.require_device(args.rehearsal, chips=1)
    walls["device"] = time.time()
    counter = lib.CompileCounter()
    mix = lib.read_json(args.traffic_file)
    model, run, cfg, acc = build(args, lib.read_json(args.config_file))

    ckpt = Checkpointer(os.path.join(args.work_dir, "ckpt"))
    # restore onto the shardings, never onto a live state: a second
    # copy of the state does not fit beside the first
    t0 = time.time()
    with jax.profiler.TraceAnnotation("perfbench:restore"):
        step0, state = ckpt.load_checkpoint(target=acc.state_shardings)
        restored = state is not None
        if not restored:
            step0, state = 0, acc.init(weights.seed_key(args.seed))
        jax.block_until_ready(state)
    state_ready_s = time.time() - t0
    feed = Feed(acc, args.seed, run["batch"], run["seq"],
                model["vocab_size"], step0 + 1)
    trainer = Trainer(acc, state, feed, step0)
    del state

    walls["state"] = time.time()
    lib.emit(
        event="worker_up", restart=restart, device=device,
        restored=restored, resumed_step=step0,
        state_step=int(trainer.state["step"]),
        state_ready_s=state_ready_s, walls=walls, layers=cfg.n_layers,
        rows=run["batch"], seq=run["seq"],
        cache_dir=jax.config.jax_compilation_cache_dir,
    )

    if restart == 0:
        lib.emit(**first_steps(trainer, model, args.seed))
        window = run_window(args, mix, trainer, ckpt, counter)
        lib.emit(**window)
        # which kernels the step holds, from its compiled text. Read
        # after the window, so that the step's executable comes out of
        # the compile cache once in set-up (by the jit call of the
        # first step), not twice.
        held = acc.train_step.lower(trainer.state, feed.ready).compile()
        lib.emit(event="kernels", kernels=lib.kernel_names(held.as_text()))
        del held
        if mix["kill"]:
            for _ in range(int(mix["kill_after_steps"])):
                loss = trainer.take_step()
                lib.emit(event="step", restart=0, step=trainer.step,
                         loss=loss, wall=time.time())
            lib.emit(event="sigkill", step=trainer.step, wall=time.time())
            sys.stdout.flush()
            os.kill(os.getpid(), signal.SIGKILL)
    else:
        loss = trainer.take_step()
        lib.emit(event="step", restart=restart, step=trainer.step,
                 loss=loss, wall=time.time())
        lib.emit(event="resumed", restart=restart,
                 memory_peak_bytes=lib.memory_peak_bytes())

    ckpt.close()
    # free the program's state; only then the reference
    trainer.state = trainer.feed = None
    del trainer, feed
    gc.collect()
    run_reference(args, model, run)
    lib.emit(event="worker_done", restart=restart, wall=time.time())
    return 0


if __name__ == "__main__":
    sys.exit(main())
