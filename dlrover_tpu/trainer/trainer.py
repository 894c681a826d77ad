"""High-level training loop — the AtorchTrainer / FlashCkptTrainer analogue.

Reference parity:
- atorch/atorch/trainer/atorch_trainer.py:136 (`AtorchTrainer`): HF-style
  train/evaluate/save loop with resume, periodic logging/eval/save.
- dlrover/trainer/torch/flash_checkpoint/hf_trainer.py:123
  (`FlashCkptTrainer`): checkpoint saves go through the flash-checkpoint
  engine instead of blocking disk writes.
- elastic_agent/monitor/training.py:77 (`TorchTrainingMonitor`): the
  trainer publishes its global step for the agent's heartbeat.

TPU design: the loop drives an `ElasticTrainer` (fixed global batch over
an SPMD mesh). Saves stage to host shm in milliseconds and persist
asynchronously; resume is memory-first. A `HangingDetector` watches
step liveness. Callbacks mirror the HF `TrainerCallback` surface the
reference exposes (on_step_end / on_log / on_save / on_evaluate).
"""

import os
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Iterable, List, Optional

import jax
import numpy as np

from dlrover_tpu.agent.monitor import (
    publish_chip_metrics,
    write_step_metrics,
)
from dlrover_tpu.common import trace
from dlrover_tpu.common.log import default_logger as logger
from dlrover_tpu.parallel import remat
from dlrover_tpu.trainer.flash_checkpoint.engine import (
    Checkpointer,
    StorageType,
)
from dlrover_tpu.utils.hanging_detector import HangingDetector


@dataclass
class TrainingArguments:
    """Reference: atorch/atorch/trainer/atorch_args.py (HF-style args)."""

    output_dir: str = "output"
    max_steps: int = -1
    num_epochs: int = 1
    logging_steps: int = 10
    eval_steps: int = 0  # 0 = no periodic eval
    save_steps: int = 0  # 0 = no periodic save
    save_storage: str = StorageType.DISK
    save_total_limit: int = 0  # kept by the storage deletion strategy
    resume: bool = True
    hang_timeout: float = 1800.0
    publish_step_metrics: bool = True
    # after the first step, send model size + compiled-program stats
    # (utils/program_stats) to the master's metric collector
    report_model_info: bool = True


class TrainerCallback:
    """Subclass-and-override hook points (HF TrainerCallback surface)."""

    def on_train_begin(self, trainer, state):  # noqa: D401
        pass

    def on_step_end(self, trainer, state, metrics: Dict):
        pass

    def on_log(self, trainer, state, logs: Dict):
        pass

    def on_save(self, trainer, state, step: int):
        pass

    def on_evaluate(self, trainer, state, metrics: Dict):
        pass

    def on_train_end(self, trainer, state):
        pass


class Trainer:
    """Train an ElasticTrainer-wrapped model with flash checkpointing.

    ``train_data`` yields host batches whose leading dim equals the
    elastic trainer's global batch size (an `ElasticDataLoader` or any
    iterable); ``eval_data`` likewise for evaluation.
    """

    def __init__(
        self,
        elastic_trainer,
        args: Optional[TrainingArguments] = None,
        train_data: Optional[Iterable] = None,
        eval_data: Optional[Iterable] = None,
        callbacks: Optional[List[TrainerCallback]] = None,
        checkpointer: Optional[Checkpointer] = None,
        master_client=None,
    ):
        self.et = elastic_trainer
        self.args = args or TrainingArguments()
        self.train_data = train_data
        self.eval_data = eval_data
        self.callbacks = list(callbacks or [])
        self._mc = master_client
        self.checkpointer = checkpointer
        if self.checkpointer is None and (
            self.args.save_steps > 0 or self.args.resume
        ):
            self.checkpointer = Checkpointer(
                os.path.join(self.args.output_dir, "checkpoints"),
                max_to_keep=self.args.save_total_limit,
            )
        self.global_step = 0
        self.last_logs: Dict = {}
        # once per PROCESS, not per job: a restarted/resumed worker
        # re-reports (the master's collector is in-memory and the
        # recompiled program may differ after an elastic resize)
        self._model_info_reported = False
        self._hang = HangingDetector(
            timeout=self.args.hang_timeout, master_client=master_client
        )

    @staticmethod
    def _log_startup():
        """One line when the first step has returned: what this
        process spent before it could train, from the ring's
        `runtime.init` span and `compile` records (a process that
        never called `dlrover_tpu.init()` left none: no line). A
        respawned worker's line is the other half of the agent's
        "worker restart: persist, respawn": programs read back from
        the persistent cache are hits, and misses are what the
        recovery waited for. Where the step's layer scans took their
        rung from the device's memory (`remat.LadderStep`), the line
        ends with the rung and what each tried rung compiled to."""
        totals = trace.compile_totals()
        if totals is None:
            return
        joined = sum(
            r[trace.DUR] for r in trace.snapshot()
            if r[trace.NAME] == "runtime.init"
        )
        ladder = remat.ladder_summary()
        logger.info(
            "worker start-up: runtime.init %.1f s, traced and lowered "
            "%.1f s, compiled %.1f s: %d programs, %d cache hits, %d "
            "misses, slowest %s%s",
            joined, totals["trace_lower_s"], totals["backend_s"],
            totals["programs"], totals["cache_hits"],
            totals["cache_misses"], totals["slowest"],
            "; " + ladder if ladder else "",
        )

    def _report_model_info(self, state, batch):
        """One-shot after the first step: model size + compiled-program
        stats to the master (reference report_model_info → brain).

        Runs the AOT lower+compile in a daemon thread: without a
        persistent compilation cache, `lower().compile()` does NOT hit
        the in-memory jit executable cache, so on a real model it is a
        second full XLA compile — off the training critical path it
        costs idle host CPU only. Shape/sharding metadata stays valid
        even after later steps donate the state buffers."""
        if self._mc is None or not self.args.report_model_info:
            return

        def _profile_and_report():
            try:
                params = (
                    state.get("params")
                    if isinstance(state, dict)
                    else state
                )
                leaves = jax.tree_util.tree_leaves(params)
                num_params = int(
                    sum(
                        int(np.prod(x.shape))
                        for x in leaves
                        if hasattr(x, "shape")
                    )
                )
                stats = None
                if hasattr(self.et, "profile_program"):
                    stats = self.et.profile_program(state, batch)
                bsz = 0
                seq = 0
                tok = (
                    batch.get("tokens")
                    if isinstance(batch, dict)
                    else None
                )
                if tok is not None and getattr(tok, "ndim", 0) >= 2:
                    # train_data yields GLOBAL batches (class
                    # docstring); the per-host share is what the
                    # master's resource estimates need
                    bsz = int(tok.shape[0]) // max(
                        jax.process_count(), 1
                    )
                    seq = int(tok.shape[1])
                # cost_analysis reports the PER-DEVICE partitioned
                # program; scale to per-host to match
                # batch_size_per_host (the servicer derives
                # flops_per_token from the pair)
                flops_host = (
                    stats.flops * jax.local_device_count()
                    if stats
                    else 0.0
                )
                self._mc.report_model_info(
                    num_params=num_params,
                    flops_per_step=flops_host,
                    batch_size_per_host=bsz,
                    seq_len=seq,
                    program_stats=stats.to_json() if stats else "",
                )
            except Exception:  # noqa: BLE001 — never kill training
                logger.debug("model info report failed", exc_info=True)

        import threading

        threading.Thread(
            target=_profile_and_report,
            name="model-info-report",
            daemon=True,
        ).start()

    # -- checkpoint --------------------------------------------------------

    def save(self, state, storage_type: Optional[str] = None) -> float:
        st = storage_type or self.args.save_storage
        blocked = self.checkpointer.save_checkpoint(
            self.global_step, state, storage_type=st
        )
        logger.info(
            "saved step %d to %s (blocked %.3f s)",
            self.global_step,
            st,
            blocked,
        )
        for cb in self.callbacks:
            cb.on_save(self, state, self.global_step)
        return blocked

    def _maybe_resume(self, state):
        if not (self.args.resume and self.checkpointer):
            return state
        step, restored = self.checkpointer.load_checkpoint(target=state)
        if restored is None:
            return state
        self.global_step = step
        logger.info("resumed from step %d", step)
        return restored

    # -- evaluation --------------------------------------------------------

    def evaluate(self, state) -> Dict:
        if self.eval_data is None:
            return {}
        totals: Dict[str, float] = {}
        count = 0
        for batch in self.eval_data:
            metrics = self.et.eval_step(state, batch)
            for k, v in metrics.items():
                totals[k] = totals.get(k, 0.0) + float(
                    np.asarray(jax.device_get(v))
                )
            count += 1
        logs = {
            f"eval_{k}": v / max(count, 1) for k, v in totals.items()
        }
        for cb in self.callbacks:
            cb.on_evaluate(self, state, logs)
        return logs

    # -- main loop ---------------------------------------------------------

    def train(self, state=None) -> Any:
        if state is None:
            state = self.et.init_state(jax.random.PRNGKey(0))
        state = self._maybe_resume(state)
        self._hang.start()
        for cb in self.callbacks:
            cb.on_train_begin(self, state)

        # on resume, don't replay already-consumed batches: loaders
        # with their own resumable sampler (ElasticDataLoader) handle
        # this via sampler state; plain iterables get skipped here.
        skip = 0
        start_epoch = 0
        if self.global_step > 0 and not hasattr(
            self.train_data, "load_state_dict"
        ):
            try:
                n_batches = len(self.train_data)
            except TypeError:
                n_batches = 0
            if n_batches:
                # fully-consumed epochs are NOT replayed; the partial
                # epoch skips to where it left off
                start_epoch = self.global_step // n_batches
                skip = self.global_step % n_batches
            else:
                skip = self.global_step

        window_t0 = time.monotonic()
        window_steps = 0
        window_host_ms = 0.0
        stop = False
        try:
            for epoch in range(start_epoch, self.args.num_epochs):
                if stop:
                    break
                if hasattr(self.train_data, "set_epoch"):
                    self.train_data.set_epoch(epoch)
                for batch in self.train_data:
                    if skip > 0:
                        skip -= 1
                        continue
                    # host time = python + dispatch, BEFORE the device
                    # wait: the runtime-straggler signal (SPMD lockstep
                    # equalizes wall time across hosts, not this)
                    t_host = time.monotonic()
                    state, metrics = self.et.step(state, batch)
                    window_host_ms += (
                        time.monotonic() - t_host
                    ) * 1e3
                    jax.block_until_ready(
                        metrics.get("loss", metrics)
                    )
                    if not self._model_info_reported:
                        self._model_info_reported = True
                        self._log_startup()
                        self._report_model_info(state, batch)
                    self.global_step += 1
                    window_steps += 1
                    self._hang.record_step(self.global_step)
                    for cb in self.callbacks:
                        cb.on_step_end(self, state, metrics)

                    a = self.args
                    if (
                        a.logging_steps
                        and self.global_step % a.logging_steps == 0
                    ):
                        dt = time.monotonic() - window_t0
                        logs = {
                            k: float(np.asarray(jax.device_get(v)))
                            for k, v in metrics.items()
                        }
                        logs["steps_per_sec"] = window_steps / max(
                            dt, 1e-9
                        )
                        logs["step"] = self.global_step
                        self.last_logs = logs
                        logger.info("step %s", logs)
                        for cb in self.callbacks:
                            cb.on_log(self, state, logs)
                        if a.publish_step_metrics:
                            write_step_metrics(
                                self.global_step, **{
                                    "loss": logs.get("loss", 0.0)
                                }
                            )
                            # accelerator stats for the agent's chip
                            # collector (the agent itself never
                            # initializes JAX — libtpu is ours)
                            try:
                                publish_chip_metrics()
                            except Exception:  # noqa: BLE001
                                pass
                        if self._mc is not None:
                            try:
                                self._mc.report_global_step(
                                    self.global_step,
                                    host_compute_ms=(
                                        window_host_ms
                                        / max(window_steps, 1)
                                    ),
                                )
                            except Exception:
                                pass
                        window_t0 = time.monotonic()
                        window_steps = 0
                        window_host_ms = 0.0
                    if (
                        a.eval_steps
                        and self.global_step % a.eval_steps == 0
                    ):
                        self.evaluate(state)
                    if (
                        a.save_steps
                        and self.global_step % a.save_steps == 0
                    ):
                        self.save(state)
                    if (
                        a.max_steps > 0
                        and self.global_step >= a.max_steps
                    ):
                        stop = True
                        break
        finally:
            self._hang.stop()
        if self.args.save_steps and self.checkpointer:
            self.save(state, storage_type=StorageType.DISK)
        for cb in self.callbacks:
            cb.on_train_end(self, state)
        return state
