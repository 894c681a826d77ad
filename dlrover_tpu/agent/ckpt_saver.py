"""Flash Checkpoint saver daemon (agent side) + shared-memory handler.

Reference parity: dlrover/python/elastic_agent/torch/ckpt_saver.py —
`SharedMemoryHandler` (:210), `AsyncCheckpointSaver` (:345, factory thread
start_async_saving_ckpt :410), `CommonDirCheckpointSaver` (:774,
save_step_checkpoint / commit_checkpoint), done-file two-phase commit,
tracker file.

TPU re-design: the staged state is a flat {path: np.ndarray} of the
host's *addressable shards* of sharded jax.Arrays (device→host DMA done
by the trainer engine). The shm segment is a /dev/shm file that survives
a trainer crash; the agent persists it asynchronously and runs the commit
protocol through the master's KV-store-free filesystem dance (done files
+ tracker), identical to the reference.
"""

import json
import os
import pickle
import queue
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from dlrover_tpu.common import trace
from dlrover_tpu.common.constants import CheckpointConstant
from dlrover_tpu.common.log import default_logger as logger
from dlrover_tpu.common.multi_process import (
    LocalSocketServer,
    SharedDict,
    SharedLock,
    SharedMemorySegment,
    SharedQueue,
)
from dlrover_tpu.common.storage import (
    CheckpointStorage,
    get_checkpoint_storage,
)

CKPT_META_NAME = "ckpt_meta"
CKPT_QUEUE_NAME = "ckpt_save_events"
CKPT_LOCK_NAME = "ckpt_shm_lock"
# restore-path fan-out (shm leaf copies, storage shard reads): the
# stall a recovering trainer pays is read + H2D, and both legs
# parallelize (reference: megatron parallel load, 242→156 s)
RESTORE_THREADS = int(os.environ.get("DLROVER_TPU_RESTORE_THREADS", "8"))


class ShmIntegrityError(RuntimeError):
    """The shm segment does not cover the staged metadata — a stale
    mapping across a writer resize, or a torn write. Restore paths must
    treat this as "no usable memory checkpoint" and fall back to
    storage/replica; the saver must skip the persist (the previously
    committed step stays authoritative)."""


@dataclass
class TensorMeta:
    path: str  # flattened pytree path, "params/layers/wq"
    shape: Tuple[int, ...]
    dtype: str
    offset: int
    nbytes: int


@dataclass
class CheckpointMeta:
    step: int = -1
    save_path: str = ""
    tensors: List[TensorMeta] = field(default_factory=list)
    aux: bytes = b""  # pickled non-array leaves + treedef info
    total_bytes: int = 0


def shm_segment_name(job_name: str, node_rank: int) -> str:
    return f"dlrover_tpu_ckpt_{job_name}_{node_rank}"


class SharedMemoryHandler:
    """Write/read a flat {path: np.ndarray} state into the shm segment.

    Reference: SharedMemoryHandler ckpt_saver.py:210 (_traverse_copy_to_shm
    :175 equivalent is `save_flat_state`).
    """

    def __init__(self, job_name: str, node_rank: int = 0):
        self.job_name = job_name
        self.node_rank = node_rank
        self.seg_name = shm_segment_name(job_name, node_rank)
        self._segment: Optional[SharedMemorySegment] = None
        self.meta_dict = SharedDict(
            f"{CKPT_META_NAME}_{node_rank}", job_name
        )
        self.lock = SharedLock(
            f"{CKPT_LOCK_NAME}_{node_rank}", job_name
        )

    # ---- write path (trainer) -------------------------------------------

    def save_flat_state(
        self,
        step: int,
        flat: Dict[str, np.ndarray],
        save_path: str = "",
        aux: bytes = b"",
    ):
        flat = {p: np.asarray(a) for p, a in flat.items()}
        tensors = []
        offset = 0
        for path, arr in flat.items():
            # metadata only needs shape/dtype/nbytes — all invariant
            # under contiguity, so no copy here (the write loop below
            # makes the one contiguous copy a strided source needs)
            tensors.append(
                TensorMeta(
                    path, tuple(arr.shape), str(arr.dtype), offset,
                    arr.nbytes,
                )
            )
            offset += arr.nbytes
        # the copy into the mapping (and, the first time or after a
        # resize, the segment's creation), then the word to the agent
        with trace.span("ckpt.shm_write", bytes=offset) as sp:
            if (
                self._segment is None
                or self._segment.size < offset
                or self._segment.is_stale()
            ):
                if self._segment is not None:
                    self._segment.close()
                self._segment = SharedMemorySegment(
                    self.seg_name, size=max(offset, 1), create=True
                )
                sp.set(new_segment=1)
            buf = self._segment.buf
            for tm, arr in zip(tensors, flat.values()):
                if tm.nbytes == 0:
                    continue
                # copy straight into the mapping: tobytes() would material-
                # ize a second full host copy of every tensor per save
                dst = np.frombuffer(
                    buf, dtype=np.uint8, count=tm.nbytes, offset=tm.offset
                )
                src = np.ascontiguousarray(arr)
                np.copyto(dst, src.reshape(-1).view(np.uint8))
        meta = CheckpointMeta(
            step=step,
            save_path=save_path,
            tensors=tensors,
            aux=aux,
            total_bytes=offset,
        )
        with trace.span("ckpt.notify"):
            self.meta_dict.set("meta", pickle.dumps(meta))

    # ---- read path (agent saver / trainer restore) ----------------------

    def get_meta(self) -> Optional[CheckpointMeta]:
        raw = self.meta_dict.get("meta")
        return pickle.loads(raw) if raw else None

    def load_flat_state(
        self,
    ) -> Tuple[Optional[CheckpointMeta], Dict[str, np.ndarray]]:
        meta = self.get_meta()
        if meta is None or meta.step < 0:
            return None, {}
        with trace.span("ckpt.shm_read", bytes=meta.total_bytes):
            return self._read_segment(meta)

    def _read_segment(
        self, meta: CheckpointMeta
    ) -> Tuple[Optional[CheckpointMeta], Dict[str, np.ndarray]]:
        if (
            self._segment is None
            or self._segment.size < meta.total_bytes
            or self._segment.is_stale()
        ):
            # A writer may have grown (ftruncate) or unlinked-and-
            # recreated the segment since we mapped it — e.g. shard
            # shapes changed on a 16→8 reshard. A stale mmap silently
            # truncates slice reads (or serves the orphaned old inode),
            # so re-attach from the file, which always has the current
            # inode and size (reference re-opens shm by name on every
            # access, ckpt_saver.py:210).
            if self._segment is not None:
                self._segment.close()
                self._segment = None
            try:
                self._segment = SharedMemorySegment(self.seg_name)
            except FileNotFoundError:
                # unlinked between staging and this read (agent
                # teardown, /dev/shm cleanup): no memory checkpoint
                return None, {}
        if self._segment.size < meta.total_bytes:
            raise ShmIntegrityError(
                f"shm segment {self.seg_name} holds "
                f"{self._segment.size} bytes but meta for step "
                f"{meta.step} claims {meta.total_bytes}"
            )
        buf = self._segment.buf
        seg_size = self._segment.size
        for tm in meta.tensors:
            if tm.offset + tm.nbytes > seg_size:
                raise ShmIntegrityError(
                    f"truncated read of {tm.path}: needs bytes "
                    f"[{tm.offset}, {tm.offset + tm.nbytes}) but "
                    f"segment size is {seg_size}"
                )

        def _copy(tm):
            # zero-copy view of the mmap, then an owned .copy() — the
            # numpy memcpy releases the GIL, so the pool below overlaps
            # per-leaf copies (the restore stall is exactly this read +
            # H2D; reference parallel-load blog: megatron_flash_
            # checkpoint.md:160 cuts 242→156 s the same way)
            dt = np.dtype(tm.dtype)
            view = np.frombuffer(
                buf, dtype=dt, count=tm.nbytes // dt.itemsize,
                offset=tm.offset,
            )
            return tm.path, view.reshape(tm.shape).copy()

        # NOT gated on cpu_count: memcpy releases the GIL so extra
        # threads are harmless on small hosts, and gating would leave
        # the pool path forever untested on the 1-CPU CI container
        n_workers = min(RESTORE_THREADS, len(meta.tensors))
        if n_workers > 1 and meta.total_bytes > (64 << 20):
            from concurrent.futures import ThreadPoolExecutor

            with ThreadPoolExecutor(n_workers) as pool:
                flat = dict(pool.map(_copy, meta.tensors))
        else:
            flat = dict(_copy(tm) for tm in meta.tensors)
        return meta, flat

    def close(self, unlink: bool = False):
        if self._segment is not None:
            if unlink:
                self._segment.unlink()
            else:
                self._segment.close()
            self._segment = None

    def close_thread_conns(self):
        """Close the calling thread's IPC connections (see
        _Proxy.close_thread) — for short-lived staging threads."""
        self.meta_dict.close_thread()
        self.lock.close_thread()


class AsyncCheckpointSaver:
    """Agent-resident daemon: drains save events, persists shm to storage,
    runs the done-file commit protocol.

    Reference: AsyncCheckpointSaver ckpt_saver.py:345 +
    CommonDirCheckpointSaver :774. One saver per host; `node_rank`/
    `num_nodes` drive the commit barrier (rank 0 writes the tracker once
    every host's done file exists).
    """

    _singleton = None

    def __init__(
        self,
        job_name: str = "default",
        node_rank: int = 0,
        num_nodes: int = 1,
        storage: Optional[CheckpointStorage] = None,
        master_client=None,
    ):
        self.job_name = job_name
        self.node_rank = node_rank
        self.num_nodes = num_nodes
        self.storage = storage or get_checkpoint_storage()
        self.master_client = master_client
        self.shm_handler = SharedMemoryHandler(job_name, node_rank)
        self.event_queue = SharedQueue(CKPT_QUEUE_NAME, job_name)
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        # serializes the commit phase: the saver loop and the agent's
        # crash/teardown persist may race, and the tracker's
        # check-then-write below must not interleave (a stale reader
        # could regress the tracker to an older step)
        self._commit_lock = threading.Lock()
        # (checkpoint_dir, max_to_keep) of the installed retention
        # strategy — see _handle_event
        self._retention = (None, 0)
        self.last_persisted_step = -1

    # ---- lifecycle -------------------------------------------------------

    @classmethod
    def start_async_saving_ckpt(cls, **kw) -> "AsyncCheckpointSaver":
        """Factory: one daemon thread per agent process (reference :410)."""
        if cls._singleton is None:
            cls._singleton = cls(**kw)
            cls._singleton.start()
        return cls._singleton

    @classmethod
    def reset(cls):
        if cls._singleton is not None:
            cls._singleton.stop()
            cls._singleton = None

    def start(self):
        self._thread = threading.Thread(
            target=self._saver_loop, name="ckpt-saver", daemon=True
        )
        self._thread.start()

    def update_topology(self, node_rank: int, num_nodes: int):
        """Re-point the saver after a rendezvous round changed this
        host's rank or the world size (commit barrier + shm name)."""
        if node_rank != self.node_rank:
            self.shm_handler.close()
            self.shm_handler = SharedMemoryHandler(
                self.job_name, node_rank
            )
        self.node_rank = node_rank
        self.num_nodes = num_nodes

    def stop(self):
        self._stop.set()

    # ---- persist path ----------------------------------------------------

    def _saver_loop(self):
        while not self._stop.is_set():
            try:
                event = self.event_queue.get(timeout=1.0)
            except queue.Empty:
                continue
            except (ConnectionError, OSError):
                time.sleep(1.0)
                continue
            try:
                self._handle_event(event)
            except Exception:  # noqa: BLE001 — saver must survive
                logger.exception("checkpoint persist failed")

    def _handle_event(self, event: dict):
        step = event["step"]
        path = event["path"]
        # deletion policy rides the event (the trainer owns the config,
        # this saver process owns the storage doing the commits):
        # save_total_limit → keep only the newest N step dirs. The
        # saver outlives trainer restarts, so re-install whenever the
        # dir or limit changes (a stale strategy would prune the WRONG
        # directory and ignore limit updates).
        max_to_keep = int(event.get("max_to_keep", 0) or 0)
        if self._retention != (path, max_to_keep):
            if max_to_keep > 0:
                from dlrover_tpu.common.storage import (
                    KeepLatestStepStrategy,
                )

                self.storage.deletion_strategy = (
                    KeepLatestStepStrategy(max_to_keep, path)
                )
            else:
                # the trainer restarted WITHOUT a retention limit (or
                # into a different dir): a stale strategy would keep
                # pruning — including under the OLD directory
                self.storage.deletion_strategy = None
            self._retention = (path, max_to_keep)
        t0 = time.monotonic()
        self.save_step_checkpoint(step, path)
        logger.info(
            "persisted checkpoint step=%d to %s in %.2fs",
            step,
            path,
            time.monotonic() - t0,
        )

    def save_step_checkpoint(
        self, step: int, path: str, commit_timeout: float = None
    ):
        """Persist the current shm state for `step` under `path/step/`."""
        # hold the shm lock only for the copy-out: load_flat_state
        # returns owned copies, and keeping the lock across the (slow)
        # storage write would block a restarting trainer's restore
        # behind the persist of the very step it wants to read
        with self.shm_handler.lock:
            try:
                meta, flat = self.shm_handler.load_flat_state()
            except ShmIntegrityError as e:
                # torn staged state (writer resized mid-cycle): skip —
                # the previously committed step stays authoritative
                logger.warning("skipping persist of step %d: %s", step, e)
                return
        if meta is None or meta.step != step:
            logger.warning(
                "shm holds step %s, wanted %d — skipping persist",
                meta.step if meta else None,
                step,
            )
            return
        step_dir = os.path.join(path, str(step))
        self.storage.makedirs(step_dir)
        self.persist_to_storage(step_dir, meta, flat)
        self.commit_checkpoint(step, path, timeout=commit_timeout)
        self.last_persisted_step = step

    def persist_to_storage(
        self, step_dir: str, meta: CheckpointMeta, flat: dict
    ):
        """One .npz per host shard + pickled aux."""
        shard_file = os.path.join(
            step_dir, f"host_{self.node_rank}.npz"
        )
        import io

        bio = io.BytesIO()
        np.savez(bio, **flat)
        self.storage.write(bio.getvalue(), shard_file)
        aux_file = os.path.join(
            step_dir, f"aux_{self.node_rank}.pkl"
        )
        self.storage.write(meta.aux, aux_file)

    # ---- commit protocol -------------------------------------------------

    def commit_checkpoint(
        self, step: int, path: str, timeout: float = None
    ):
        """Two-phase: every host writes `.done_{rank}`; rank 0 waits for
        all, then atomically updates the tracker file and notifies the
        master (reference commit_checkpoint + update_tracker_file)."""
        timeout = timeout or CheckpointConstant.SAVE_TIMEOUT_SECS
        step_dir = os.path.join(path, str(step))
        done_file = os.path.join(
            step_dir,
            f"{CheckpointConstant.DONE_FILE_PREFIX}{self.node_rank}",
        )
        self.storage.write(b"1", done_file)

        def _coverage() -> int:
            return len(
                [
                    f
                    for f in self.storage.listdir(step_dir) or []
                    if f.startswith(CheckpointConstant.DONE_FILE_PREFIX)
                ]
            )

        if self.node_rank != 0:
            # non-zero ranks normally leave the tracker to rank 0, but
            # when they observe full coverage they promote it themselves
            # (idempotent write of the same value). This matters on the
            # scale-down path: if the rank-0 host is the one leaving, it
            # persists first and is gone — the survivor must still be
            # able to commit the jointly-covered step.
            if _coverage() >= self.num_nodes:
                self._promote_tracker(step, path)
            return
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            done = _coverage()
            if done >= self.num_nodes:
                break
            time.sleep(0.1)
        else:
            logger.error(
                "commit timeout: %d/%d done files for step %d",
                done,
                self.num_nodes,
                step,
            )
            self.storage.commit(step, False)
            return
        self._promote_tracker(step, path)
        if self.master_client is not None:
            try:
                self.master_client.report_ckpt_saved(step, path)
            except Exception:  # noqa: BLE001
                logger.warning("ckpt step report failed", exc_info=True)

    def _promote_tracker(self, step: int, path: str):
        """Advance the tracker to `step` unless it already points past
        it. The check-then-write runs under _commit_lock so concurrent
        commits in this process (saver loop + agent persist) cannot
        regress the tracker; cross-host, done-file coverage gates the
        write so every committer writes a fully-covered step."""
        with self._commit_lock:
            if step > read_tracker_step(self.storage, path):
                tracker = os.path.join(
                    path, CheckpointConstant.TRACKER_FILE
                )
                self.storage.write(str(step), tracker)
            self.storage.commit(step, True)

    # ---- crash path ------------------------------------------------------

    def save_shm_to_storage(self, commit_timeout: float = 15.0):
        """Called by the agent when the trainer dies, restarts for a
        membership change, or leaves on a scale-down: persist whatever
        step is staged in shm if newer than the last persisted one
        (reference _save_ckpt_to_storage training.py:674).

        Uses a SHORT commit-barrier timeout: peers may already be gone
        (that is often why we are persisting), and a restart must not
        stall SAVE_TIMEOUT_SECS waiting for their done-files. The
        tracker only advances on full coverage, so a skewed partial
        persist leaves the previous committed step authoritative."""
        meta = self.shm_handler.get_meta()
        if meta is None or meta.step < 0 or not meta.save_path:
            return
        if meta.step <= self.last_persisted_step:
            return
        logger.info(
            "trainer gone — persisting staged shm checkpoint step=%d",
            meta.step,
        )
        self.save_step_checkpoint(
            meta.step, meta.save_path, commit_timeout=commit_timeout
        )


def read_tracker_step(storage: CheckpointStorage, path: str) -> int:
    raw = storage.read(
        os.path.join(path, CheckpointConstant.TRACKER_FILE), "r"
    )
    try:
        return int(raw)
    except (TypeError, ValueError):
        return -1
