"""Flash Checkpoint engine (trainer side): jax state ↔ shm ↔ storage.

Reference parity: dlrover/trainer/torch/flash_checkpoint/engine.py:136
(`CheckpointEngine` — save_state_dict_to_memory :297,
get_state_dict_from_memory :332) and checkpointer.py:23 (`Checkpointer`
ABC, StorageType.MEMORY/DISK).

TPU re-design: the "state dict" is any jax pytree (params/opt_state/step).
`save_to_memory` device_gets each leaf's *addressable* shards into the
agent-owned /dev/shm segment under the shared lock (device→host DMA is
the only blocking cost — the reference's 0.2 s-class stall), then pokes
the agent's saver queue for async persistence. Restore prefers shm (warm
restart after a process crash), falling back to the persisted .npz.

Pytree structure is carried as a pickled treedef + flat path list so
optax named-tuple states round-trip exactly.
"""

import io
import os
import pickle
import threading
import time
from typing import Any, Dict, Optional, Tuple

import numpy as np

from dlrover_tpu.agent.ckpt_saver import (
    CKPT_QUEUE_NAME,
    RESTORE_THREADS,
    SharedMemoryHandler,
    ShmIntegrityError,
    read_tracker_step,
)
from dlrover_tpu.common import trace
from dlrover_tpu.common.constants import NodeEnv
from dlrover_tpu.common.log import default_logger as logger
from dlrover_tpu.common.multi_process import SharedQueue, server_alive
from dlrover_tpu.common.storage import (
    CheckpointStorage,
    get_checkpoint_storage,
)


class StorageType:
    MEMORY = "memory"
    DISK = "disk"


def _log_legs(what: str, step: int, parent: trace.Span, extra: str = ""):
    """One line per save and per restore: the spans recorded under
    `parent`, each leg's own time (its extent less its children's),
    and the bytes moved. A `compile` record under a leg (a restore
    that met a shape for the first time) stays part of that leg's own
    time: it is jax's stretch, not a leg of the checkpoint."""
    by_parent: Dict[int, list] = {}
    for r in trace.snapshot(since=parent.wall):
        if r[trace.NAME] != "compile":
            by_parent.setdefault(r[trace.PARENT], []).append(r)
    legs: Dict[str, float] = {}
    n_bytes = 0
    todo = list(by_parent.get(parent.id, ()))
    while todo:
        r = todo.pop()
        kids = by_parent.get(r[trace.ID], ())
        todo.extend(kids)
        name = r[trace.NAME].split(".", 1)[1]
        legs[name] = (
            legs.get(name, 0.0) + r[trace.DUR]
            - sum(k[trace.DUR] for k in kids)
        )
        n_bytes = max(n_bytes, r[trace.COUNTS].get("bytes", 0))
    logger.info(
        "flash checkpoint %s step %d: %.1f ms, %d bytes; %s %s",
        what, step, parent.dur_s * 1e3, n_bytes,
        " ".join(f"{k}={v * 1e3:.1f}ms" for k, v in sorted(legs.items())),
        extra,
    )


def _extract_npz(blob: bytes) -> Dict[str, np.ndarray]:
    """Extract every member of an in-memory .npz, fanning the per-leaf
    extraction over a thread pool for large archives.

    Restore is the stall a recovering trainer pays (reference parallel
    load cuts 242→156 s, megatron_flash_checkpoint.md:160); zip CRC and
    the member memcpy both release the GIL, so concurrent extraction
    overlaps them. Each worker opens its own np.load view — zipfile
    handles are not thread-safe, the underlying bytes are immutable."""
    with np.load(io.BytesIO(blob)) as npz:
        names = list(npz.files)
        n = min(RESTORE_THREADS, len(names))
        if n <= 1 or len(blob) < (32 << 20):
            return {k: npz[k] for k in names}
    from concurrent.futures import ThreadPoolExecutor

    def _group(keys):
        out = {}
        with np.load(io.BytesIO(blob)) as npz:
            for k in keys:
                out[k] = npz[k]
        return out

    flat: Dict[str, np.ndarray] = {}
    with ThreadPoolExecutor(n) as pool:
        for part in pool.map(_group, [names[i::n] for i in range(n)]):
            flat.update(part)
    return flat


# ---------------------------------------------------------------------------
# pytree <-> flat ndarray dict
# ---------------------------------------------------------------------------


def _leaf_path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        elif hasattr(p, "name"):
            parts.append(str(p.name))
        else:
            parts.append(str(p))
    return "/".join(parts)


def flatten_state(state: Any) -> Tuple[Dict[str, np.ndarray], bytes]:
    """Pytree → ({path: host ndarray}, aux bytes).

    Device arrays come back as the host view of their addressable data
    (on multi-host meshes each host stages only its shards — matching
    the reference's per-rank shm layout). Span `ckpt.flatten` covers
    all of it and `ckpt.d2h` inside it the copies off the device."""
    import jax

    with trace.span("ckpt.flatten"):
        leaves_with_paths, treedef = jax.tree_util.tree_flatten_with_path(
            state
        )
        with trace.span("ckpt.d2h", leaves=len(leaves_with_paths)):
            # kick off the device→host DMA for EVERY leaf before
            # draining any: np.asarray on a jax.Array is a synchronous
            # round-trip, and a 300-leaf train state staged serially
            # pays 300 transfer latencies back to back — a pipeline
            # stall on the chip's host link. After this pass the
            # per-leaf np.asarray below finds bytes already in flight.
            for _, leaf in leaves_with_paths:
                if isinstance(leaf, jax.Array):
                    try:
                        for shard in leaf.addressable_shards:
                            shard.data.copy_to_host_async()
                    except Exception:  # noqa: BLE001 - best-effort prefetch
                        pass
            flat = {}
            paths = []
            shard_meta = {}
            for path, leaf in leaves_with_paths:
                p = _leaf_path_str(path)
                paths.append(p)
                if isinstance(leaf, jax.Array):
                    # fully-addressable arrays: plain device_get; sharded
                    # multi-host arrays: concatenate local shards is wrong —
                    # stage each addressable shard separately and record how to
                    # reassemble them in aux.
                    if leaf.is_fully_addressable:
                        flat[p] = np.asarray(jax.device_get(leaf))
                    else:
                        entry = {
                            "shape": tuple(leaf.shape),
                            "dtype": str(leaf.dtype),
                            "keys": [],
                            "indices": [],
                        }
                        # keys carry the process index so shard files from
                        # different hosts can be merged without collisions
                        proc = jax.process_index()
                        for i, shard in enumerate(leaf.addressable_shards):
                            key = f"{p}#shard{proc}_{i}"
                            flat[key] = np.asarray(jax.device_get(shard.data))
                            entry["keys"].append(key)
                            entry["indices"].append(shard.index)
                        shard_meta[p] = entry
                else:
                    flat[p] = np.asarray(leaf)
        aux = pickle.dumps(
            {"treedef": treedef, "paths": paths, "shards": shard_meta}
        )
    return flat, aux


def _reassemble_sharded(
    path: str,
    entry: Dict,
    flat: Dict[str, np.ndarray],
    target_leaf,
):
    """Rebuild one multi-host leaf from its staged local shards.

    With a `target_leaf` (the live array on the restoring mesh) the
    local shards are placed directly on their devices via
    make_array_from_single_device_arrays — each host restores only its
    addressable slice, which is exactly what it staged. Without a
    target the global array is stitched on host, requiring every shard
    to be present in `flat`."""
    import jax

    present = [
        (k, ix)
        for k, ix in zip(entry["keys"], entry["indices"])
        if k in flat
    ]
    # true coverage check: the distinct shard indices must tile the full
    # shape. "all listed keys present" is NOT enough — an aux written by
    # one host lists only that host's shards, and stitching those into
    # zeros would silently fabricate a wrong (and per-host different)
    # global array.
    total = int(np.prod(entry["shape"])) if entry["shape"] else 1
    seen = {}
    for k, ix in present:
        seen[_index_key(ix)] = flat[k].size
    covered = sum(seen.values())
    if present and covered >= total:
        # full coverage (single host, or storage merged every host's
        # shard files): stitch the global array — works for ANY restore
        # mesh, since restore_to_shardings re-shards it afterwards
        out = np.zeros(entry["shape"], dtype=np.dtype(entry["dtype"]))
        for k, ix in present:
            out[ix] = flat[k]
        return out
    sharding = _leaf_sharding(target_leaf)
    if sharding is not None:
        # partial coverage (this host staged only its shards): place
        # each saved shard directly on the device that owns that index
        # in the restore sharding — valid only when the mesh layout
        # still matches what was saved
        shape = entry["shape"]
        index_to_saved = {
            _index_key(ix): flat[k]
            for k, ix in zip(entry["keys"], entry["indices"])
            if k in flat
        }
        arrays = []
        for d, ix in sharding.addressable_devices_indices_map(
            shape
        ).items():
            host = index_to_saved.get(_index_key(ix))
            if host is None:
                raise KeyError(
                    f"staged state for {path!r} is missing the shard "
                    f"at index {ix} needed by device {d}; the saved "
                    "sharding does not cover the restore mesh"
                )
            arrays.append(jax.device_put(host, d))
        return jax.make_array_from_single_device_arrays(
            shape, sharding, arrays
        )
    raise KeyError(
        f"cannot reassemble {path!r} on host: some shards were staged "
        "on other hosts; pass `target` so each host restores its own "
        "shards"
    )


def _index_key(ix) -> tuple:
    return tuple(
        (s.start, s.stop, s.step) if isinstance(s, slice) else s
        for s in ix
    )


def _leaf_sharding(ref):
    """A restore target leaf may be a live array (carries .sharding) or
    a bare jax.sharding.Sharding (e.g. Accelerated.state_shardings)."""
    import jax

    if ref is None:
        return None
    if isinstance(ref, jax.sharding.Sharding):
        return ref
    return getattr(ref, "sharding", None)


def _merge_aux(own_aux: bytes, other_auxes) -> bytes:
    """Union the per-host shard metadata so a merged flat dict can be
    stitched to full coverage (each host's aux lists only the shard
    keys/indices that host staged)."""
    meta = pickle.loads(own_aux)
    shards = meta.get("shards", {})
    for raw in other_auxes:
        if raw is None:
            continue
        try:
            other = pickle.loads(raw)
        except Exception:  # noqa: BLE001 — a torn aux never blocks restore
            continue
        for p, entry in other.get("shards", {}).items():
            mine = shards.setdefault(
                p,
                {
                    "shape": entry["shape"],
                    "dtype": entry["dtype"],
                    "keys": [],
                    "indices": [],
                },
            )
            for k, ix in zip(entry["keys"], entry["indices"]):
                if k not in mine["keys"]:
                    mine["keys"].append(k)
                    mine["indices"].append(ix)
    meta["shards"] = shards
    return pickle.dumps(meta)


def unflatten_state(
    flat: Dict[str, np.ndarray], aux: bytes, target: Any = None
) -> Any:
    """Inverse of flatten_state. `target` (a pytree of live arrays with
    the restore-time shardings) is required to reassemble leaves that
    were staged as multi-host shards."""
    import jax

    meta = pickle.loads(aux)
    treedef = meta["treedef"]
    shard_meta = meta.get("shards", {})
    target_leaves = None
    if target is not None:
        target_leaves = jax.tree_util.tree_leaves(target)
    leaves = []
    for i, p in enumerate(meta["paths"]):
        if p in flat:
            leaves.append(flat[p])
        elif p in shard_meta:
            tl = (
                target_leaves[i]
                if target_leaves is not None
                and i < len(target_leaves)
                else None
            )
            leaves.append(
                _reassemble_sharded(p, shard_meta[p], flat, tl)
            )
        else:
            raise KeyError(f"state leaf {p!r} missing from staged data")
    return jax.tree_util.tree_unflatten(treedef, leaves)


def restore_to_shardings(state: Any, target: Any) -> Any:
    """device_put a host-restored state onto `target`'s shardings —
    the re-shard-on-resume path (SURVEY.md §7 'hard parts': elastic
    world resize re-shards checkpointed state onto the new mesh).
    `target` leaves may be live arrays or bare Shardings
    (Accelerated.state_shardings)."""
    import jax

    def _put(host, ref):
        sharding = _leaf_sharding(ref)
        if sharding is not None:
            return jax.device_put(host, sharding)
        return host

    return jax.tree_util.tree_map(_put, state, target)


# ---------------------------------------------------------------------------
# engine
# ---------------------------------------------------------------------------


class CheckpointEngine:
    """Save/load a jax pytree with memory staging + async persistence."""

    def __init__(
        self,
        checkpoint_dir: str,
        storage: Optional[CheckpointStorage] = None,
        job_name: Optional[str] = None,
        node_rank: Optional[int] = None,
        local_saver: bool = True,
        replica_manager=None,
        max_to_keep: int = 0,
    ):
        self.checkpoint_dir = checkpoint_dir
        # >0: keep only the newest N committed step dirs
        # (KeepLatestStepStrategy applied by whichever saver commits)
        self.max_to_keep = max_to_keep
        self.replica_manager = replica_manager
        self._replica_thread = None
        self._backup_lock = threading.Lock()
        self._pending_backup = None  # latest-wins parked backup
        self._staging_thread = None
        self._staging_error = None
        self._saved_once = False  # a process's first save costs more
        self.storage = storage or get_checkpoint_storage()
        self.job_name = job_name or os.environ.get(
            NodeEnv.JOB_NAME, "default"
        )
        self.node_rank = (
            node_rank
            if node_rank is not None
            else int(os.environ.get(NodeEnv.NODE_RANK, 0))
        )
        self._has_agent = server_alive(self.job_name)
        self._local_saver = None
        if self._has_agent:
            self.shm_handler = SharedMemoryHandler(
                self.job_name, self.node_rank
            )
            self.event_queue = SharedQueue(
                CKPT_QUEUE_NAME, self.job_name
            )
        elif local_saver:
            # no agent on this host (bare script): run the IPC server +
            # saver thread in-process so the API still works.
            from dlrover_tpu.agent.ckpt_saver import AsyncCheckpointSaver
            from dlrover_tpu.common.multi_process import LocalSocketServer

            self._ipc = LocalSocketServer(self.job_name)
            self._ipc.start()
            self._local_saver = AsyncCheckpointSaver(
                job_name=self.job_name,
                node_rank=self.node_rank,
                storage=self.storage,
            )
            self._local_saver.start()
            self.shm_handler = SharedMemoryHandler(
                self.job_name, self.node_rank
            )
            self.event_queue = SharedQueue(
                CKPT_QUEUE_NAME, self.job_name
            )
        else:
            raise RuntimeError(
                f"no agent IPC server for job {self.job_name!r}"
            )

    # ---- save ------------------------------------------------------------

    def save_to_memory_async(self, step: int, state: Any) -> float:
        """Async staging: snapshot the pytree on-device (an HBM→HBM copy,
        milliseconds), then device→host DMA + shm write in a background
        thread. Returns blocking seconds — the snapshot dispatch only.

        TPU-first design point: jax arrays are immutable, so the
        snapshot only exists to decouple from buffer *donation* by the
        next train_step; training proceeds the moment the copy is
        enqueued. This is the reference's 0.2 s-class stall
        (docs/blogs/flash_checkpoint.md:401-408) without even the D2H
        wait on the critical path.
        """
        import jax
        import jax.numpy as jnp

        t0 = time.monotonic()
        # previous staging still in flight: wait (bounds shm churn and
        # keeps at most one extra state copy in HBM); surfaces any
        # failure of that staging rather than silently dropping it
        self.wait_for_staging()
        snap = jax.tree_util.tree_map(jnp.copy, state)

        def _stage():
            try:
                self._stage_to_shm(step, snap)
            except Exception as e:  # noqa: BLE001
                logger.exception("async checkpoint staging failed")
                self._staging_error = e
            finally:
                # this thread dies now — drop its IPC connections so
                # the server isn't left holding a parked handler per
                # checkpoint at high save frequency
                self.shm_handler.close_thread_conns()

        self._staging_thread = threading.Thread(target=_stage, daemon=True)
        self._staging_thread.start()
        return time.monotonic() - t0

    def wait_for_staging(self):
        """Block until the last save_to_memory_async has hit shm.
        Raises if that staging failed (the checkpoint never landed)."""
        t = self._staging_thread
        if t is not None:
            t.join()
        err = self._staging_error
        if err is not None:
            self._staging_error = None
            raise RuntimeError(
                "async checkpoint staging failed; the last "
                "save_to_memory_async never reached shm"
            ) from err

    def save_to_memory(self, step: int, state: Any) -> float:
        """Stage state into shm; returns blocking seconds (the extent
        of the `ckpt.save` span). One log line per save gives its legs
        (docs/QUICKSTART.md)."""
        first = int(not self._saved_once)
        with trace.span("ckpt.save", step=int(step), first=first) as sp:
            # an in-flight async staging must land first — otherwise
            # the older async snapshot could overwrite this newer state
            # in shm (and a queued DISK persist for this step would be
            # skipped)
            self.wait_for_staging()
            self._stage_to_shm(step, state)
        self._saved_once = True
        _log_legs("save", step, sp, f"first_save={first}")
        return sp.dur_s

    def _stage_to_shm(self, step: int, state: Any) -> None:
        flat, aux = flatten_state(state)
        with self.shm_handler.lock:
            self.shm_handler.save_flat_state(
                step, flat, save_path=self.checkpoint_dir, aux=aux
            )
        if self.replica_manager is not None:
            # ship the replica off-host in the background (replica.py:
            # the reference backs up to a peer's shm asynchronously
            # too). If the previous backup is still in flight (e.g. a
            # network partition is stalling its RPCs), park this state
            # in a latest-wins slot the backup thread drains — never
            # block the milliseconds fast path, never leave the
            # replica stale after the partition heals.
            with self._backup_lock:
                if (
                    self._replica_thread is None
                    or not self._replica_thread.is_alive()
                ):
                    self._pending_backup = None
                    self._replica_thread = threading.Thread(
                        target=self._backup_drain,
                        args=(step, flat, aux),
                        daemon=True,
                    )
                    self._replica_thread.start()
                else:
                    logger.info(
                        "replica backup for step %d parked "
                        "(previous still in flight; latest wins)",
                        step,
                    )
                    self._pending_backup = (step, flat, aux)

    def _backup_drain(self, step: int, flat, aux) -> None:
        """Backup-thread body: ship the given state, then keep
        draining whatever newer state was parked while shipping."""
        while True:
            try:
                self.replica_manager.backup(step, flat, aux)
            except Exception:  # noqa: BLE001 — replica is best-effort
                logger.warning(
                    "replica backup for step %d failed", step,
                    exc_info=True,
                )
            with self._backup_lock:
                if self._pending_backup is None:
                    # exit decision and the saver's liveness check
                    # share this lock: clear the thread slot HERE so
                    # a save racing our exit sees "no drain running"
                    # and starts a fresh thread instead of parking a
                    # backup nobody will ever drain
                    if (
                        self._replica_thread
                        is threading.current_thread()
                    ):
                        self._replica_thread = None
                    return
                step, flat, aux = self._pending_backup
                self._pending_backup = None

    def save_to_storage(self, step: int, state: Any) -> float:
        """Stage + queue async persist (reference save_to_storage)."""
        blocked = self.save_to_memory(step, state)
        event = {"step": step, "path": self.checkpoint_dir}
        if self.max_to_keep:
            # the saver (agent process) owns the storage that commits —
            # the retention policy rides the event to it
            event["max_to_keep"] = self.max_to_keep
        self.event_queue.put(event)
        return blocked

    # ---- load ------------------------------------------------------------

    def load_from_memory(
        self, target: Any = None
    ) -> Tuple[int, Optional[Any]]:
        # the shared lock keeps a concurrent writer resize (save path)
        # from tearing this read — the saver takes it too
        with self.shm_handler.lock:
            meta, flat = self.shm_handler.load_flat_state()
        if meta is None or meta.step < 0:
            return -1, None
        return meta.step, unflatten_state(flat, meta.aux, target)

    def load_from_storage(
        self, step: Optional[int] = None, target: Any = None
    ) -> Tuple[int, Optional[Any]]:
        if step is None:
            step = read_tracker_step(self.storage, self.checkpoint_dir)
        if step < 0:
            return -1, None
        step_dir = os.path.join(self.checkpoint_dir, str(step))
        listing = self.storage.listdir(step_dir) or []
        aux = self.storage.read(
            os.path.join(step_dir, f"aux_{self.node_rank}.pkl")
        )
        # fast path: rank-local shard file + own aux only. When the mesh
        # is unchanged each host needs exactly the shards it staged, so
        # skip materializing every peer's host_*.npz (O(model size) host
        # RAM per host on shared storage). Falls back to the full merge
        # when local shards don't cover the restore sharding.
        if aux is not None:
            own = self.storage.read(
                os.path.join(step_dir, f"host_{self.node_rank}.npz")
            )
            if own is not None:
                local_flat = _extract_npz(own)
                try:
                    return step, unflatten_state(
                        local_flat, aux, target
                    )
                except KeyError:
                    logger.info(
                        "rank-local restore of step %d does not cover "
                        "the restore sharding; merging all host files",
                        step,
                    )
        if aux is None:
            # a host added by a scale-up has no aux of its own — any
            # peer's aux carries the same treedef/paths
            for n in listing:
                if n.startswith("aux_"):
                    aux = self.storage.read(
                        os.path.join(step_dir, n)
                    )
                    if aux is not None:
                        break
        if aux is None:
            return -1, None
        # merge every host's shard + aux file visible on this storage
        # (shared filesystems expose all of them → full shard coverage,
        # with per-host shard indices unioned from the aux files, lets a
        # DIFFERENT mesh restore; local disk sees just our own, which
        # the target-placement path handles)
        flat: Dict[str, np.ndarray] = {}
        names = [
            n
            for n in listing
            if n.startswith("host_") and n.endswith(".npz")
        ] or [f"host_{self.node_rank}.npz"]
        # fan the per-host shard reads over a pool (I/O-bound against
        # shared storage). read+extract happen inside the task so at
        # most pool-width blobs are alive at once — list()-ing all
        # reads first would hold every host's blob simultaneously
        # (node_count x shard_size peak RAM on a recovering node)
        def _read_extract(name):
            blob = self.storage.read(os.path.join(step_dir, name))
            return _extract_npz(blob) if blob is not None else {}

        if len(names) > 1:
            from concurrent.futures import ThreadPoolExecutor

            with ThreadPoolExecutor(
                min(RESTORE_THREADS, len(names))
            ) as pool:
                for part in pool.map(_read_extract, names):
                    flat.update(part)
        else:
            flat.update(_read_extract(names[0]))
        if not flat:
            return -1, None
        aux = _merge_aux(
            aux,
            [
                self.storage.read(os.path.join(step_dir, n))
                for n in listing
                if n.startswith("aux_")
                and n != f"aux_{self.node_rank}.pkl"
            ],
        )
        return step, unflatten_state(flat, aux, target)

    def load(
        self, target: Any = None
    ) -> Tuple[int, Optional[Any]]:
        """Memory-first restore (reference engine.load :427): shm wins
        if its step >= the tracker's; else read storage. If `target`
        is given, the restored host state is device_put onto its
        shardings."""
        with trace.span("ckpt.restore") as sp:
            step, state = self._load(target)
        if state is not None:
            _log_legs("restore", step, sp)
        return step, state

    def _load(self, target: Any) -> Tuple[int, Optional[Any]]:
        # compare steps BEFORE paying for any unflatten/device_put
        shm_meta = self.shm_handler.get_meta()
        mem_step = shm_meta.step if shm_meta is not None else -1
        disk_step = read_tracker_step(self.storage, self.checkpoint_dir)
        step, state = -1, None
        if mem_step >= 0 and mem_step >= disk_step:
            try:
                step, state = self.load_from_memory(target)
            except (KeyError, ValueError, ShmIntegrityError) as e:
                # shm shards don't cover the (resized) mesh, or the
                # mapping is stale/torn across a writer resize — fall
                # back to storage, whose merged shard files re-shard
                # fully. Crash-looping here strands a job whose disk
                # checkpoint is fine (round-3 postmortem).
                logger.warning(
                    "shm restore failed (%s); falling back to storage", e
                )
                step, state = -1, None
        tried_replica = False
        if state is None and self.replica_manager is not None:
            # respawn path: a survivor-held replica is DRAM on the
            # master — when it's at least as fresh as the tracker, pull
            # it BEFORE paying the storage round-trip (reference
            # replica.py:193 gathers the lost shard from the peer's shm
            # first; storage is the slow path, not the first resort)
            rstep = self.replica_manager.peek_step()
            if rstep >= 0 and rstep >= disk_step:
                tried_replica = True
                try:
                    step, state = self.replica_manager.restore_state(
                        target=target
                    )
                except (
                    KeyError, ValueError, ConnectionError, OSError,
                ) as e:
                    # the replica carries the same flatten as shm, so
                    # a resized mesh fails its unflatten the same way
                    # — fall through to storage (merged shards cover
                    # any mesh) instead of crash-looping (r3
                    # postmortem, same guard as the shm path above).
                    # ConnectionError/OSError: the replica lives on
                    # the MASTER (kv_get raises ConnectionError when
                    # it is unreachable) — a control-plane outage
                    # between peek_step and the chunk fetch must fall
                    # through to storage, not crash the restore
                    logger.warning(
                        "replica restore failed (%s); "
                        "falling back to storage",
                        e,
                    )
                    step, state = -1, None
                if state is not None:
                    logger.info(
                        "restored step %d from replica "
                        "(fresher than storage step %d)",
                        step,
                        disk_step,
                    )
        if state is None:
            step, state = self.load_from_storage(
                disk_step if disk_step >= 0 else None, target
            )
        if (
            state is None
            and self.replica_manager is not None
            and not tried_replica
        ):
            # storage had nothing readable and the replica is older
            # than the tracker claimed — still better than no state
            try:
                step, state = self.replica_manager.restore_state(
                    target=target
                )
            except (
                KeyError, ValueError, ConnectionError, OSError,
            ) as e:
                # same guard as above: an unreachable master is a
                # missing replica, not a fatal restore error
                logger.warning("replica restore failed (%s)", e)
                step, state = -1, None
            if state is not None:
                logger.info("restored step %d from replica", step)
        if state is not None and target is not None:
            import jax

            # the transfers are waited for inside the span, so that the
            # leg is the copy and not only its dispatch
            with trace.span("ckpt.h2d"):
                state = jax.block_until_ready(
                    restore_to_shardings(state, target)
                )
        return step, state

    def wait_for_persist(
        self, step: int, timeout: float = 60.0
    ) -> bool:
        """Block until `step` is committed to storage (tests/shutdown)."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if (
                read_tracker_step(self.storage, self.checkpoint_dir)
                >= step
            ):
                return True
            time.sleep(0.05)
        return False

    def close(self):
        t = self._staging_thread
        if t is not None and t.is_alive():
            # let an in-flight async staging land rather than tear the
            # saver/IPC down under it (the checkpoint would be lost)
            t.join(timeout=30.0)
        # snapshot under the lock: _backup_drain nulls the slot from
        # its own thread on exit, so unsynchronized attribute reads
        # here can see None between the check and the join
        with self._backup_lock:
            rt = self._replica_thread
        if rt is not None and rt.is_alive():
            # let an in-flight backup commit rather than die mid-write
            rt.join(timeout=30.0)
        if self._local_saver is not None:
            self._local_saver.stop()
            self._ipc.stop()


class Checkpointer:
    """User-facing API (reference checkpointer.py:23).

    save_checkpoint(step, state, storage_type=MEMORY) stages to host shm
    in ~milliseconds; DISK additionally persists asynchronously. The
    last MEMORY state survives training-process crashes because the shm
    segment + saver live with the agent.
    """

    def __init__(self, checkpoint_dir: str, **kw):
        self.engine = CheckpointEngine(checkpoint_dir, **kw)

    def save_checkpoint(
        self,
        step: int,
        state: Any,
        storage_type: str = StorageType.DISK,
    ) -> float:
        if storage_type == StorageType.MEMORY:
            return self.engine.save_to_memory(step, state)
        return self.engine.save_to_storage(step, state)

    def load_checkpoint(
        self, target: Any = None
    ) -> Tuple[int, Optional[Any]]:
        return self.engine.load(target)

    def wait_latest_checkpoint(self, step: int, timeout: float = 60.0):
        return self.engine.wait_for_persist(step, timeout)

    def close(self):
        self.engine.close()
