"""Elastic training agent: per-host supervisor of the JAX worker process.

Reference parity: dlrover/python/elastic_agent/torch/training.py —
`MasterRendezvousHandler` (:182, next_rendezvous :253),
`ElasticTrainingAgent` (:365, _invoke_run :584, _restart_workers :713,
_membership_changed :720), `launch_agent` :780, `ElasticLaunchConfig` :119.

TPU re-design: torchelastic restarts N local ranks and rebuilds NCCL; here
each host runs ONE JAX process (it owns all local TPU chips), and a new
rendezvous round means the agent restarts that process with fresh
`jax.distributed.init` coordinates (coordinator = rank-0 host). The agent —
not the training process — owns the flash-checkpoint staging memory, so a
training-process crash never loses the in-memory checkpoint.
"""

import os
import signal
import subprocess
import sys
import threading
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from dlrover_tpu.agent.master_client import MasterClient
from dlrover_tpu.common import trace
from dlrover_tpu.common.constants import (
    JobConstant,
    NodeEnv,
    NodeStatus,
    TrainingExceptionLevel,
)
from dlrover_tpu.common.log import default_logger as logger
from dlrover_tpu.common.messages import find_free_port
from dlrover_tpu.runtime import MEMBERSHIP_RESTART_EXIT_CODE

CommWorld = Dict[int, Tuple[int, int, str]]


@dataclass
class ElasticLaunchConfig:
    """Reference ElasticLaunchConfig training.py:119, trimmed to the TPU
    shape: one worker process per host."""

    min_nodes: int = 1
    max_nodes: int = 1
    nproc_per_node: int = 1
    max_restarts: int = 3
    monitor_interval: float = (
        JobConstant.TRAINING_AGENT_LOOP_INTERVAL_SECS
    )
    rdzv_timeout: float = JobConstant.RDZV_JOIN_TIMEOUT_DEFAULT
    network_check: bool = False
    node_unit: int = 1
    job_name: str = "job"
    log_dir: Optional[str] = None

    def auto_configure_params(self):
        """Reference :156 — network check implies at least 2 nodes."""
        if self.network_check and self.max_nodes < 2:
            self.network_check = False


class RendezvousAborted(Exception):
    """The agent is stopping (leave/preemption) — abandon the poll."""


class MasterRendezvousHandler:
    """Join the master rendezvous and block for the comm world.

    Reference: MasterRendezvousHandler training.py:182. The returned
    world maps node_rank -> (node_id, local_world_size, node_addr);
    rank 0's addr hosts the jax.distributed coordinator.
    """

    def __init__(
        self,
        client: MasterClient,
        rdzv_name: str = "training",
        timeout: float = JobConstant.RDZV_JOIN_TIMEOUT_DEFAULT,
        poll_interval: float = 0.5,
        should_stop=None,
    ):
        self.client = client
        self.rdzv_name = rdzv_name
        self.timeout = timeout
        self.poll_interval = poll_interval
        # callable checked each poll: a SIGTERM/leave() arriving while
        # the main thread is blocked HERE must abort the poll promptly
        # (after a DELETED report this node can never join a world, so
        # without the check the loop burns the whole rdzv timeout and
        # the eviction grace period with it)
        self.should_stop = should_stop or (lambda: False)

    def next_rendezvous(
        self, local_world_size: int = 1, node_addr: str = ""
    ) -> Tuple[int, int, CommWorld]:
        """Returns (round, node_rank, world). Blocks until the round
        forms; raises TimeoutError on timeout or RendezvousAborted
        when `should_stop` fires mid-poll."""
        # NOTE on the error class: MasterClient wraps EVERY exhausted
        # RPC (grpc.RpcError on each attempt, retries included) in
        # ConnectionError — "control plane unreachable right now".
        # A blackholed control plane must not kill the agent
        # (reference chaos scenario: 100% network loss,
        # fault_tolerance_exps.md:211), so every RPC in this loop
        # retries until the ONE rendezvous deadline bounds the join.
        net_errors = (ConnectionError,)
        deadline = time.monotonic() + self.timeout
        joined = False
        while time.monotonic() < deadline:
            if self.should_stop():
                raise RendezvousAborted(
                    f"rendezvous {self.rdzv_name!r} aborted: agent "
                    "stopping (leave/preemption)"
                )
            if not joined:
                try:
                    self.client.join_rendezvous(
                        local_world_size=local_world_size,
                        rdzv_name=self.rdzv_name,
                        node_addr=node_addr,
                    )
                    joined = True
                except net_errors as e:
                    logger.warning(
                        "rendezvous join RPC failed (%s); retrying "
                        "until the %.0fs deadline", e, self.timeout,
                    )
                    time.sleep(self.poll_interval)
                    continue
            try:
                rnd, _, world = self.client.get_comm_world(
                    self.rdzv_name
                )
            except net_errors as e:
                logger.warning(
                    "rendezvous poll RPC failed (%s); retrying "
                    "until the %.0fs deadline", e, self.timeout,
                )
                time.sleep(self.poll_interval)
                continue
            if world:
                for rank, (nid, _, _) in world.items():
                    if nid == self.client.node_id:
                        return rnd, rank, world
                # round formed without us (node_unit rounding) — rejoin
                # next iteration, after the same pacing sleep as every
                # other branch (a tight rejoin loop would hammer the
                # master while it keeps serving the formed world)
                joined = False
            time.sleep(self.poll_interval)
        raise TimeoutError(
            f"rendezvous {self.rdzv_name!r} did not complete in "
            f"{self.timeout}s"
        )


class WorkerProcess:
    """One supervised training process."""

    def __init__(self, proc: subprocess.Popen, env: Dict[str, str]):
        self.proc = proc
        self.env = env
        self.start_time = time.time()

    def poll(self) -> Optional[int]:
        return self.proc.poll()

    def terminate(self, grace: float = 10.0):
        if self.proc.poll() is not None:
            return
        self.proc.terminate()
        try:
            self.proc.wait(grace)
        except subprocess.TimeoutExpired:
            self.proc.kill()
            self.proc.wait()


class ElasticTrainingAgent:
    """Supervise the local worker; restart on failure or membership change.

    The run loop mirrors reference `_invoke_run` training.py:584:
      1. rendezvous -> world;
      2. start worker with JAX coordination env;
      3. monitor: on FAILED report + (maybe) restart; on master signaling
         waiting nodes (_membership_changed :720), restart into a new
         round; on SUCCEEDED report and exit.
    """

    def __init__(
        self,
        config: ElasticLaunchConfig,
        entrypoint: List[str],
        client: Optional[MasterClient] = None,
        host_addr: str = "127.0.0.1",
    ):
        self.config = config
        self.entrypoint = entrypoint
        self.client = client or MasterClient.singleton()
        self.host_addr = host_addr
        self.rdzv = MasterRendezvousHandler(
            self.client,
            timeout=config.rdzv_timeout,
            should_stop=lambda: self._stop.is_set()
            or self._leave_flag,
        )
        self.worker: Optional[WorkerProcess] = None
        self.restart_count = 0
        self._current_round = 0
        self._stop = threading.Event()
        self._leave_requested = threading.Event()
        # plain bool written by the SIGTERM handler: Event.set()
        # acquires a non-reentrant lock, so a signal landing while the
        # main thread is inside its own _stop bookkeeping could
        # deadlock — the handler stores this flag and the loops
        # promote it to the Events (_promote_signal_flags)
        self._leave_flag = False
        self._heartbeat_thread: Optional[threading.Thread] = None
        self._coordinator_port = find_free_port()
        # flash-checkpoint plumbing: the agent owns the IPC server, the
        # shm staging segment and the async saver so checkpoints survive
        # trainer crashes (reference AsyncCheckpointSaver in the agent,
        # ckpt_saver.py:345)
        from dlrover_tpu.agent.ckpt_saver import AsyncCheckpointSaver
        from dlrover_tpu.common.multi_process import LocalSocketServer

        self._ipc = LocalSocketServer(config.job_name)
        self._ipc.start()
        self.ckpt_saver = AsyncCheckpointSaver(
            job_name=config.job_name,
            node_rank=0,
            master_client=self.client,
        )
        self.ckpt_saver.start()
        # diagnosis data collectors: log windows + chip metrics pushed
        # into the master's inference chain (reference
        # elastic_agent/datacollector/*)
        from dlrover_tpu.agent.collector import (
            ChipMetricsCollector,
            CollectorRunner,
            TrainingLogCollector,
        )

        self.collectors = CollectorRunner(
            self.client,
            [
                TrainingLogCollector(config.log_dir),
                ChipMetricsCollector(),
            ],
        )

    # ---- heartbeats ------------------------------------------------------

    def _heartbeat_loop(self):
        master_session = ""
        while not self._stop.is_set():
            try:
                resp = self.client.report_heart_beat()
                if resp.action == "stop":
                    logger.info("master requested stop")
                    self._stop.set()
                session = getattr(resp, "master_session", "")
                if session and session != master_session:
                    if master_session:
                        # a DIFFERENT master answered: the old one died
                        # and the platform relaunched it with empty
                        # state — put this node back on its books
                        logger.warning(
                            "master restarted (session %s -> %s); "
                            "re-registering",
                            master_session,
                            session,
                        )
                    # re-register on the FIRST observed session too:
                    # the master may have restarted between our
                    # register_node() and this heartbeat (registration
                    # is idempotent, so the common case costs one RPC)
                    self._on_master_restart()
                    master_session = session
            except Exception:  # noqa: BLE001
                logger.warning("heartbeat failed", exc_info=True)
            self._wait_stop(JobConstant.HEARTBEAT_INTERVAL_SECS)

    def _on_master_restart(self):
        """Re-establish this agent's state on a fresh master: node
        registration + live status. Worker-held state re-flows on its
        own (sharding clients re-register datasets on unknown-dataset
        replies; rendezvous re-forms on the next membership change)."""
        try:
            self.client.register_node()
            if self.worker is not None and self.worker.poll() is None:
                self.client.report_node_status(NodeStatus.RUNNING)
        except Exception:  # noqa: BLE001
            logger.warning("master-restart re-register failed",
                           exc_info=True)

    def _start_heartbeats(self):
        if self._heartbeat_thread is None:
            self._heartbeat_thread = threading.Thread(
                target=self._heartbeat_loop,
                name="agent-heartbeat",
                daemon=True,
            )
            self._heartbeat_thread.start()

    # ---- worker lifecycle ------------------------------------------------

    def _worker_env(
        self, rnd: int, node_rank: int, world: CommWorld
    ) -> Dict[str, str]:
        """JAX coordination env for the worker process. The coordinator
        lives on the rank-0 host at a port the rank-0 agent allocated and
        published in its rendezvous addr ("host:port")."""
        coord_addr = world[0][2]
        num_procs = len(world)
        env = dict(os.environ)
        if env.get("DLROVER_TPU_FORCE_CPU") == "1":
            # CPU-forced workers (tests, local sim)
            env["JAX_PLATFORMS"] = "cpu"
        env.update(
            {
                NodeEnv.JOB_NAME: self.config.job_name,
                NodeEnv.MASTER_ADDR: self.client._stub.addr,
                NodeEnv.NODE_ID: str(self.client.node_id),
                NodeEnv.NODE_RANK: str(node_rank),
                NodeEnv.NODE_NUM: str(num_procs),
                NodeEnv.COORDINATOR_ADDR: coord_addr,
                NodeEnv.RESTART_COUNT: str(self.restart_count),
                "DLROVER_TPU_RDZV_ROUND": str(rnd),
            }
        )
        # workers may run with any cwd: make sure they can import the
        # package the agent itself was loaded from
        pkg_root = os.path.dirname(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        )
        pp = env.get("PYTHONPATH", "")
        if pkg_root not in pp.split(os.pathsep):
            env["PYTHONPATH"] = (
                f"{pkg_root}{os.pathsep}{pp}" if pp else pkg_root
            )
        return env

    def _start_worker(self) -> Tuple[int, CommWorld]:
        node_addr = f"{self.host_addr}:{self._coordinator_port}"
        rnd, node_rank, world = self.rdzv.next_rendezvous(
            local_world_size=self.config.nproc_per_node,
            node_addr=node_addr,
        )
        env = self._worker_env(rnd, node_rank, world)
        log_path = None
        stdout = stderr = None
        if self.config.log_dir:
            os.makedirs(self.config.log_dir, exist_ok=True)
            log_path = os.path.join(
                self.config.log_dir,
                f"worker_{node_rank}_r{self.restart_count}.log",
            )
            stdout = open(log_path, "ab")
            stderr = subprocess.STDOUT
        self.ckpt_saver.update_topology(node_rank, len(world))
        proc = subprocess.Popen(
            self.entrypoint,
            env=env,
            stdout=stdout,
            stderr=stderr,
        )
        self.worker = WorkerProcess(proc, env)
        self._current_round = rnd
        self.client.report_node_status(NodeStatus.RUNNING)
        logger.info(
            "started worker pid=%d rank=%d world=%d round=%d%s",
            proc.pid,
            node_rank,
            len(world),
            rnd,
            f" log={log_path}" if log_path else "",
        )
        return rnd, world

    def _stop_worker(self):
        if self.worker is not None:
            self.worker.terminate()
            self.worker = None

    def _membership_changed(self) -> bool:
        """Reference _membership_changed training.py:720 — extended
        with world-invalidation: if a member of our current world died,
        the master cleared the world (rendezvous.remove_node) and every
        survivor must re-rendezvous (SPMD workers cannot outlive their
        world)."""
        try:
            st = self.client.rdzv_state()
        except Exception:  # noqa: BLE001
            return False
        if st.waiting_num > 0:
            return True
        if st.round > self._current_round:
            return True  # a newer round formed without us
        return (
            st.round == self._current_round
            and self._current_round > 0
            and st.world_size == 0
        )

    def _restart_worker(self) -> Tuple[int, CommWorld]:
        """Reference _restart_workers :713.

        EVERY restart flavor persists any staged shm checkpoint first —
        the reference does the same (training.py:674,713). Membership
        restarts (scale-down / re-rendezvous) are the path that loses
        data otherwise: N MEMORY-only saves since the last DISK commit
        would roll training back to the old disk step. The saver skips
        stale steps, so this is a no-op when shm already hit storage."""
        with trace.span("agent.persist", path="restart") as persist:
            try:
                self.ckpt_saver.save_shm_to_storage()
            except Exception:  # noqa: BLE001
                logger.exception("pre-restart checkpoint persist failed")
        with trace.span("agent.respawn") as respawn:
            self._stop_worker()
            started = self._start_worker()
        logger.info(
            "worker restart: persist %.1f ms, respawn %.1f ms "
            "(stop, rendezvous, start)",
            persist.dur_s * 1e3, respawn.dur_s * 1e3,
        )
        return started

    # ---- main loop -------------------------------------------------------

    def run(self) -> int:
        self._start_heartbeats()
        self.collectors.start()
        self.client.register_node()
        try:
            rnd, world = self._start_worker()
            return self._monitor_loop()
        except RendezvousAborted:
            # leave()/SIGTERM landed while blocked in a rendezvous
            # poll — a graceful exit, not a failure; the finally below
            # still persists any staged shm
            logger.info("agent stopping during rendezvous — exiting")
            return 0
        finally:
            self._promote_signal_flags()  # a late SIGTERM only set the bool
            self._stop.set()
            self.collectors.stop()
            self._stop_worker()
            # last duty before teardown: any staged-but-uncommitted shm
            # checkpoint goes to shared storage. This is the leave()/
            # scale-down path's durability guarantee — this host's final
            # MEMORY-only step may exist nowhere else (reference
            # persists shm on every restart flavor, training.py:674,713)
            try:
                self.ckpt_saver.save_shm_to_storage()
            except Exception:  # noqa: BLE001
                logger.exception("teardown checkpoint persist failed")
            if self._leave_requested.is_set():
                # signal-requested leave: the handler only set flags
                # (anything heavier could deadlock on locks its own
                # interrupted frame holds); the DELETED report happens
                # here, AFTER the persist above, with one short
                # attempt so a blackholed master cannot eat the grace
                try:
                    self.client.report_node_status(
                        NodeStatus.DELETED,
                        "preempted",
                        timeout=5.0,
                        retries=1,
                    )
                except Exception:  # noqa: BLE001
                    logger.warning("leave report failed", exc_info=True)
            self.ckpt_saver.stop()
            self._ipc.stop()

    def _monitor_loop(self) -> int:
        while not self._stop.is_set():
            # one poll period: where it ends with an exit code, its
            # extent bounds how long the exit went unnoticed
            with trace.span("agent.detect") as detect:
                self._wait_stop(self.config.monitor_interval)
                if self._stop.is_set():
                    break
                # snapshot: leave() (another thread / in-process E2E
                # callers) nulls self.worker concurrently
                w = self.worker
                code = w.poll() if w else None
                if code is not None:
                    detect.set(exit_code=code)
            if code is None:
                if self._membership_changed():
                    logger.info(
                        "membership change detected — restarting worker "
                        "into a new rendezvous round"
                    )
                    self.restart_count += 1
                    self._restart_worker()
                continue
            if code == 0:
                logger.info("worker succeeded")
                self.client.report_node_status(NodeStatus.SUCCEEDED)
                return 0
            if code == MEMBERSHIP_RESTART_EXIT_CODE:
                # the worker's MembershipWatch saw the world go stale
                # and exited voluntarily — re-rendezvous immediately;
                # this is elasticity, not a failure (no restart budget)
                logger.info(
                    "worker requested membership restart (code %d)",
                    code,
                )
                self._restart_worker()
                continue
            # failure path: persist any staged shm checkpoint first
            # (reference _save_ckpt_to_storage training.py:674)
            logger.warning(
                "worker exited with code %d (noticed within %.1f ms)",
                code, detect.dur_s * 1e3,
            )
            with trace.span("agent.persist", path="crash") as persist:
                try:
                    self.ckpt_saver.save_shm_to_storage()
                except Exception:  # noqa: BLE001
                    logger.exception(
                        "crash-path checkpoint persist failed"
                    )
            logger.info(
                "crash-path checkpoint persist: %.1f ms",
                persist.dur_s * 1e3,
            )
            self.client.report_failure(
                f"worker exit code {code}",
                TrainingExceptionLevel.PROCESS_ERROR,
                self.restart_count,
            )
            if self.restart_count >= self.config.max_restarts:
                # fatal_error marks the node unrecoverable on the master
                # (reference: _should_relaunch dist_job_manager.py:593)
                self.client.report_node_status(
                    NodeStatus.FAILED, "fatal_error"
                )
                return code
            self.restart_count += 1
            logger.info(
                "restarting worker (%d/%d)",
                self.restart_count,
                self.config.max_restarts,
            )
            self._restart_worker()
        self._stop_worker()
        return 0

    def stop(self):
        self._stop.set()

    def request_leave(self):
        """Async-signal-safe leave trigger: stores ONE plain bool and
        returns. The monitor loop promotes it to the Events, run()
        unwinds, and the teardown persists the staged shm then reports
        DELETED. A signal handler must not call leave() directly (its
        persist would deadlock on the saver's commit lock if the
        signal interrupted a persist on this same thread) and must not
        touch threading.Event either — Event.set() acquires a
        non-reentrant condition lock the interrupted frame may already
        hold."""
        self._leave_flag = True

    def _promote_signal_flags(self):
        """Thread-context half of request_leave: lift the bool the
        signal handler stored into the Events every loop tick."""
        if self._leave_flag and not self._leave_requested.is_set():
            self._leave_requested.set()
            self._stop.set()

    def _wait_stop(self, timeout: float) -> bool:
        """_stop.wait(timeout) in sub-second slices, promoting signal
        flags between slices so a SIGTERM interrupts the wait within
        ~0.2 s instead of a full interval."""
        deadline = time.monotonic() + timeout
        while True:
            self._promote_signal_flags()
            left = deadline - time.monotonic()
            if left <= 0:
                return self._stop.is_set()
            if self._stop.wait(min(0.2, left)):
                return True

    def leave(self):
        """Graceful departure (preemption notice / scale-down): stop
        supervising, persist the staged checkpoint, then tell the
        master this node is gone so it invalidates the rendezvous
        world — survivors re-rendezvous instead of hanging on our
        collectives. The TPU analogue of a SIGTERM-with-grace pod
        eviction. Order matters twice over: stop first so the monitor
        loop cannot re-join the rendezvous after the DELETED report
        cleaned us out of it, and PERSIST BEFORE REPORTING — the
        eviction grace is finite, and a blackholed master (whole-job
        eviction) must not burn it ahead of the one action that makes
        this host's final MEMORY-only step durable. The report itself
        is a single short attempt for the same reason; run()'s
        teardown re-persists harmlessly (the saver skips stale
        steps)."""
        self.stop()
        self._stop_worker()
        try:
            self.ckpt_saver.save_shm_to_storage()
        except Exception:  # noqa: BLE001
            logger.exception("leave-path checkpoint persist failed")
        try:
            self.client.report_node_status(
                NodeStatus.DELETED, "preempted", timeout=5.0, retries=1
            )
        except Exception:  # noqa: BLE001 — master may be gone too
            logger.warning("leave report failed", exc_info=True)


def launch_agent(
    config: ElasticLaunchConfig,
    entrypoint: List[str],
    master_addr: str,
    node_id: int = 0,
    host_addr: str = "127.0.0.1",
) -> int:
    """Reference launch_agent training.py:780: build client + agent, run
    optional pre-flight node check, then supervise training."""
    config.auto_configure_params()
    client = MasterClient(master_addr, node_id=node_id)
    if config.network_check:
        from dlrover_tpu.agent.node_check import node_health_check

        ok = node_health_check(client, config)
        if not ok:
            logger.error("node failed pre-flight health check")
            client.report_node_status(NodeStatus.FAILED, "hardware_error")
            return 3
    agent = ElasticTrainingAgent(
        config, entrypoint, client, host_addr=host_addr
    )

    # pod eviction / preemption notice arrives as SIGTERM-with-grace:
    # route it to leave() so the monitor loop exits and run()'s
    # teardown persists the staged shm checkpoint (this host's final
    # MEMORY-only step may exist nowhere else) before the process
    # dies. Without the handler the default action kills the agent
    # mid-supervision and survivors stall until heartbeat timeout.
    # Reference: --save_at_breakpoint / torch agent shutdown path.
    def _graceful_leave(signum, frame):  # noqa: ARG001
        logger.info("SIGTERM — graceful leave (preemption notice)")
        agent.request_leave()

    try:
        signal.signal(signal.SIGTERM, _graceful_leave)
    except ValueError:
        # not the main thread (embedded/test callers) — skip wiring;
        # such callers drive leave() themselves
        logger.warning("not main thread; SIGTERM leave not installed")
    return agent.run()
