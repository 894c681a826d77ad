"""Elastic-agent tests: worker supervision, restart-on-failure, and the
fault-injection tier (kill a worker process, assert recovery) — mirrors
dlrover/python/tests/test_elastic_training_agent.py + the chaos scenarios
(SURVEY.md §4 tier 3).
"""

import os
import signal
import sys
import textwrap
import time

import pytest

from dlrover_tpu.agent.master_client import MasterClient
from dlrover_tpu.agent.training import (
    ElasticLaunchConfig,
    ElasticTrainingAgent,
    MasterRendezvousHandler,
)
from dlrover_tpu.common.constants import NodeEnv, NodeStatus
from dlrover_tpu.master.master import LocalJobMaster


@pytest.fixture()
def master():
    m = LocalJobMaster(num_nodes=1)
    m.start()
    yield m
    m.stop()


@pytest.fixture()
def client(master):
    c = MasterClient(master.addr, node_id=0, node_type="worker")
    yield c
    c.close()


def _script(tmp_path, body: str) -> str:
    path = tmp_path / "worker.py"
    path.write_text(textwrap.dedent(body))
    return str(path)


def _agent(config, script, client):
    return ElasticTrainingAgent(
        config, [sys.executable, script], client
    )


class TestRendezvousHandler:
    def test_next_rendezvous_assigns_rank(self, client):
        h = MasterRendezvousHandler(client, timeout=10)
        rnd, rank, world = h.next_rendezvous(
            local_world_size=2, node_addr="127.0.0.1:9999"
        )
        assert rnd == 1
        assert rank == 0
        assert world[0] == (0, 2, "127.0.0.1:9999")


class TestAgentLifecycle:
    def test_successful_worker(self, tmp_path, client, master):
        script = _script(tmp_path, "print('ok')")
        config = ElasticLaunchConfig(monitor_interval=0.1)
        agent = _agent(config, script, client)
        assert agent.run() == 0
        node = master.servicer.node_manager.get_node("worker", 0)
        assert node.status == NodeStatus.SUCCEEDED

    def test_worker_env_propagated(self, tmp_path, client):
        out = tmp_path / "env.txt"
        script = _script(
            tmp_path,
            f"""
            import os
            keys = ["{NodeEnv.NODE_RANK}", "{NodeEnv.NODE_NUM}",
                    "{NodeEnv.COORDINATOR_ADDR}", "{NodeEnv.MASTER_ADDR}"]
            with open({str(out)!r}, "w") as f:
                f.write(",".join(os.environ.get(k, "MISSING") for k in keys))
            """,
        )
        config = ElasticLaunchConfig(monitor_interval=0.1)
        agent = _agent(config, script, client)
        assert agent.run() == 0
        rank, num, coord, addr = out.read_text().split(",")
        assert rank == "0"
        assert num == "1"
        assert ":" in coord
        assert addr == client._stub.addr

    def test_restart_on_failure_then_succeed(self, tmp_path, client):
        """Worker fails on first run, succeeds after restart — the
        process-restart recovery path (reference ~75% of faults)."""
        marker = tmp_path / "attempt"
        script = _script(
            tmp_path,
            f"""
            import os, sys
            marker = {str(marker)!r}
            if not os.path.exists(marker):
                open(marker, "w").close()
                sys.exit(7)
            """,
        )
        config = ElasticLaunchConfig(max_restarts=2, monitor_interval=0.1)
        agent = _agent(config, script, client)
        assert agent.run() == 0
        assert agent.restart_count == 1

    def test_max_restarts_exceeded(self, tmp_path, client, master):
        script = _script(tmp_path, "import sys; sys.exit(3)")
        config = ElasticLaunchConfig(max_restarts=1, monitor_interval=0.1)
        agent = _agent(config, script, client)
        assert agent.run() == 3
        node = master.servicer.node_manager.get_node("worker", 0)
        assert node.status in (NodeStatus.FAILED, NodeStatus.PENDING)
        # failure was reported to the error monitor
        assert master.servicer.error_monitor.recent()

    def test_kill_signal_recovery(self, tmp_path, client):
        """Chaos tier: worker killed by SIGKILL mid-run recovers
        (reference fault_tolerance_exps.md process-kill scenario)."""
        marker = tmp_path / "attempt"
        script = _script(
            tmp_path,
            f"""
            import os, time
            marker = {str(marker)!r}
            if not os.path.exists(marker):
                open(marker, "w").close()
                os.kill(os.getpid(), 9)
            """,
        )
        config = ElasticLaunchConfig(max_restarts=2, monitor_interval=0.1)
        agent = _agent(config, script, client)
        assert agent.run() == 0
        assert agent.restart_count == 1


class TestElasticRunCLI:
    def test_end_to_end_local(self, tmp_path):
        """dlrover-tpu-run with no master configured: node 0 spawns the
        local master, agent supervises, job succeeds."""
        from dlrover_tpu.trainer.elastic_run import main

        script = tmp_path / "train.py"
        script.write_text("print('trained')\n")
        code = main(
            [
                "--nnodes",
                "1",
                "--max-restarts",
                "1",
                str(script),
            ]
        )
        assert code == 0

    def test_parse_nnodes(self):
        from dlrover_tpu.trainer.elastic_run import parse_nnodes

        assert parse_nnodes("4") == (4, 4)
        assert parse_nnodes("2:8") == (2, 8)


class TestNodeCheck:
    """Pre-flight health check (agent/node_check.py) — previously the
    one agent module with no direct test (PARITY listed this file as
    its prover; now it is)."""

    def test_bench_reports_healthy_and_elapsed(self):
        from dlrover_tpu.agent.node_check import matmul_collective_bench

        ok, elapsed = matmul_collective_bench(size=128, iters=2)
        assert ok is True
        assert elapsed > 0.0

    def test_isolated_bench_subprocess_roundtrip(self):
        # the real subprocess path: spawn, bench, parse verdict — the
        # launcher process itself must never init jax (libtpu is
        # exclusive per process; in-process init would starve the
        # workers launched right after the check)
        from dlrover_tpu.agent.node_check import run_bench_isolated

        ok, elapsed = run_bench_isolated(timeout_s=280.0)
        assert ok is True
        assert elapsed > 0.0

    def test_mock_error_rank_forces_unhealthy_report(self, monkeypatch):
        from dlrover_tpu.agent import node_check
        from dlrover_tpu.common.constants import NodeEnv

        monkeypatch.setenv(NodeEnv.MOCK_ERR_RANK, "3")
        monkeypatch.setenv(NodeEnv.NODE_ID, "3")
        assert node_check._mock_error() is True
        monkeypatch.setenv(NodeEnv.NODE_ID, "1")
        assert node_check._mock_error() is False

    def test_health_check_flow_against_fake_client(self, monkeypatch):
        from dlrover_tpu.agent import node_check

        class FakeClient:
            node_id = 0

            def __init__(self):
                self.reports = []

            def report_network_check(self, normal, elapsed):
                self.reports.append((normal, elapsed))

            def check_fault_nodes(self):
                return []

            def check_stragglers(self):
                return []

        # avoid spawning the real bench subprocess twice in a unit test
        monkeypatch.setattr(
            node_check,
            "run_bench_isolated",
            lambda: (True, 0.01),
        )
        c = FakeClient()
        assert node_check.node_health_check(c) is True
        assert len(c.reports) == 2  # two check rounds
        assert all(normal for normal, _ in c.reports)

    def test_health_check_false_when_marked_faulty(self, monkeypatch):
        from dlrover_tpu.agent import node_check

        class FaultyClient:
            node_id = 2

            def report_network_check(self, normal, elapsed):
                pass

            def check_fault_nodes(self):
                return [2]

            def check_stragglers(self):  # pragma: no cover
                return []

        monkeypatch.setattr(
            node_check,
            "run_bench_isolated",
            lambda: (True, 0.01),
        )
        assert node_check.node_health_check(FaultyClient()) is False


class TestSigtermGracefulLeave:
    def test_sigterm_mid_training_leaves_and_exits_zero(self, tmp_path):
        """A real pod eviction is SIGTERM-with-grace to the launcher:
        the handler must route it to agent.leave() so the run exits
        cleanly (staged shm persisted by run()'s teardown) instead of
        dying mid-supervision."""
        import signal as sig
        import subprocess
        import sys
        import time

        script = tmp_path / "train.py"
        script.write_text(
            "import time\n"
            "print('training-started', flush=True)\n"
            "time.sleep(120)\n"
        )
        import os

        env = {**os.environ, "DLROVER_TPU_FORCE_CPU": "1"}
        proc = subprocess.Popen(
            [
                sys.executable,
                "-m",
                "dlrover_tpu.trainer.elastic_run",
                "--nnodes",
                "1",
                "--max-restarts",
                "1",
                str(script),
            ],
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            text=True,
            env=env,
        )
        try:
            # wait for the worker to actually start training. A reader
            # thread drains stdout so the deadline below actually
            # fires even when the launcher hangs producing NO output
            # (a blocking readline would wait forever).
            import threading

            lines = []
            started = threading.Event()

            def _drain():
                for line in proc.stdout:
                    lines.append(line)
                    if "training-started" in line:
                        started.set()

            t = threading.Thread(target=_drain, daemon=True)
            t.start()
            if not started.wait(timeout=120):
                raise AssertionError(
                    "worker never started: " + "".join(lines)[-2000:]
                )
            proc.send_signal(sig.SIGTERM)
            proc.wait(timeout=90)
            t.join(timeout=10)
            full = "".join(lines)
            assert "graceful leave" in full, full[-2000:]
            assert proc.returncode == 0, (proc.returncode, full[-2000:])
        finally:
            if proc.poll() is None:
                proc.kill()


class TestRendezvousAbort:
    def test_should_stop_aborts_poll_promptly(self):
        """leave()/SIGTERM during a rendezvous poll must abort the
        loop immediately — after the DELETED report this node can
        never join a world, so waiting out rdzv_timeout would burn
        the whole eviction grace period."""
        import time as _time

        import pytest

        from dlrover_tpu.agent.training import (
            MasterRendezvousHandler,
            RendezvousAborted,
        )

        class NeverFormsClient:
            node_id = 0

            def join_rendezvous(self, **kw):
                return 0

            def get_comm_world(self, name):
                return 0, 0, {}

        h = MasterRendezvousHandler(
            NeverFormsClient(),
            timeout=30.0,
            poll_interval=0.05,
            should_stop=lambda: True,
        )
        t0 = _time.monotonic()
        with pytest.raises(RendezvousAborted):
            h.next_rendezvous()
        assert _time.monotonic() - t0 < 5.0


class TestGpt2Example:
    def test_gpt2_example_end_to_end(self, tmp_path):
        """examples/train_gpt2.py through the real launcher (the
        nanoGPT-train parity example, r5 VERDICT missing #5)."""
        import subprocess

        repo = os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))
        )
        env = dict(os.environ)
        env["DLROVER_TPU_JOB_NAME"] = f"gpt2ex-{os.getpid()}"
        env["DLROVER_TPU_FORCE_CPU"] = "1"  # workers stay on the CPU
        env["PYTHONPATH"] = repo + os.pathsep + env.get(
            "PYTHONPATH", ""
        )
        r = subprocess.run(
            [
                sys.executable, "-m",
                "dlrover_tpu.trainer.elastic_run",
                "--nnodes", "1", "--max-restarts", "1",
                os.path.join(repo, "examples", "train_gpt2.py"),
                "--steps", "8",
            ],
            capture_output=True,
            text=True,
            timeout=240,
            env=env,
            cwd=str(tmp_path),
        )
        assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
        assert "done:" in r.stdout
