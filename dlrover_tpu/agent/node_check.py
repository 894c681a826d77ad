"""Pre-flight node health check: compute + collective micro-bench.

Reference parity: NodeCheckElasticAgent training.py:910 (run :951,
_run_node_check :1009), node_health_check :1119, comm_perf_check :1138,
and the device benches dlrover/trainer/torch/node_check/{nvidia_gpu.py,
utils.py:45 bm_allgather, mock_error :36}.

TPU version: the bench runs a jitted bf16 matmul chain (MXU exercise) and
a psum/all_gather over local devices (ICI exercise); elapsed time is
reported to the master's NetworkCheckRendezvousManager, which aggregates
fault/straggler sets across rounds. `MOCK_ERR_RANK` injects a failure for
chaos tests (reference utils.py:36).
"""

import os
import time
from typing import Tuple

from dlrover_tpu.agent.master_client import MasterClient
from dlrover_tpu.common.constants import NodeEnv
from dlrover_tpu.common.log import default_logger as logger


def matmul_collective_bench(
    size: int = 0, iters: int = 8
) -> Tuple[bool, float]:
    """(healthy, elapsed_seconds). Runs on whatever backend is live.

    size=0 picks per backend: 1024 exercises the MXU properly on TPU,
    but bf16 matmuls are EMULATED on the CPU backend — at 1024^3 the
    pre-flight check there takes minutes and reads as a hang (the CPU
    tier is a plumbing smoke, not a hardware bench)."""
    try:
        # honor DLROVER_TPU_FORCE_CPU here too: a check for workers
        # that were pinned to the CPU must not take the chip
        from dlrover_tpu.utils.platform import ensure_cpu_if_forced

        ensure_cpu_if_forced()

        import jax
        import jax.numpy as jnp

        if size == 0:
            size = 1024 if jax.default_backend() != "cpu" else 256

        n_local = jax.local_device_count()

        @jax.jit
        def chain(x):
            for _ in range(4):
                x = jnp.tanh(x @ x)
            return x

        x = jnp.ones((size, size), jnp.bfloat16)
        chain(x).block_until_ready()  # compile outside the timed region

        if n_local > 1:
            import functools

            # axis_name MUST be declared on the pmap: without it the
            # all_gather raises "unbound axis name" on every
            # multi-device host, making the pre-flight check mark
            # healthy nodes faulty (caught by TestNodeCheck — the
            # single-device path never enters this branch)
            @functools.partial(jax.pmap, axis_name="i")
            def allgather(y):
                return jax.lax.all_gather(y, axis_name="i")

            y = jnp.ones((n_local, size // n_local, size), jnp.bfloat16)
            allgather(y).block_until_ready()

        start = time.monotonic()
        for _ in range(iters):
            out = chain(x)
        out.block_until_ready()
        if n_local > 1:
            for _ in range(iters):
                g = allgather(y)
            jax.tree_util.tree_map(
                lambda a: a.block_until_ready(), g
            )
        elapsed = time.monotonic() - start
        return True, elapsed
    except Exception:  # noqa: BLE001 — any device error = unhealthy node
        logger.exception("node check bench failed")
        return False, 0.0


def _mock_error() -> bool:
    """Chaos hook: DLROVER_TPU_MOCK_ERR_RANK=<node_id> fails that node."""
    mock = os.environ.get(NodeEnv.MOCK_ERR_RANK, "")
    node_id = os.environ.get(NodeEnv.NODE_ID, "-1")
    return bool(mock) and mock == node_id


def run_bench_isolated(timeout_s: float = 300.0) -> Tuple[bool, float]:
    """Run the bench in a SHORT-LIVED subprocess and parse its verdict.

    The caller is the long-lived launcher/agent process, and libtpu is
    exclusive per process (the same invariant agent/collector.py keeps:
    the agent must never import jax or it steals the TPU from the
    training process it supervises). In-process jax init here would
    hold the chip past the check and starve the workers launched next;
    the subprocess acquires it, benches, and RELEASES it on exit."""
    import json
    import subprocess
    import sys

    try:
        proc = subprocess.run(
            [sys.executable, "-m", "dlrover_tpu.agent.node_check"],
            capture_output=True,
            text=True,
            timeout=timeout_s,
        )
        for line in reversed(proc.stdout.splitlines()):
            line = line.strip()
            if line.startswith("{"):
                verdict = json.loads(line)
                return bool(verdict["ok"]), float(verdict["elapsed"])
        logger.error(
            "node check subprocess produced no verdict (rc=%d): %s",
            proc.returncode,
            proc.stderr[-500:],
        )
        return False, 0.0
    except Exception:  # noqa: BLE001 — timeout/spawn error = unhealthy
        logger.exception("node check subprocess failed")
        return False, 0.0


def node_health_check(client: MasterClient, config=None) -> bool:
    """Two check rounds against the network-check rendezvous; returns
    False if the master marks this node faulty."""
    for round_idx in range(2):
        normal, elapsed = run_bench_isolated()
        if _mock_error():
            normal, elapsed = False, 0.0
        client.report_network_check(normal=normal, elapsed=elapsed)
        logger.info(
            "node check round %d: normal=%s elapsed=%.3fs",
            round_idx,
            normal,
            elapsed,
        )
    fault_nodes = client.check_fault_nodes()
    if client.node_id in fault_nodes:
        return False
    stragglers = client.check_stragglers()
    if client.node_id in stragglers:
        logger.warning("this node is a straggler (continuing)")
    return True


if __name__ == "__main__":
    # subprocess entry for run_bench_isolated: bench, print verdict
    import json as _json

    _ok, _t = matmul_collective_bench()
    print(_json.dumps({"ok": _ok, "elapsed": _t}), flush=True)
