"""Platform helpers: backend selection + device facts."""

import os

FORCE_CPU_ENV = "DLROVER_TPU_FORCE_CPU"


def ensure_cpu_if_forced():
    """The repo's own way to pin a spawned worker to the CPU in tests:
    with DLROVER_TPU_FORCE_CPU=1 in its environment, a process that
    calls this before any backend use runs on the CPU platform."""
    if os.environ.get(FORCE_CPU_ENV) != "1":
        return
    import jax

    try:
        jax.config.update("jax_platforms", "cpu")
    except Exception:  # noqa: BLE001 — backend already initialized
        pass


def backend_name() -> str:
    import jax

    return jax.default_backend()


def is_tpu() -> bool:
    return backend_name() == "tpu"
