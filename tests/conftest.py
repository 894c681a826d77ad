"""Test environment: force a virtual 8-device CPU mesh.

Test strategy mirrors the reference (SURVEY.md §4):
  tier 1 — in-process master + real gRPC (tests hit real RPC);
  tier 2 — multi-device JAX on the CPU backend (8 virtual devices);
  tier 3 — fault injection: kill a worker proc, assert recovery.

The whole suite runs on the CPU platform: JAX_PLATFORMS=cpu is set
here (and honoured by jax) before the first jax import, and spawned
workers are pinned the same way through DLROVER_TPU_FORCE_CPU. Nothing
in this file may describe or load a TPU — tests/test_tpu_compile.py
owns that, inside a fixture.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    _flags = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
# the CPU backend's AllReducePromotion pass crashes cloning bf16
# all-reduces inside scan bodies (pipeline/MoE programs); TPU has no
# such pass. Disabling it lets tests compile + run the SAME bf16
# programs that run on hardware.
if "xla_disable_hlo_passes" not in _flags:
    _flags = (_flags + " --xla_disable_hlo_passes=all-reduce-promotion").strip()
os.environ["XLA_FLAGS"] = _flags
# Subprocesses spawned by tests (agent workers) read this to apply the
# same override — see dlrover_tpu.utils.platform.ensure_cpu_if_forced().
os.environ["DLROVER_TPU_FORCE_CPU"] = "1"

import jax  # noqa: E402  (must come after the env setup above)

jax.config.update("jax_platforms", "cpu")

import gc  # noqa: E402

import pytest  # noqa: E402


def _vm_map_count() -> int:
    try:
        with open("/proc/self/maps", "rb") as f:
            return sum(1 for _ in f)
    except OSError:  # non-Linux: no mmap-count pressure signal
        return 0


@pytest.fixture(autouse=True, scope="module")
def _shed_jit_mappings():
    """Keep the full-suite run under the kernel's vm.max_map_count.

    Every compiled XLA:CPU executable holds JIT code in its own mmap
    regions; a full tier-1 run accumulates tens of thousands of
    mappings and segfaults inside backend_compile when mmap starts
    failing near the 65530 default cap. Dropping jax's compilation
    caches between modules releases executables whose owners died
    with the module, resetting the count. Gated on the live map count
    so cheap modules keep cross-module compile reuse.
    """
    yield
    if _vm_map_count() > 35_000:
        jax.clear_caches()
        gc.collect()
