"""Persistent XLA compilation cache wiring (runtime.init).

A respawned worker recompiles a program its predecessor already
compiled; runtime.enable_compile_cache turns jax's disk cache on so
respawns read it back. The one rule for WHERE it lives: placed from
outside by JAX_COMPILATION_CACHE_DIR (jax's own handling, the code
sets no directory), else one fixed directory inside the checkout."""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
IN_TREE = os.path.join(REPO, ".xla_cache")

_PROG = """
from dlrover_tpu.utils.platform import ensure_cpu_if_forced
ensure_cpu_if_forced()
import dlrover_tpu
dlrover_tpu.init()
import jax
print("CACHE_DIR", jax.config.jax_compilation_cache_dir)
x = jax.jit(lambda a: (a @ a).sum())(
    jax.numpy.ones((256, 256))
)
print("OK", float(x))
"""


def _run(extra_env, prog=_PROG):
    env = dict(os.environ)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.update(
        {
            "DLROVER_TPU_FORCE_CPU": "1",
            "JAX_PLATFORMS": "cpu",
            "PYTHONPATH": REPO,
        }
    )
    env.update(extra_env)
    r = subprocess.run(
        [sys.executable, "-c", prog],
        capture_output=True,
        text=True,
        timeout=240,
        env=env,
    )
    assert r.returncode == 0, r.stderr[-1500:]
    return r.stdout


def test_env_var_places_the_cache(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: jax's own handling IS the
    cache — that directory is in effect and nothing in the tree is
    touched by code."""
    cache = str(tmp_path / "xc")
    existed = os.path.isdir(IN_TREE)
    out = _run({"JAX_COMPILATION_CACHE_DIR": cache})
    assert f"CACHE_DIR {cache}" in out
    assert os.path.isdir(IN_TREE) == existed


def test_unset_uses_fixed_in_tree_dir():
    """Nothing set: the one fixed, git-ignored directory inside the
    checkout — the same on two runs (the path is part of the cache
    key, so a directory that moves never hits)."""
    first = _run({})
    second = _run({})
    assert f"CACHE_DIR {IN_TREE}" in first
    assert f"CACHE_DIR {IN_TREE}" in second
    ignored = subprocess.run(
        ["git", "check-ignore", "-q", IN_TREE], cwd=REPO
    )
    assert ignored.returncode == 0, ".xla_cache must be git-ignored"


def test_preconfigured_dir_not_clobbered(tmp_path):
    pre = str(tmp_path / "pre")
    prog = _PROG.replace(
        "import dlrover_tpu\n",
        "import jax\n"
        f"jax.config.update('jax_compilation_cache_dir', {pre!r})\n"
        "import dlrover_tpu\n",
    )
    out = _run({}, prog)
    assert f"CACHE_DIR {pre}" in out


def test_cache_dir_populated(tmp_path):
    """The directory in effect really receives the compiled program
    (what the respawned worker reads back)."""
    cache = str(tmp_path / "xc")
    # a trivial matmul compiles under the one-second bar init() sets
    # for what is worth caching; lower the bar, keep the wiring
    prog = _PROG.replace(
        "x = jax.jit",
        "jax.config.update("
        "'jax_persistent_cache_min_compile_time_secs', 0)\n"
        "x = jax.jit",
    )
    _run({"JAX_COMPILATION_CACHE_DIR": cache}, prog)
    assert os.path.isdir(cache) and os.listdir(cache)
