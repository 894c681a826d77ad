"""Acceleration engine: dry-run profiling + automatic strategy search.

Reference parity: ATorch's acceleration engine — `auto_accelerate`'s
engine path generates candidate strategies, a `DryRunner` profiles each
(atorch/auto/dry_runner/dry_runner.py:19, `tune_batchsize` :142), and
strategy-generation algorithms (Bayesian opt / HEBO,
auto/engine/sg_algo/) pick the next candidate; an executor/servicer pair
(auto/engine/executor.py:36, servicer.py) serves this over gRPC.

TPU re-design: "profiling a strategy" does not need a training run —
XLA's ahead-of-time pipeline gives FLOPs + bytes (cost analysis) and
peak HBM (memory analysis) from `jit(...).lower().compile()` without
executing a step. The search scores candidates with a roofline model
(max of MXU time, HBM time, estimated collective time) and only
optionally timing real steps for the top candidates. Candidate space =
mesh factorizations x remat policy x precision x grad-accum.
"""

import itertools
import math
import time
from dataclasses import dataclass, field, replace
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from dlrover_tpu.common.log import default_logger as logger
from dlrover_tpu.parallel.accelerate import Strategy, accelerate
from dlrover_tpu.parallel.mesh import MeshSpec
from dlrover_tpu.utils.program_stats import device_memory_bytes

# conservative per-chip peaks used when the backend exposes nothing
# (v5p-class: 459 TFLOP/s bf16, 2765 GB/s HBM, 100 GB/s/link ICI)
DEFAULT_PEAK_FLOPS = 459e12
DEFAULT_HBM_GBPS = 2765.0
DEFAULT_ICI_GBPS = 100.0


@dataclass
class DryRunReport:
    """What one compile-only profile yields."""

    strategy: Strategy
    flops: float = 0.0
    bytes_accessed: float = 0.0
    peak_memory_bytes: float = 0.0
    compile_seconds: float = 0.0
    est_step_seconds: float = float("inf")
    measured_step_seconds: float = 0.0
    fits_memory: bool = True
    error: str = ""


class DryRunner:
    """Compile (and optionally run) one strategy; extract cost/memory.

    build(strategy) must return an `Accelerated` plus a host batch the
    train step accepts — the engine stays agnostic of model specifics.
    """

    def __init__(
        self,
        build: Callable[[Strategy], Tuple[Any, Any]],
        hbm_bytes_per_device: Optional[float] = None,
        peak_flops: float = DEFAULT_PEAK_FLOPS,
        hbm_gbps: float = DEFAULT_HBM_GBPS,
    ):
        self.build = build
        self.peak_flops = peak_flops
        self.hbm_gbps = hbm_gbps
        self.hbm_bytes = (
            hbm_bytes_per_device or device_memory_bytes()
        )

    def profile(
        self, strategy: Strategy, run_steps: int = 0
    ) -> DryRunReport:
        report = DryRunReport(strategy=strategy)
        state = None
        try:
            t0 = time.monotonic()
            acc, batch = self.build(strategy)
            batch = acc.shard_batch(batch)
            step = acc.train_step
            if not hasattr(step, "lower"):  # plain callable → wrap
                step = jax.jit(step)
            if acc.state_shardings is not None and run_steps <= 0:
                # AOT path: compile against abstract state carrying the
                # strategy's shardings — no model-sized allocation
                # during the search (the point of cost-model search)
                abstract = jax.eval_shape(
                    acc.init, jax.random.PRNGKey(0)
                )
                spec_state = jax.tree_util.tree_map(
                    lambda a, s: jax.ShapeDtypeStruct(
                        a.shape, a.dtype, sharding=s
                    ),
                    abstract,
                    acc.state_shardings,
                )
                compiled = step.lower(spec_state, batch).compile()
            else:
                state = acc.init(jax.random.PRNGKey(0))
                compiled = step.lower(state, batch).compile()
            report.compile_seconds = time.monotonic() - t0
        except Exception as e:  # noqa: BLE001 — search survives bad points
            report.error = f"{type(e).__name__}: {e}"
            report.fits_memory = False
            return report

        cost = compiled.cost_analysis() or {}
        report.flops = float(cost.get("flops", 0.0))
        report.bytes_accessed = float(cost.get("bytes accessed", 0.0))
        mem = compiled.memory_analysis()
        if mem is not None:
            report.peak_memory_bytes = float(
                getattr(mem, "temp_size_in_bytes", 0)
                + getattr(mem, "argument_size_in_bytes", 0)
                + getattr(mem, "output_size_in_bytes", 0)
                - getattr(mem, "alias_size_in_bytes", 0)
            )
            if self.hbm_bytes > 0:
                report.fits_memory = (
                    report.peak_memory_bytes <= self.hbm_bytes
                )
        n_dev = max(strategy.mesh.num_devices, 1)
        # roofline: per-device compute vs HBM traffic
        flop_t = report.flops / n_dev / self.peak_flops
        mem_t = report.bytes_accessed / n_dev / (self.hbm_gbps * 1e9)
        report.est_step_seconds = max(flop_t, mem_t, 1e-9)

        if run_steps > 0 and report.fits_memory:
            try:
                if state is None:
                    state = acc.init(jax.random.PRNGKey(0))
                state, _ = acc.train_step(state, batch)  # warmup
                jax.block_until_ready(state)
                t0 = time.monotonic()
                for _ in range(run_steps):
                    state, _ = acc.train_step(state, batch)
                jax.block_until_ready(state)
                report.measured_step_seconds = (
                    time.monotonic() - t0
                ) / run_steps
            except Exception as e:  # noqa: BLE001
                report.error = f"run: {type(e).__name__}: {e}"
        return report


# ---------------------------------------------------------------------------
# candidate generation + search
# ---------------------------------------------------------------------------


def mesh_candidates(
    n_devices: int,
    axes: Sequence[str] = ("data", "fsdp", "tensor"),
    max_tensor: int = 8,
) -> List[MeshSpec]:
    """All factorizations of n_devices over the given axes (the
    create_parallel_group configuration space)."""
    out = []
    seen = set()
    for combo in _factorizations(n_devices, len(axes)):
        kw = dict(zip(axes, combo))
        if kw.get("tensor", 1) > max_tensor:
            continue
        spec = MeshSpec(**kw)
        if spec not in seen:
            seen.add(spec)
            out.append(spec)
    return out


def _factorizations(n: int, k: int) -> List[Tuple[int, ...]]:
    if k == 1:
        return [(n,)]
    out = []
    for d in range(1, n + 1):
        if n % d == 0:
            for rest in _factorizations(n // d, k - 1):
                out.append((d,) + rest)
    return out


@dataclass
class SearchResult:
    best: Optional[DryRunReport]
    reports: List[DryRunReport] = field(default_factory=list)


class StrategySearch:
    """Enumerate (small spaces) or BO-sample (large) strategy candidates,
    score via DryRunner, return the winner.

    Score = measured step time when `run_steps` > 0, else the roofline
    estimate; OOM/compile failures are inf. Ties break toward less
    parallelism (fewer collectives to go wrong)."""

    def __init__(
        self,
        runner: DryRunner,
        n_devices: Optional[int] = None,
        remat_choices: Sequence[str] = ("none", "dots"),
        precision_choices: Sequence[str] = ("bf16",),
        grad_accum_choices: Sequence[int] = (1,),
        axes: Sequence[str] = ("data", "fsdp", "tensor"),
        max_candidates: int = 32,
    ):
        self.runner = runner
        self.n_devices = n_devices or len(jax.devices())
        self.remat_choices = remat_choices
        self.precision_choices = precision_choices
        self.grad_accum_choices = grad_accum_choices
        self.axes = axes
        self.max_candidates = max_candidates

    def candidates(self) -> List[Strategy]:
        meshes = mesh_candidates(self.n_devices, self.axes)
        cands = [
            Strategy(
                mesh=m,
                remat=r,
                precision=p,
                grad_accum=g,
            )
            for m, r, p, g in itertools.product(
                meshes,
                self.remat_choices,
                self.precision_choices,
                self.grad_accum_choices,
            )
        ]
        if len(cands) > self.max_candidates:
            # subsample deterministically, keeping the extremes
            idx = np.linspace(
                0, len(cands) - 1, self.max_candidates
            ).astype(int)
            cands = [cands[i] for i in idx]
        return cands

    def search(self, run_steps: int = 0) -> SearchResult:
        reports: List[DryRunReport] = []
        for strat in self.candidates():
            rep = self.runner.profile(strat, run_steps=run_steps)
            reports.append(rep)
            logger.info(
                "strategy %s: est=%.2gs measured=%.2gs mem=%.2fGB%s",
                _strategy_tag(strat),
                rep.est_step_seconds,
                rep.measured_step_seconds,
                rep.peak_memory_bytes / 1e9,
                f" ERR {rep.error}" if rep.error else "",
            )
        viable = [r for r in reports if r.fits_memory and not r.error]
        if not viable:
            return SearchResult(best=None, reports=reports)

        def score(r: DryRunReport) -> Tuple:
            t = (
                r.measured_step_seconds
                if r.measured_step_seconds > 0
                else r.est_step_seconds
            )
            simplicity = (
                r.strategy.mesh.tensor
                + r.strategy.mesh.fsdp
                + r.strategy.grad_accum
            )
            return (t, simplicity)

        best = min(viable, key=score)
        return SearchResult(best=best, reports=reports)


def _strategy_tag(s: Strategy) -> str:
    m = s.mesh
    return (
        f"d{m.data}/f{m.fsdp}/t{m.tensor}/s{m.seq}/e{m.expert}/"
        f"p{m.pipe} remat={s.remat} prec={s.precision} ga={s.grad_accum}"
    )


# ---------------------------------------------------------------------------
# batch-size tuner (dry_runner.tune_batchsize equivalent)
# ---------------------------------------------------------------------------


def tune_batchsize(
    build_with_bs: Callable[[Strategy, int], Tuple[Any, Any]],
    strategy: Strategy,
    start: int = 8,
    limit: int = 4096,
    hbm_bytes_per_device: Optional[float] = None,
) -> int:
    """Largest per-step batch that compiles within device memory:
    doubling ascent, last fitting value wins. On backends without memory
    stats every size 'fits' — the caller should pass an explicit
    budget there."""
    runner_mem = hbm_bytes_per_device or device_memory_bytes()
    best = 0
    bs = start
    while bs <= limit:
        runner = DryRunner(
            lambda s: build_with_bs(s, bs),
            hbm_bytes_per_device=runner_mem,
        )
        rep = runner.profile(strategy)
        if rep.error or not rep.fits_memory:
            break
        best = bs
        bs *= 2
    return best
