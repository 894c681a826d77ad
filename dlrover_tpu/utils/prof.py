"""Profiling: step timing, MFU, device timeline, HLO cost analysis.

Reference parity: atorch `AProfiler` (atorch/atorch/utils/prof.py:38 —
module fwd/bwd hooks accumulating per-module flops/time + Chrome
timeline), timers (utils/timer.py), trace parsing
(utils/parse_trace_json.py).

TPU re-design: module hooks don't exist under jit — and aren't needed:
XLA knows the flops. Per-op numbers come from
`jax.jit(fn).lower(...).compile().cost_analysis()`; wall-clock comes
from a step-boundary profiler; the timeline comes from
`jax.profiler.trace` (perfetto, the Chrome-timeline analogue).
"""

import contextlib
import json
import os
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

from dlrover_tpu.common.log import default_logger as logger

# peak bf16 TFLOP/s per chip by generation (public spec sheets). A
# device that is not in the table is an error, not a default: an MFU
# priced at the wrong chip's peak is a wrong number with no warning.
PEAK_TFLOPS = {
    "v4": 275.0,
    "v5e": 197.0,
    "v5p": 459.0,
    "v6e": 918.0,
}


class Timer:
    """Accumulating named timer (reference atorch/utils/timer.py)."""

    def __init__(self):
        self.totals: Dict[str, float] = {}
        self.counts: Dict[str, int] = {}

    @contextlib.contextmanager
    def record(self, name: str):
        t0 = time.monotonic()
        try:
            yield
        finally:
            dt = time.monotonic() - t0
            self.totals[name] = self.totals.get(name, 0.0) + dt
            self.counts[name] = self.counts.get(name, 0) + 1

    def mean(self, name: str) -> float:
        return self.totals.get(name, 0.0) / max(
            self.counts.get(name, 0), 1
        )

    def summary(self) -> Dict[str, Dict[str, float]]:
        return {
            k: {
                "total_s": self.totals[k],
                "count": self.counts[k],
                "mean_s": self.mean(k),
            }
            for k in self.totals
        }


@dataclass
class StepStats:
    step: int
    wall_s: float
    tokens: int = 0
    tflops: float = 0.0

    @property
    def tokens_per_sec(self) -> float:
        return self.tokens / max(self.wall_s, 1e-9)


class StepProfiler:
    """Step-boundary profiler: throughput + MFU.

    `flops_per_step` (e.g. 6*N*tokens for a decoder) divides by wall
    time and the chip's peak to give MFU — the master's SpeedMonitor
    consumes tokens/sec, the bench consumes MFU.
    """

    def __init__(
        self,
        tokens_per_step: int = 0,
        flops_per_step: float = 0.0,
        peak_tflops: Optional[float] = None,
        window: int = 50,
    ):
        self.tokens_per_step = tokens_per_step
        self.flops_per_step = flops_per_step
        self.peak_tflops = peak_tflops
        self.window = window
        self.history: List[StepStats] = []
        self._t0: Optional[float] = None
        self._step = 0

    def step_start(self):
        self._t0 = time.monotonic()

    def step_end(self, step: Optional[int] = None) -> StepStats:
        wall = time.monotonic() - (self._t0 or time.monotonic())
        self._step = step if step is not None else self._step + 1
        st = StepStats(
            step=self._step,
            wall_s=wall,
            tokens=self.tokens_per_step,
            tflops=self.flops_per_step / 1e12,
        )
        self.history.append(st)
        if len(self.history) > self.window:
            self.history.pop(0)
        return st

    @contextlib.contextmanager
    def step(self, step: Optional[int] = None):
        self.step_start()
        try:
            yield
        finally:
            self.step_end(step)

    @property
    def mean_step_s(self) -> float:
        if not self.history:
            return 0.0
        return sum(s.wall_s for s in self.history) / len(self.history)

    @property
    def tokens_per_sec(self) -> float:
        return self.tokens_per_step / max(self.mean_step_s, 1e-9)

    @property
    def mfu(self) -> float:
        """Achieved / peak flops per device."""
        import jax

        if not self.flops_per_step:
            return 0.0
        peak = self.peak_tflops or detect_peak_tflops()
        achieved = self.flops_per_step / max(self.mean_step_s, 1e-9)
        n_dev = jax.device_count()
        return achieved / (peak * 1e12 * n_dev)


def device_fence(out) -> None:
    """Completion fence for timing: device_get one element of every
    array leaf in `out`.

    A data-dependent D2H read of the result cannot complete before
    the kernels that produce it, so the fence holds on any backend
    whatever its `block_until_ready` does. For sharded leaves one
    element is read from EVERY addressable shard — element (0,..,0)
    alone would only fence the device owning it. The one-element
    gather compiles once per leaf shape; time it separately (call
    this twice, the second call is pure fence cost) when the timed
    region is short."""
    import jax

    for leaf in jax.tree_util.tree_leaves(out):
        if not (hasattr(leaf, "shape") and hasattr(leaf, "dtype")):
            continue
        shards = getattr(leaf, "addressable_shards", None)
        datas = [s.data for s in shards] if shards else [leaf]
        for d in datas:
            if getattr(d, "size", 1) == 0:
                continue  # nothing to read from an empty leaf
            if d.shape:
                d = d[tuple(0 for _ in d.shape)]
            jax.device_get(d)


def timed_with_fence(thunk, iters: int, warmup: int = 1):
    """Time `iters` calls of `thunk` under device_fence semantics.

    Fences after warmup, times the loop, fences, then re-fences the
    (already complete) output to measure the fence's own round-trip
    cost and subtracts it. `warmup` is effectively >= 1: one untimed
    call is always made to bind the fence target and pre-compile its
    gather. Returns (seconds_per_iter, last_output)."""
    import time as _time

    out = thunk()
    for _ in range(max(warmup - 1, 0)):
        out = thunk()
    device_fence(out)
    t0 = _time.monotonic()
    for _ in range(iters):
        out = thunk()
    device_fence(out)
    elapsed = _time.monotonic() - t0
    t1 = _time.monotonic()
    device_fence(out)
    elapsed -= _time.monotonic() - t1
    return max(elapsed, 1e-9) / iters, out


def detect_tpu_gen() -> str:
    """Chip generation from the live device's device_kind. Known kind
    strings: 'TPU v4'; 'TPU v5 lite' / 'TPU v5e' (v5e); 'TPU v5' /
    'TPU v5p' (v5p — the bare 'v5' has NO suffix, so substring order
    matters); 'TPU v6 lite' / 'TPU v6e' (v6e). Any other device is a
    ValueError: there is no peak to price it at."""
    import jax

    kind = jax.devices()[0].device_kind
    norm = kind.lower().replace(" ", "").replace("lite", "e")
    if "tpu" in norm:
        for gen in ("v6e", "v5e", "v5p", "v4"):
            if gen in norm:
                return gen
        if "v5" in norm:
            return "v5p"  # bare 'TPU v5' is the p-series
        if "v6" in norm:
            return "v6e"
    raise ValueError(
        f"device kind {kind!r} is not in the peak table "
        f"({sorted(PEAK_TFLOPS)}): no MFU can be stated for it"
    )


def detect_peak_tflops() -> float:
    return PEAK_TFLOPS[detect_tpu_gen()]


def cost_analysis(fn: Callable, *args, **kw) -> Dict[str, float]:
    """XLA's own per-program cost model: flops, bytes accessed, memory.

    Replaces the reference's module-hook flops accounting — the
    compiler's numbers include fusion, remat and GSPMD partitioning.
    """
    import jax

    compiled = jax.jit(fn).lower(*args, **kw).compile()
    costs = compiled.cost_analysis() or {}
    out = {
        "flops": float(costs.get("flops", 0.0)),
        "bytes_accessed": float(costs.get("bytes accessed", 0.0)),
    }
    try:
        mem = compiled.memory_analysis()
        out["peak_bytes"] = float(
            getattr(mem, "temp_size_in_bytes", 0)
            + getattr(mem, "argument_size_in_bytes", 0)
            + getattr(mem, "output_size_in_bytes", 0)
        )
    except Exception:
        pass
    return out


@contextlib.contextmanager
def device_trace(log_dir: str):
    """Capture a device timeline viewable in perfetto/tensorboard —
    the Chrome-timeline analogue of AProfiler(timeline=True)."""
    import jax

    os.makedirs(log_dir, exist_ok=True)
    jax.profiler.start_trace(log_dir)
    try:
        yield log_dir
    finally:
        jax.profiler.stop_trace()
        logger.info("device trace written to %s", log_dir)


def save_profile(path: str, profiler: StepProfiler, timer: Timer = None):
    payload: Dict[str, Any] = {
        "mean_step_s": profiler.mean_step_s,
        "tokens_per_sec": profiler.tokens_per_sec,
        "mfu": profiler.mfu,
        "steps": [
            {"step": s.step, "wall_s": s.wall_s} for s in profiler.history
        ],
    }
    if timer is not None:
        payload["timers"] = timer.summary()
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path, "w") as f:
        json.dump(payload, f, indent=2)
