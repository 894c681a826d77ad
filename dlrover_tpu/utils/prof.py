"""Profiling helpers: completion fences for timing, the chips' peak
table, HLO cost analysis.

Reference parity: atorch `AProfiler` (atorch/atorch/utils/prof.py:38 —
module fwd/bwd hooks accumulating per-module flops/time).

TPU re-design: module hooks don't exist under jit — and aren't needed:
XLA knows the flops. Per-op numbers come from
`jax.jit(fn).lower(...).compile().cost_analysis()`. Host spans and
their place on the device trace's clock are `common/trace.py`'s; the
trace -> metrics reduction is `perfbench/trace_reduce.py`'s.
"""

from typing import Callable, Dict

# peak bf16 TFLOP/s per chip by generation (public spec sheets). A
# device that is not in the table is an error, not a default: an MFU
# priced at the wrong chip's peak is a wrong number with no warning.
PEAK_TFLOPS = {
    "v4": 275.0,
    "v5e": 197.0,
    "v5p": 459.0,
    "v6e": 918.0,
}


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
