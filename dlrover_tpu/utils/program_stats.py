"""Compiled-program stats extraction (the XLA answer to the reference's
TF graph profile extractor).

Reference parity: elastic_agent/tensorflow/profile_extractor.py —
`OperationStats` (op counts, flops) and `TensorStats` (variable sizes,
alloc bytes) pulled from TF graphs to feed the brain resource optimizer.
Here the unit of analysis is the jitted train step: XLA exposes
`cost_analysis()` (flops, bytes accessed) and `memory_analysis()`
(argument/output/temp/generated-code bytes) on the compiled executable,
and the HLO module gives op histograms. These are the numbers the
resource optimizer and the paral-config tuner actually need on TPU —
HBM headroom and arithmetic intensity, not per-op CPU timings.
"""

import collections
import dataclasses
import json
import re
from typing import Any, Dict, Optional


@dataclasses.dataclass
class ProgramStats:
    """Stats of one compiled XLA program (reference OperationStats +
    TensorStats merged — one program replaces one TF graph)."""

    flops: float = 0.0
    bytes_accessed: float = 0.0
    # memory_analysis: what the program needs in HBM
    argument_bytes: int = 0
    output_bytes: int = 0
    temp_bytes: int = 0
    alias_bytes: int = 0  # donated outputs aliasing arguments
    generated_code_bytes: int = 0
    # HLO op histogram
    op_count: int = 0
    op_histogram: Dict[str, int] = dataclasses.field(
        default_factory=dict
    )
    collective_count: int = 0
    fusion_count: int = 0

    @property
    def peak_hbm_bytes(self) -> int:
        """Arguments + outputs + temps, minus donated aliases (a
        donated train state is counted once, not as arg AND out) —
        the allocation the runtime must fit."""
        return (
            self.argument_bytes
            + self.output_bytes
            + self.temp_bytes
            - self.alias_bytes
        )

    @property
    def arithmetic_intensity(self) -> float:
        """FLOPs per byte accessed — below the chip's ridge point the
        program is HBM-bound (v5e: ~240 flops/byte at bf16)."""
        return self.flops / self.bytes_accessed if self.bytes_accessed else 0.0

    def to_json(self) -> str:
        d = dataclasses.asdict(self)
        d["peak_hbm_bytes"] = self.peak_hbm_bytes
        d["arithmetic_intensity"] = round(self.arithmetic_intensity, 3)
        return json.dumps(d)


_COLLECTIVE_OPS = (
    "all-reduce",
    "all-gather",
    "all-to-all",
    "collective-permute",
    "reduce-scatter",
)

_HLO_OP_RE = re.compile(r"([a-z][\w\-]*)\(")


def _op_histogram(hlo_text: str) -> Dict[str, int]:
    """Count HLO ops: each instruction line is `%name = <type> op(...)`.
    The type may itself be a parenthesized tuple (multi-output fusions,
    tuple collectives), so the op is the FIRST `word(` after the `=` —
    type tokens like `f32[128]{1,0}` never immediately precede a '('."""
    hist: Dict[str, int] = collections.Counter()
    for line in hlo_text.splitlines():
        _, eq, rhs = line.partition(" = ")
        if not eq:
            continue
        m = _HLO_OP_RE.search(rhs)
        if m:
            hist[m.group(1)] += 1
    return dict(hist)


def extract_program_stats(compiled: Any) -> ProgramStats:
    """Stats from a `jax.stages.Compiled` (the result of
    `jax.jit(f).lower(...).compile()` — or any live jitted function's
    cached executable).

    Every field degrades to its default when a backend does not expose
    the underlying analysis (CPU exposes cost_analysis but trimmed
    memory stats)."""
    stats = ProgramStats()
    try:
        cost = compiled.cost_analysis() or {}
        stats.flops = float(cost.get("flops", 0.0))
        stats.bytes_accessed = float(cost.get("bytes accessed", 0.0))
    except Exception:  # noqa: BLE001 — backend-dependent
        pass
    try:
        mem = compiled.memory_analysis()
        stats.argument_bytes = int(
            getattr(mem, "argument_size_in_bytes", 0)
        )
        stats.output_bytes = int(
            getattr(mem, "output_size_in_bytes", 0)
        )
        stats.temp_bytes = int(getattr(mem, "temp_size_in_bytes", 0))
        stats.alias_bytes = int(
            getattr(mem, "alias_size_in_bytes", 0)
        )
        stats.generated_code_bytes = int(
            getattr(mem, "generated_code_size_in_bytes", 0)
        )
    except Exception:  # noqa: BLE001
        pass
    try:
        hlo = compiled.as_text()
        hist = _op_histogram(hlo)
        stats.op_histogram = hist
        stats.op_count = sum(hist.values())
        stats.collective_count = sum(
            n for op, n in hist.items() if op in _COLLECTIVE_OPS
        )
        stats.fusion_count = hist.get("fusion", 0)
    except Exception:  # noqa: BLE001
        pass
    return stats


def device_memory_bytes(device: Any = None) -> float:
    """The `bytes_limit` one device states (the first, by default):
    what a compiled program's `peak_hbm_bytes` is held against. 0.0
    where the backend states none (the CPU's): unknown, so nothing is
    ever refused, or chosen, on memory there."""
    import jax

    try:
        stats = (device or jax.devices()[0]).memory_stats()
        if stats and "bytes_limit" in stats:
            return float(stats["bytes_limit"])
    except Exception:  # noqa: BLE001 — CPU backend has no stats
        pass
    return 0.0


def abstractify(tree: Any) -> Any:
    """Array-likes → ShapeDtypeStruct avals (sharding preserved when
    present) so lowering never touches real buffers."""
    import jax

    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=getattr(x, "sharding", None)
        )
        if hasattr(x, "shape")
        else x,
        tree,
    )


def profile_step_fn(
    fn: Any, *example_args, static_argnums=(), **example_kwargs
) -> ProgramStats:
    """Convenience: lower+compile `fn` on abstract avals (no execution,
    no real buffers) and extract its stats — how the paral-config tuner
    sizes a candidate config without paying a training step."""
    import jax

    args, kwargs = abstractify((example_args, example_kwargs))
    lowered = jax.jit(fn, static_argnums=static_argnums).lower(
        *args, **kwargs
    )
    return extract_program_stats(lowered.compile())


def params_stats(params: Any) -> Dict[str, Any]:
    """Variable-side stats (reference TensorStats.update_varible_stats):
    count / total / max leaf sizes of a pytree of arrays."""
    import jax

    leaves = jax.tree_util.tree_leaves(params)
    sizes = [
        int(getattr(x, "nbytes", 0) or 0) for x in leaves
    ]
    return {
        "variable_count": len(leaves),
        "total_variable_bytes": sum(sizes),
        "max_variable_bytes": max(sizes, default=0),
    }
