"""Activation checkpointing (rematerialization) policies + host offload.

Reference parity: atorch `CheckpointOptimization`
(auto/opt_lib/checkpoint_optimization.py:217) wraps chosen torch modules
in torch.utils.checkpoint; `selective_offloading_checkpoint.py:252`
offloads selected activations to CPU DRAM instead of recomputing.

TPU design: XLA already fuses; the lever is `jax.checkpoint` with a
*policy* deciding which intermediates are saved vs recomputed vs
offloaded to pinned host memory. A policy here is a name → the
jax.checkpoint_policies object, including "save these named activations
and offload them to host" (the selective-offloading equivalent — names
come from `checkpoint_name` tags inside the model).

Which policy a layer scan should run depends on the room the device
has left, and that changes whenever an elastic job is rebuilt on
another number of chips. So the model's default is the name "auto":
`accelerate()` tries the rungs of `LADDER`, least recomputation first,
against the compiled step's `memory_analysis()` and the device's
`bytes_limit` (`LadderStep`), and the model reads the rung being
traced through `scan_policy`. Traced anywhere else (a bare loss
function, an evaluation, a backend that states no limit) "auto" is
"full". An explicit name is obeyed and no ladder runs: set one to pin
a job's program (a sweep, a comparison), or where the process holds
more on the device beside its step than `MARGIN_BYTES` allows for."""

import contextlib
import threading
import time
from typing import Any, Callable, Dict, Optional, Sequence

import jax

from dlrover_tpu.common import trace
from dlrover_tpu.common.log import default_logger as logger
from dlrover_tpu.utils.program_stats import extract_program_stats

# re-export the tag the model layer uses to name offloadable activations
from jax.ad_checkpoint import checkpoint_name  # noqa: F401

_P = jax.checkpoint_policies


def resolve_policy(
    name: str,
    save_names: Sequence[str] = (),
    offload_src: str = "device",
    offload_dst: str = "pinned_host",
):
    """Map a strategy-level policy name to a jax.checkpoint policy.

    - "full": recompute everything (max memory savings)
    - "dots": save matmul outputs (skip recomputing MXU work)
    - "dots_no_batch": save only non-batch matmuls (the common LLM choice)
    - "save_names": save exactly the activations tagged `checkpoint_name`
    - "offload_names": keep tagged activations but in HOST memory —
      trades ICI-free PCIe/DMA bandwidth for HBM, the
      selective-offloading-checkpoint equivalent
    - "none": no remat (policy=None with no checkpoint wrap)
    """
    if name == "none":
        return None
    if name == "full":
        return _P.nothing_saveable
    if name == "dots":
        return _P.dots_saveable
    if name == "dots_no_batch":
        return _P.dots_with_no_batch_dims_saveable
    if name == "proj":
        # save the [B,S,dim]-sized projection outputs (cheap in HBM),
        # recompute the mlp_dim-wide matmuls + the flash-attention fwd
        return _P.save_only_these_names(
            "qkv_proj", "attn_proj", "mlp_down"
        )
    if name == "proj_mlp":
        # additionally save the mlp_dim-wide gate/up activations: no
        # matmul is recomputed, ~4x the activation HBM of "proj"
        return _P.save_only_these_names(
            "qkv_proj", "attn_proj", "mlp_down", "mlp_gate", "mlp_up"
        )
    if name == "save_names":
        return _P.save_only_these_names(*save_names)
    if name == "offload_names":
        return _P.save_and_offload_only_these_names(
            names_which_can_be_saved=[],
            names_which_can_be_offloaded=list(save_names),
            offload_src=offload_src,
            offload_dst=offload_dst,
        )
    raise ValueError(f"unknown remat policy: {name}")


def apply_remat(
    fn: Callable,
    policy_name: str = "full",
    save_names: Sequence[str] = (),
    prevent_cse: bool = True,
) -> Callable:
    """Wrap `fn` (a layer body / block fn) with the chosen remat policy.
    Under `lax.scan` layer stacking pass prevent_cse=False (scan already
    prevents the CSE hazard and the flag costs compile time)."""
    if policy_name == "none":
        return fn
    return jax.checkpoint(
        fn,
        policy=resolve_policy(policy_name, save_names),
        prevent_cse=prevent_cse,
    )


def remat_every_n(
    fn: Callable, layer_index: int, n: int, policy_name: str = "full"
) -> Callable:
    """Selective layer checkpointing: remat layers where index % n == 0,
    leave the rest saved — the reference's per-module checkpoint list,
    expressed for a python-unrolled stack (scan stacks use apply_remat
    on the whole body instead)."""
    if n <= 0 or layer_index % n != 0:
        return fn
    return apply_remat(fn, policy_name)


# ---------------------------------------------------------------------------
# "auto": the rung of a layer scan, chosen from the compiled step's memory
# ---------------------------------------------------------------------------

# Least recomputation first. The same mathematics at every rung: kept
# or recomputed, an activation is the same array. Each rung is here
# because the Mistral-7B train cell (2 layers, 4096 tokens a step, one
# v5e; PERF.md section 4, PR 51) compiles to a peak of its own and
# takes a time of its own there: "none" 16.15 GB and 139.7 ms a step,
# "proj_mlp" 14.99 and 146.7, "full" 13.61 and 152.2. ("proj", 13.88
# and 149.0, is not a rung: a window of 0.27 GB for 2%, and one more
# compile on every fall; nor "dots": "proj_mlp"'s bytes, 1.3 ms slower.)
LADDER = ("none", "proj_mlp", "full")

# What a rung must leave free of the device's `bytes_limit`: room for
# what a training process holds on the device beside its step program
# (the batches in flight, metrics, an evaluation's outputs, the
# allocator's slack). The Mistral cell holds 36 MB of that, with no
# evaluation and no checkpoint beside the step. The compiler's verdict
# is exact for the program alone; what the margin misjudges shows when
# the step first runs, and that is a rung that does not fit either
# (`LadderStep.__call__`). The sum the ladder judges by errs to the
# safe side: in that cell it stands 1.75 GB above what the chip's
# loader reserves (PERF.md section 7 (aa)).
MARGIN_BYTES = 512 * 2**20

_tracing = threading.local()  # .rung, .asked: this thread's, see below


def scan_policy(name: str) -> str:
    """The policy a layer scan runs under the name its config gives:
    the name itself, and for "auto" the rung of the step being traced
    (`tracing_at`), "full" where no one is choosing."""
    if name != "auto":
        return name
    _tracing.asked = asked() + 1
    return getattr(_tracing, "rung", "full")


def asked() -> int:
    """How often a model traced on this thread has read the rung: a
    step whose trace does not move this has nothing to choose."""
    return getattr(_tracing, "asked", 0)


@contextlib.contextmanager
def tracing_at(rung: str):
    """`scan_policy("auto")` is `rung` inside the block. The rung is
    part of what is traced, and `jax.jit` and `jax.checkpoint` keep
    their traces by function and avals: wrap ONE function a rung,
    never one function for all."""
    was = getattr(_tracing, "rung", "full")
    _tracing.rung = rung
    try:
        yield
    finally:
        _tracing.rung = was


def _out_of_memory(e: Exception) -> bool:
    return "RESOURCE_EXHAUSTED" in str(e)


class LadderStep:
    """A train step whose layer scans keep what the device has room
    for. `step_at(rung)` gives the jitted step traced at that rung
    (`tracing_at`), a new function a call; `limit_bytes` is the
    `bytes_limit` of ONE device of this process.

    The first call, or `lower`, takes the rungs from the top: each is
    traced, lowered and compiled, and the first whose
    `memory_analysis()` (arguments + temporaries + outputs less what
    donation aliases) stays inside the budget (the limit less
    `MARGIN_BYTES`) is kept; a compile the compiler refuses for
    memory is a rung that does not fit. From then on the step IS the
    kept rung's jitted function, whose call finds the executable
    compiled for the check: a step that fits at the top pays one
    compilation, and a batch of another shape compiles at the kept
    rung as under `jax.jit`. The bottom rung is kept whatever it
    reads: it is what an explicit "full" would run. A step whose
    trace never reads the rung (an explicit policy, `remat=False`,
    another model) has no ladder: its one program runs, and `rung`
    stays None.

    The compiler judges the program alone. If the kept rung's FIRST
    run is refused for memory (the process holds more beside its step
    than the margin allows for), that rung does not fit either and
    the ladder goes on below it: in a job of one process, and while
    the arguments are whole (a refused run donates nothing). A job of
    several raises the refusal as it came: a process that fell alone
    would run another program than its peers.

    Every choice is ONE record `remat.ladder` in the program's ring,
    beside the tried rungs' `compile` records and under the span they
    are under: `rung`, `compiled` (rungs, so far), `budget_bytes`,
    `room_bytes` (the budget less the kept rung's peak), a tried
    rung's `peak_<rung>` (-1: refused by the compiler) and `run_<rung>`
    -1 where its first run was refused.

    Every process of a job compiles the same program for the same
    kind of chip and reads the limit from a device of its own, so
    every process takes the same rung."""

    def __init__(self, step_at: Callable[[str], Any], limit_bytes: float):
        self._step_at = step_at
        self._budget = int(limit_bytes) - MARGIN_BYTES
        self._tried: Dict[str, int] = {}
        self._below = 0      # rungs of LADDER already let go
        self._run = None     # the kept rung's jitted step
        self._ran = False    # ... and nothing more can make it fall
        self.rung: Optional[str] = None

    def __call__(self, *args):
        if self._ran:
            return self._run(*args)
        while True:
            if self._run is None:
                self._choose(args)
            try:
                out = self._run(*args)
            except jax.errors.JaxRuntimeError as e:
                if not self._falls_at_run(e, args):
                    raise
                continue
            self._ran = True
            return out

    def lower(self, *args):
        """The kept rung's program for these arguments (arrays or
        avals; the rung is chosen now if it was not): what
        `jax.jit(...).lower` gives."""
        if self._run is None:
            self._choose(args)
        return self._run.lower(*args)

    def _falls_at_run(self, e: Exception, args) -> bool:
        rung = self.rung
        if (
            rung in (None, LADDER[-1])
            or not _out_of_memory(e)
            or jax.process_count() > 1
            or any(
                x.is_deleted() for x in jax.tree_util.tree_leaves(args)
                if isinstance(x, jax.Array)
            )
        ):
            return False
        logger.warning(
            "remat ladder: the first run at %r was refused for memory: %s",
            rung, str(e).splitlines()[0],
        )
        self._tried[f"run_{rung}"] = -1
        self._below = LADDER.index(rung) + 1
        self._run = None
        return True

    def _choose(self, args) -> None:
        wall, t0, tried = time.time(), time.perf_counter(), self._tried
        for rung in LADDER[self._below:]:
            step, before = self._step_at(rung), asked()
            traced = step.trace(*args)
            if asked() == before:  # one program whatever the rung
                self._run, self._ran = step, True
                return
            try:
                run = traced.lower().compile()
            except jax.errors.JaxRuntimeError as e:
                if rung == LADDER[-1] or not _out_of_memory(e):
                    raise
                tried[f"peak_{rung}"] = -1
                continue
            peak = extract_program_stats(run).peak_hbm_bytes
            tried[f"peak_{rung}"] = peak
            if peak <= self._budget or rung == LADDER[-1]:
                break
        self.rung, self._run = rung, step
        counts = dict(
            rung=rung, budget_bytes=self._budget,
            room_bytes=self._budget - peak,
            compiled=sum(k.startswith("peak_") for k in tried),
            **tried,
        )
        trace.record(
            "remat.ladder", wall, time.perf_counter() - t0, None, **counts
        )
        logger.info("remat ladder: %s", _in_words(counts))


def ladder_summary() -> str:
    """The newest `remat.ladder` record of this process in a few
    words, for a log line; "" where no ladder ran."""
    chosen = [
        r for r in trace.snapshot() if r[trace.NAME] == "remat.ladder"
    ]
    return _in_words(chosen[-1][trace.COUNTS]) if chosen else ""


def _in_words(counts: Dict[str, Any]) -> str:
    tried = ", ".join(
        f"{k[5:]} " + ("refused" if v < 0 else f"{v / 1e9:.2f} GB")
        + (" and refused at its first run"
           if f"run_{k[5:]}" in counts else "")
        for k, v in counts.items() if k.startswith("peak_")
    )
    return (
        f"layer scans keep {counts['rung']!r} ({tried}; budget "
        f"{counts['budget_bytes'] / 1e9:.2f} GB)"
    )
