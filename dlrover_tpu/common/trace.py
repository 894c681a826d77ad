"""Spans, events and their counts: the program's one tracing primitive.

A span is a named stretch of host time at a layer boundary. Closing it
appends one record to a process-wide bounded ring; while a
`jax.profiler` session runs, the span also lies on the device trace's
own clock as the TraceAnnotation ``dlrover:<name>``. An event is a
record with no extent (a request's legs); `record()` leaves one that
someone else timed, which is how every compilation gets here: once
`watch_compiles()` is called, each leg of jax's compile path (a
function traced, lowered, compiled or read back from the persistent
cache) is a record `compile` under the span that caused it.

A record is the plain tuple ``(name, wall, dur_s, id, parent, req,
counts)``, indexed by NAME .. COUNTS: `wall` is `time.time()` at the
start, `dur_s` the extent by `time.perf_counter()`, `parent` the id of
the span open around it on the same thread (0: none), `req` the
request's id where there is one, `counts` a dict of small numbers and
strings taken at the same boundary. Never an array, an engine or a
request object: the ring outlives them and must not keep them alive.

Always on; no switch and no sampling. It stays cheap by where a span
may stand: per engine step, per pump, per admission, per request —
never per token, per slot, or inside a loop over either.

Stdlib only, and jax is never imported from here: the annotation is
opened, and the compile path listened to, only where `jax` already is
in `sys.modules`, so the agent and other processes that must not touch
the chip can trace with the ring alone.
"""

import collections
import itertools
import sys
import threading
import time
from typing import List, Optional

NAME, WALL, DUR, ID, PARENT, REQ, COUNTS = range(7)
RING_SIZE = 65536
ANNOTATION_PREFIX = "dlrover:"

_PLAIN = (int, float, str, type(None))  # bool is an int
_ring: "collections.deque[tuple]" = collections.deque(maxlen=RING_SIZE)
_ids = itertools.count(1)  # next() is one bytecode: safe across threads
_open = threading.local()  # .stack: ids of the spans open on this thread


def _stack() -> List[int]:
    try:
        return _open.stack
    except AttributeError:
        _open.stack = []
        return _open.stack


def _plain(counts: dict) -> dict:
    for key, value in counts.items():
        if not isinstance(value, _PLAIN):
            raise TypeError(
                f"trace count {key}={type(value).__name__}: a record "
                "holds numbers and strings only"
            )
    return counts


_annotate = None  # jax.profiler.TraceAnnotation, once jax is imported


def _annotation(name: str):
    """A TraceAnnotation where this process has imported jax (a flag
    test when no profiler session runs), else None."""
    global _annotate
    if _annotate is None:
        jax = sys.modules.get("jax")
        profiler = getattr(jax, "profiler", None)  # None mid-import
        if profiler is None:
            return None
        _annotate = profiler.TraceAnnotation
    return _annotate(ANNOTATION_PREFIX + name)


class Span:
    """One open span. `t0` is its start on `time.perf_counter()` and,
    after the block, `dur_s` its extent: totals that cover the same
    boundary are fed from these two readings, not from a second clock.
    `set()` adds counts known only inside the block."""

    __slots__ = (
        "name", "req", "counts", "id", "parent", "wall", "t0", "dur_s",
        "_annotation",
    )

    def __init__(self, name: str, req, counts: dict):
        self.name, self.req, self.counts = name, req, _plain(counts)
        self.dur_s = 0.0

    def set(self, **counts) -> None:
        self.counts.update(_plain(counts))

    def __enter__(self) -> "Span":
        stack = _stack()
        self.parent = stack[-1] if stack else 0
        self.id = next(_ids)
        stack.append(self.id)
        self._annotation = _annotation(self.name)
        if self._annotation is not None:
            self._annotation.__enter__()
        self.wall = time.time()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        self.dur_s = time.perf_counter() - self.t0
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
        _stack().pop()
        _ring.append((
            self.name, self.wall, self.dur_s, self.id, self.parent,
            self.req, self.counts,
        ))
        return False


def span(name: str, req: Optional[int] = None, **counts) -> Span:
    """``with span("engine.step") as sp: ...; sp.set(wait_s=...)``"""
    return Span(name, req, counts)


def record(name: str, wall: float, dur_s: float,
           req: Optional[int] = None, /, **counts) -> None:
    """A stretch that someone else timed, `wall` on `time.time()`:
    the tuple a span closed now would leave, under the span open on
    this thread."""
    stack = _stack()
    _ring.append((
        name, wall, dur_s, next(_ids), stack[-1] if stack else 0,
        req, _plain(counts),
    ))


def event(name: str, req: Optional[int] = None, **stamps) -> None:
    """A record with no extent, under the span open on this thread."""
    record(name, time.time(), 0.0, req, **stamps)


def snapshot(since: float = 0.0, until: float = float("inf")) -> List[tuple]:
    """The records whose start lies in [since, until] on `time.time()`,
    oldest first (a span is recorded when it closes, so a parent comes
    after its children)."""
    while True:
        try:
            records = list(_ring)
            break
        except RuntimeError:  # another thread appended mid-copy
            continue
    return [r for r in records if since <= r[WALL] <= until]


def clear() -> None:
    _ring.clear()


# ---- compilations ----------------------------------------------------
# jax times its own compile path and tells `jax.monitoring`'s listeners,
# synchronously on the thread that compiles: when a leg opens (a scalar),
# what the persistent cache said inside the backend leg (events), and
# when a leg closes (a time span on `time.time()`, the ring's clock).

_LEGS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}
_CACHE = {  # in the order jax fires them inside a backend leg
    "/jax/compilation_cache/compile_requests_use_cache": "miss",
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "miss",
}
# the least compile time `runtime.enable_compile_cache` has jax store:
# a faster program "misses" on every start and says nothing
STORED_COMPILE_S = 1.0


class _Compiling(threading.local):
    """This thread's place on jax's compile path and what it has
    spent there: the legs open (a function traced inside another
    nests), the cache's word inside the open backend leg, and two
    running totals a span reads as a difference (`compiled()`)."""

    depth = 0
    cache = "off"
    seconds = 0.0  # outermost legs only: no stretch counted twice
    programs = 0  # backend legs closed


_compiling = _Compiling()
_watching = False
_watch_lock = threading.Lock()


def _on_leg_open(event: str, _value=None, **_kw) -> None:
    if event in _LEGS:
        _compiling.depth += 1


def _on_cache(event: str, **_kw) -> None:
    said = _CACHE.get(event)
    if said is not None:
        _compiling.cache = said


def _on_leg(event: str, start: float, end: float, **kw) -> None:
    leg = _LEGS.get(event)
    if leg is None:
        return
    own = _compiling
    own.depth = max(0, own.depth - 1)  # 0: a leg open before the watch
    if own.depth == 0:
        own.seconds += end - start
    counts = {"leg": leg, "program": str(kw.get("fun_name", ""))}
    if leg == "backend":
        own.programs += 1
        counts["cache"], own.cache = own.cache, "off"
    record("compile", start, end - start, **counts)


def watch_compiles() -> bool:
    """Leave a record `compile` (counts `leg`: trace, lower or
    backend; `program`: jax's name for the function; on the backend
    leg `cache`: hit, miss or off) for every leg of jax's compile
    path from now on. Idempotent; False, and nothing registered, in a
    process that has not imported jax."""
    global _watching
    monitoring = getattr(sys.modules.get("jax"), "monitoring", None)
    if monitoring is None:
        return False
    with _watch_lock:
        if not _watching:
            _watching = True
            monitoring.register_scalar_listener(_on_leg_open)
            monitoring.register_event_listener(_on_cache)
            monitoring.register_event_time_span_listener(_on_leg)
    return True


def compiled() -> tuple:
    """(seconds, programs) this thread has spent on jax's compile
    path and compiled or read back since the watch began: a span that
    wants its own share reads it before and after."""
    return _compiling.seconds, _compiling.programs


def _union_s(legs: List[tuple]) -> float:
    """Seconds covered by the records' [start, end] stretches."""
    total, reach = 0.0, float("-inf")
    for start, end in sorted((r[WALL], r[WALL] + r[DUR]) for r in legs):
        if end > reach:
            total += end - max(start, reach)
            reach = end
    return total


def compile_totals(
    since: float = 0.0, until: float = float("inf")
) -> Optional[dict]:
    """What the `compile` records that start in [since, until] come
    to: `trace_lower_s` and `backend_s` (the legs' unions: a function
    traced inside another is counted once), `programs` (backend
    legs), `cache_hits`, `cache_misses` (of the programs slow enough
    to be stored), `slowest` (the program whose legs sum longest) and
    `first_wall` (the earliest leg's start). None where there is no
    such record, or where the ring is full and no longer reaches back
    to `since`: a sum over what is left would be a wrong number."""
    records = snapshot()
    if len(records) == RING_SIZE and records[0][WALL] > since:
        return None
    legs = [
        r for r in records
        if r[NAME] == "compile" and since <= r[WALL] <= until
    ]
    if not legs:
        return None
    backend = [r for r in legs if r[COUNTS]["leg"] == "backend"]
    by_program: dict = {}
    for r in legs:
        name = r[COUNTS]["program"]
        if name.startswith("jit(") and name.endswith(")"):
            name = name[4:-1]  # the trace leg's name has no wrapper
        by_program[name] = by_program.get(name, 0.0) + r[DUR]
    return {
        "trace_lower_s": _union_s(
            [r for r in legs if r[COUNTS]["leg"] != "backend"]),
        "backend_s": _union_s(backend),
        "programs": len(backend),
        "cache_hits": sum(r[COUNTS]["cache"] == "hit" for r in backend),
        "cache_misses": sum(
            r[COUNTS]["cache"] == "miss" and r[DUR] >= STORED_COMPILE_S
            for r in backend
        ),
        "slowest": max(by_program, key=by_program.get),
        "first_wall": min(r[WALL] for r in legs),
    }
