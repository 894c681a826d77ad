"""Spans, events and their counts: the program's one tracing primitive.

A span is a named stretch of host time at a layer boundary. Closing it
appends one record to a process-wide bounded ring; while a
`jax.profiler` session runs, the span also lies on the device trace's
own clock as the TraceAnnotation ``dlrover:<name>``. An event is a
record with no extent (a request's legs).

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
opened only where `jax` already is in `sys.modules`, so the agent and
other processes that must not touch the chip can trace with the ring
alone.
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


def event(name: str, req: Optional[int] = None, **stamps) -> None:
    """A record with no extent, under the span open on this thread."""
    stack = _stack()
    _ring.append((
        name, time.time(), 0.0, next(_ids), stack[-1] if stack else 0,
        req, _plain(stamps),
    ))


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
