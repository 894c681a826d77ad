"""From a profiler trace (.xplane.pb) to the few numbers the
benchmark reports: the seconds in which an operation ran on the
device, the traced window, device time per operation by name, and the
idle gaps by what the host was doing in them. Read with nothing but
jax (jax.profiler.ProfileData). Only the process that holds the chip
can trace it, so that process calls this on its own trace and hands
its parent the small dict.

What counts as a device operation: an event on a `/device:` plane's
"XLA Ops" line. There an event's name is the whole text of its HLO
instruction ("%fusion.7 = bf16[...] fusion(...)"); it is cut to the
instruction's name ("fusion.7"). A `while` or a `call` spans the
operations of its body on the same line, so an operation's seconds
are its SELF time: its span less the spans nested inside it. (On the CPU backend, which has no device plane, the
rehearsal takes the host events that carry an `hlo_op` stat, so that
the same code runs; it proves nothing about a chip.)

The host's spans are the `perfbench:*` TraceAnnotations the
benchmark's own files put around their calls into each layer; they
are on the trace's clock. A gap is charged to the innermost such span
that covers its middle, or to "(no span)".
"""

import glob
import os

SPAN_PREFIX = "perfbench:"
OPS_LINE = "XLA Ops"
TOP = 10


def profile_options():
    """Host TraceMe events on, the Python call tracer off: it makes
    traces of a few seconds hundreds of megabytes and slows the host
    that is being measured."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    return options


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    ))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def reduce_dir(trace_dir: str) -> dict:
    return reduce_file(find_xplane(trace_dir))


def op_name(event_name: str) -> str:
    """The instruction's name out of an XLA Ops event's name."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def self_seconds(events) -> dict:
    """{name: self seconds} of one line's (name, start, dur) events,
    which nest (a while around its body) but never cross."""
    out, stack = {}, []  # stack of [name, end, self_ns]

    def close(upto):
        while stack and stack[-1][1] <= upto:
            name, _, own = stack.pop()
            out[name] = out.get(name, 0.0) + own / 1e9

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        close(start)
        if stack:
            stack[-1][2] -= dur
        stack.append([name, start + dur, dur])
    close(float("inf"))
    return out


def _union(intervals):
    """Merged [start, end) intervals, sorted."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def read_planes(path: str) -> dict:
    """{"devices": {plane: [(name, start_ns, dur_ns)]}, "spans":
    [(name, start_ns, dur_ns)]} — the part of a trace the reduction
    uses, as plain tuples (also what the recorded test trace holds)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host_ops, spans = {}, [], []
    for plane in data.planes:
        on_device = plane.name.startswith("/device:")
        for line in plane.lines:
            if on_device:
                if line.name != OPS_LINE:
                    continue
                devices.setdefault(plane.name, []).extend(
                    (op_name(e.name), float(e.start_ns),
                     float(e.duration_ns))
                    for e in line.events
                )
                continue
            for e in line.events:
                if e.name.startswith(SPAN_PREFIX):
                    spans.append(
                        (e.name, float(e.start_ns), float(e.duration_ns))
                    )
                elif not devices and e.duration_ns > 0 and any(
                    k == "hlo_op" for k, _ in e.stats
                ):
                    host_ops.append(
                        (e.name, float(e.start_ns), float(e.duration_ns))
                    )
    if not devices and host_ops:
        devices["/host:CPU (rehearsal)"] = host_ops
    return {"devices": devices, "spans": spans}


def reduce_file(path: str) -> dict:
    return reduce_planes(read_planes(path))


def reduce_planes(planes: dict) -> dict:
    devices, spans = planes["devices"], planes["spans"]
    if not devices:
        raise RuntimeError("the trace holds no device operation")
    edges = [
        (s, s + d) for evs in devices.values() for _, s, d in evs
    ] + [(s, s + d) for _, s, d in spans]
    t_lo = min(s for s, _ in edges)
    t_hi = max(e for _, e in edges)
    busy_ns, per_op = [], {}
    first_busy = None
    for plane in sorted(devices):
        events = devices[plane]
        merged = _union((s, s + d) for _, s, d in events)
        busy_ns.append(sum(e - s for s, e in merged))
        if first_busy is None:
            first_busy = merged
        for name, seconds in self_seconds(events).items():
            per_op[name] = per_op.get(name, 0.0) + seconds
    n = len(devices)
    gaps = _gaps(first_busy, t_lo, t_hi, spans)
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])
    return {
        "window_s": (t_hi - t_lo) / 1e9,
        "busy_s": sum(busy_ns) / n / 1e9,
        "n_devices": n,
        # per operation: seconds summed over the devices, then averaged
        "op_seconds": {k: v / n for k, v in ops},
        "device_ops": [[k, v / n] for k, v in ops[:TOP]],
        "idle_gaps": gaps[:TOP],
        "span_seconds": _span_totals(spans),
    }


def _span_totals(spans) -> dict:
    out = {}
    for name, _, d in spans:
        out[name] = out.get(name, 0.0) + d / 1e9
    return out


def _gaps(busy, t_lo, t_hi, spans):
    """Idle seconds of the first device, by the host span in which
    each gap's middle falls; largest first."""
    idle, cursor = [], t_lo
    for s, e in busy:
        if s > cursor:
            idle.append((cursor, s))
        cursor = max(cursor, e)
    if t_hi > cursor:
        idle.append((cursor, t_hi))
    by_name = {}
    for s, e in idle:
        mid = (s + e) / 2
        covering = [
            (d, name) for name, s0, d in spans if s0 <= mid < s0 + d
        ]
        name = min(covering)[1] if covering else "(no span)"
        by_name[name] = by_name.get(name, 0.0) + (e - s) / 1e9
    return [[k, v] for k, v in sorted(by_name.items(), key=lambda kv: -kv[1])]


def kernel_seconds(reduced: dict, prefixes) -> float:
    """Device seconds of the operations whose name starts with one of
    `prefixes` (a Pallas kernel's events carry the kernel's name)."""
    return sum(
        v for k, v in reduced["op_seconds"].items()
        if any(k.startswith(p) for p in prefixes)
    )
