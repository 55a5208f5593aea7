"""Reduction of a `jax.profiler` trace (`*.xplane.pb`) to the device's busy
time, its busiest operations and its longest idle gaps.

`load` reads the device planes with nothing but JAX; `reduce` works on the
plain form `{plane: {line: [[name, start_ns, duration_ns], ...]}}`, which is
also what the recorded trace of the test holds. A device is busy while an
event of its operations line runs; busy time is the union of those
intervals, averaged over the devices that ran anything. The traced span is
the node's own clock from the profiler's start to its stop, or the span of
the device's events where that is longer (operations in flight at the stop
are traced to their end), so busy time never exceeds it.
"""

from __future__ import annotations

DEVICE_PLANE = "/device:"
OPS_LINE = "XLA Ops"            # one event per device operation
MODULES_LINE = "XLA Modules"    # one event per executed program
TOP = 10


def load(path: str) -> dict:
    from jax.profiler import ProfileData
    planes = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(DEVICE_PLANE):
            planes[plane.name] = {
                line.name: [[event.name, int(event.start_ns),
                             int(event.duration_ns)] for event in line.events]
                for line in plane.lines}
    return planes


def union_ns(events: list) -> int:
    busy, end = 0, None
    for _, start, duration in sorted(events, key=lambda e: e[1]):
        stop = start + duration
        if end is None or start > end:
            busy += duration
            end = stop
        elif stop > end:
            busy += stop - end
            end = stop
    return busy


def gaps(events: list, names: list) -> list:
    """[(what ran next, gap seconds)] between consecutive busy stretches;
    `names` are the program events used to say what followed the gap."""
    out, end = [], None
    programs = sorted(names, key=lambda e: e[1])
    for _, start, duration in sorted(events, key=lambda e: e[1]):
        if end is not None and start > end:
            following = next((n for n, s, d in programs if s + d > start),
                             "?")
            out.append((f"before:{following}", (start - end) / 1e9))
        end = max(end or 0, start + duration)
    return out


def reduce(planes: dict, window_s: float) -> dict:
    """`{"busy_s", "window_s", "chips", "device_ops", "idle_gaps"}` or None
    where no device plane holds an operation."""
    busy, ops, idle, spans = [], {}, {}, [window_s]
    for lines in planes.values():
        events = lines.get(OPS_LINE)
        if events is None:      # a backend with no such line: every event
            events = [e for line in lines.values() for e in line]
        if not events:
            continue
        busy.append(union_ns(events) / 1e9)
        spans.append((max(s + d for _, s, d in events)
                      - min(s for _, s, d in events)) / 1e9)
        for name, _, duration in events:
            ops[name] = ops.get(name, 0.0) + duration / 1e9
        for name, seconds in gaps(events, lines.get(MODULES_LINE, events)):
            idle[name] = idle.get(name, 0.0) + seconds
    if not busy:
        return None
    chips = len(busy)

    def top(table):
        return [[name, seconds / chips] for name, seconds in
                sorted(table.items(), key=lambda kv: -kv[1])[:TOP]]
    return {"busy_s": sum(busy) / chips, "window_s": max(spans),
            "chips": chips, "device_ops": top(ops), "idle_gaps": top(idle)}
