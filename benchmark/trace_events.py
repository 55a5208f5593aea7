"""The profiler trace with its host planes and event stats kept, for the
readers that need more than `device_trace.py` keeps: what a device operation
was lowered from (its scope), which program ran it, and what the host was
doing at the time (`qw.<phase>` spans, which the program writes with
`jax.profiler.TraceAnnotation` while a request carries a profile).

`Run` does not hand a reader the trace's path, so `of(run)` finds the one
`*.xplane.pb` the node wrote (the pattern `NodeProcess.stop_trace` globs),
loads it once with nothing but JAX and keeps the result on the run.
Everything else works on the plain form, which a recorded JSON fixture can
hold as well:

    {plane: [{"line": name, "events": [[name, start_ns, dur_ns, stats], ...]}]}

`stats` is `{name: value}` of the event's string and number stats, and for a
device's events also the stats of the event's metadata record, which
`ProfileData` does not show (`xplane_wire.py`): what is the same for every
run of one operation, such as the path it was lowered from. A plane has a
list of lines, not a table: a host's thread lines share names. Host lines
keep their `qw.*` events only. Times of all planes are nanoseconds on the
profiler's one clock.
"""

from __future__ import annotations

import glob
import os
import re

import data
import xplane_wire
from device_trace import DEVICE_PLANE, MODULES_LINE, OPS_LINE

HOST_PLANE = "/host:"
SPAN_PREFIX = "qw."
# The program's scope vocabulary (quickwit_tpu/observability/profile.py,
# `SCOPE_*`; docs/observability.md): the yardstick's own copy. `aggs` stands
# for every `aggs.<kind>`.
SCOPES = ("term_mask", "bm25_score", "range_filter", "sort_key", "topk",
          "aggs", "pack", "mask_fill")
MASK_FILL_PROGRAM = "qw_mask_fill"
# stats that may hold an operation's framework-op path, best first; any
# other string stat that names a `jit(…)` program is tried after them
PATH_STATS = ("tf_op", "op_name", "name", "long_name")


def find_trace() -> str | None:
    files = glob.glob(os.path.join(data.CACHE_DIR, "run", "trace", "plugins",
                                   "profile", "*", "*.xplane.pb"))
    return files[0] if len(files) == 1 else None


def load(path: str) -> dict:
    from jax.profiler import ProfileData
    planes: dict = {}
    metadata = xplane_wire.event_metadata(path, DEVICE_PLANE)
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith(DEVICE_PLANE)
        if not device and not plane.name.startswith(HOST_PLANE):
            continue
        lines = []
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            events = [[event.name, int(event.start_ns),
                       int(event.duration_ns), plain_stats(event)]
                      for event in line.events
                      if device or event.name.startswith(SPAN_PREFIX)]
            records = metadata.get(plane.name, {}).get(line.name, [])
            if device and len(records) == len(events):    # same file order
                for event, record in zip(events, records):
                    if record.get("name") == event[0]:
                        event[3] = {**{k: v for k, v in record.items()
                                       if k != "name"}, **event[3]}
            if events:
                lines.append({"line": line.name, "events": events})
        if lines:
            planes[plane.name] = lines
    return planes


def plain_stats(event) -> dict:
    out = {}
    for name, value in event.stats:
        if isinstance(value, (str, int, float)) and name:
            out[name] = value
        elif isinstance(value, bytes):
            out[name] = value.decode(errors="replace")
    return out


def of(run) -> dict | None:
    """The run's trace in the plain form, loaded once; None where the run
    took no trace or its file cannot be found."""
    if not run.trace:
        return None
    if not hasattr(run, "trace_events"):
        path = find_trace()
        run.trace_events = load(path) if path else None
    return run.trace_events


def plane_line(planes: dict, plane: str, line: str) -> list:
    """The events of the named line of one plane."""
    return [event for entry in planes.get(plane, [])
            if entry["line"] == line for event in entry["events"]]


def device_lines(planes: dict, line: str) -> list:
    """[(plane, events)] of the named line on each device plane."""
    return [(plane, plane_line(planes, plane, line))
            for plane in sorted(planes) if plane.startswith(DEVICE_PLANE)]


def host_spans(planes: dict) -> list:
    """Every `qw.*` event of every host thread: [name, start_ns, dur_ns,
    stats]."""
    return [event for name, lines in planes.items()
            if name.startswith(HOST_PLANE)
            for entry in lines for event in entry["events"]
            if event[0].startswith(SPAN_PREFIX)]


WORD = re.compile(r"[A-Za-z_][\w.]*")


def scope_of(stats: dict) -> str | None:
    """The outermost name of the vocabulary in the operation's framework-op
    path (`jit(qw_solo_k10)/jit(main)/aggs.terms/scatter-add` is `aggs`; a
    vmapped program says `jit(qw_stacked_q4_k10)/vmap(aggs.terms)/…`), or
    None where no stat holds one. An operation counts once."""
    names = [n for n in PATH_STATS if n in stats] + sorted(
        n for n in stats if n not in PATH_STATS)
    for name in names:
        value = stats[name]
        if not isinstance(value, str) or "jit(" not in value:
            continue        # a path starts with the program: jit(<name>)/…
        for word in WORD.findall(value):
            head = "aggs" if word.startswith("aggs.") else word
            if head in SCOPES:
                return head
    return None


def intervals_union(intervals: list) -> list:
    """Sorted, disjoint [(start, end)] covering the same points."""
    out: list = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], end))
        elif end > start:
            out.append((start, end))
    return out


def overlap(first: list, second: list) -> float:
    """Length of the intersection of two disjoint sorted interval lists."""
    total, j = 0, 0
    for start, end in first:
        while j < len(second) and second[j][1] <= start:
            j += 1
        k = j
        while k < len(second) and second[k][0] < end:
            total += min(end, second[k][1]) - max(start, second[k][0])
            k += 1
    return total


def idle_gaps(ops: list) -> list:
    """[(start, end)] between consecutive busy stretches of one device's
    operations: what `device_trace.gaps` measures, kept as intervals."""
    busy = intervals_union([(s, s + d) for _, s, d, *_ in ops])
    return [(a[1], b[0]) for a, b in zip(busy, busy[1:])]
