#!/usr/bin/env python3
"""Builder's aid: a look by hand at the last run's profiler trace with its
event stats, which `chip_session.py --keep-trace` leaves out. Not part of a
benchmark run.

    python3 benchmark/tools/trace_stats.py <out_dir> [<trace.xplane.pb>]

Prints, per device plane, the stat names its operation events carry, a few
events with all their stats, and the device time by scope and by program as
the readers reckon them; per host plane, the `qw.*` spans by name. Writes
`trace_stats.txt` and a small recorded form (`trace_events_recorded.json`,
the plain form of `trace_events.py`, cut to the first events of each line)
into <out_dir>.
"""

from __future__ import annotations

import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(HERE, "readers"))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import trace_events
import trace_gap_spans
import trace_named_ms

SHOWN = 12
KEPT = 300


def describe(planes: dict) -> list:
    out = []
    for plane, lines in sorted(planes.items()):
        out.append(f"plane {plane!r}")
        for entry in lines:
            events = entry["events"]
            stat_names: dict = {}
            for event in events:
                for name in event[3]:
                    stat_names[name] = stat_names.get(name, 0) + 1
            out.append(f"  line {entry['line']!r}: {len(events)} events; "
                       f"stats {stat_names}")
            if plane.startswith(trace_events.DEVICE_PLANE):
                values: dict = {}
                for event in events:    # a few distinct values of each stat
                    for name, value in event[3].items():
                        seen = values.setdefault(name, [])
                        if value not in seen and len(seen) < 6:
                            seen.append(value)
                out.append(f"    stat values: {json.dumps(values)[:4000]}")
            longest = sorted(events, key=lambda e: -e[2])[:SHOWN]
            for name, start, duration, stats in events[:4] + longest:
                out.append(f"    {name[:110]!r} start_ns={start} "
                           f"dur_ns={duration}")
                out.append(f"      {json.dumps(stats)[:1500]}")
    table = trace_named_ms.by_scope(planes)
    out.append(f"device ms by scope: {table}")
    programs: dict = {}
    for _, events in trace_events.device_lines(planes,
                                               trace_events.MODULES_LINE):
        for name, _, duration, _ in events:
            key = name.split("(")[0]
            count, total = programs.get(key, (0, 0.0))
            programs[key] = (count + 1, total + duration / 1e6)
    out.append(f"programs (runs, ms): {programs}")
    spans: dict = {}
    for name, _, duration, _ in trace_events.host_spans(planes):
        count, total = spans.get(name, (0, 0.0))
        spans[name] = (count + 1, total + duration / 1e6)
    out.append(f"host spans (count, ms): {spans}")
    if spans:
        idle, covered, inside = trace_gap_spans.attribute(planes)
        out.append(f"idle ns {idle}, inside a span {covered}, by span "
                   f"{sorted(inside.items(), key=lambda kv: -kv[1])[:8]}")
    return out


def main(argv: list) -> int:
    out_dir = argv[0]
    path = argv[1] if len(argv) > 1 else trace_events.find_trace()
    if not path:
        print("no trace file found")
        return 1
    os.makedirs(out_dir, exist_ok=True)
    planes = trace_events.load(path)
    text = "\n".join(describe(planes))
    print(text[-6000:])
    with open(os.path.join(out_dir, "trace_stats.txt"), "w") as fh:
        fh.write(text + "\n")
    recorded = {plane: [{"line": entry["line"],
                         "events": entry["events"][:KEPT]}
                        for entry in lines]
                for plane, lines in planes.items()}
    with open(os.path.join(out_dir, "trace_events_recorded.json"), "w") as fh:
        json.dump(recorded, fh)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
