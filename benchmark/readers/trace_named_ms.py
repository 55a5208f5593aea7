"""Device time (ms) of named work in the profiler trace.

- `per: "event"`: the median duration of one event of `line` whose name
  contains `match` (one run of a program on the `XLA Modules` line).
- `per: "query"`: the summed duration of the `line`'s operations whose scope
  is `match` (a name of `trace_events.SCOPES`, a list of them, or
  "unscoped"), averaged over the devices that ran any, over the requests
  answered inside the traced span by the load generator's clock. An
  operation's scope is the outermost vocabulary name in its framework-op
  path, and `mask_fill` for every operation that ran inside a mask-fill
  program, whatever its own path says.

Nothing where the trace has no such event: a program without the names
(`jit_qw_…`) or the scopes reads as None, never as 0. args: line, match, per.
"""

import statistics

import trace_events


def read(run, line: str, match, per: str):
    planes = trace_events.of(run)
    if not planes:
        return None
    if per == "event":
        found = [d / 1e6 for _, events in trace_events.device_lines(planes,
                                                                    line)
                 for name, _, d, *_ in events if match in name]
        return float(statistics.median(found)) if found else None
    lo, hi = run.trace_span
    answered = sum(1 for r in run.records
                   if r["ok"] and lo <= r["t_done"] <= hi)
    table = by_scope(planes, line)
    if not answered or table is None:
        return None
    wanted = [match] if isinstance(match, str) else list(match)
    return float(sum(table.get(name, 0.0) for name in wanted) / answered)


def by_scope(planes: dict, line: str = trace_events.OPS_LINE):
    """{scope or "unscoped": ms} summed over the line's operations and
    averaged over the devices that ran any; None where no operation of the
    trace carries a scope (a program from before the scopes)."""
    tables = []
    for plane, events in trace_events.device_lines(planes, line):
        if not events:
            continue
        fills = trace_events.intervals_union([
            (s, s + d) for name, s, d, *_ in trace_events.plane_line(
                planes, plane, trace_events.MODULES_LINE)
            if trace_events.MASK_FILL_PROGRAM in name])
        table: dict = {}
        for _, start, duration, stats in events:
            scope = trace_events.scope_of(stats)
            if fills and trace_events.overlap([(start, start + duration)],
                                              fills) > 0:
                scope = "mask_fill"
            key = scope or "unscoped"
            table[key] = table.get(key, 0.0) + duration / 1e6
        tables.append(table)
    if not any(set(t) - {"unscoped"} for t in tables):
        return None
    return {key: sum(t.get(key, 0.0) for t in tables) / len(tables)
            for key in set().union(*tables)}
