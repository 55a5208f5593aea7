"""Share (%) of the device's idle time that lies inside a host span of the
program: the idle seconds are the gaps between consecutive busy stretches
of each device's `XLA Ops` line, the host spans are the `qw.<phase>` events
the program writes into the profiler's trace (one clock with the device).
Also says on one stdout line the five span names that cover most idle time.
Nothing where the trace holds no `qw.*` span (a program from before them)
or the device was never idle between two operations. args: none."""

import trace_events

TOP = 5


def attribute(planes: dict):
    """(idle ns, idle ns inside any span, {span name: idle ns inside it})."""
    gaps = [gap for _, ops in trace_events.device_lines(
                planes, trace_events.OPS_LINE)
            for gap in trace_events.idle_gaps(ops)]
    gaps = trace_events.intervals_union(gaps)
    spans = trace_events.host_spans(planes)
    by_name: dict = {}
    for name, start, duration, *_ in spans:
        by_name.setdefault(name, []).append((start, start + duration))
    everything = trace_events.intervals_union(
        [i for intervals in by_name.values() for i in intervals])
    inside = {name: trace_events.overlap(
                  gaps, trace_events.intervals_union(intervals))
              for name, intervals in by_name.items()}
    return (sum(end - start for start, end in gaps),
            trace_events.overlap(gaps, everything), inside)


def read(run):
    planes = trace_events.of(run)
    if not planes or not trace_events.host_spans(planes):
        return None
    idle, covered, inside = attribute(planes)
    if not idle:
        return None
    top = sorted(inside.items(), key=lambda kv: -kv[1])[:TOP]
    print("[idle] %.3f ms idle between device operations, %.3f ms of it "
          "inside a qw.* host span; most by span: %s"
          % (idle / 1e6, covered / 1e6,
             [(name, round(ns / 1e6, 3)) for name, ns in top]), flush=True)
    return 100.0 * covered / idle
