"""Median over the window's profiled requests of the time (ms) that no phase
covers: `wall_ms` minus the union of all phase intervals (`start_ms`,
`duration_ms`) of the root profile, overlapping phases on several threads
counted once. A remote leaf's child profile has a clock of its own, so only
the length of its own union is taken off, as if it overlapped nothing; an
embedded leaf (every leaf of a one-node deployment) writes into the root
profile and is exact. Also says on one stdout line between which phases the
widest uncovered intervals lie. args: none."""

import statistics

TOP = 5


def covered(profile: dict, wall_ms: float) -> list:
    """Disjoint sorted (start, end, first phase, phase that ends last) of
    the profile's own phases, cut to [0, wall_ms]."""
    spans = []
    for phase in profile.get("phases") or []:
        start = max(0.0, phase.get("start_ms", 0.0))
        end = min(wall_ms, start + phase.get("duration_ms", 0.0))
        spans.append((start, end, phase.get("name")))
    out: list = []
    for start, end, name in sorted(spans):
        if out and start <= out[-1][1]:
            if end > out[-1][1]:
                out[-1] = (out[-1][0], end, out[-1][2], name)
        elif end > start:
            out.append((start, end, name, name))
    return out


def child_ms(profile: dict) -> float:
    total = 0.0
    for child in profile.get("leaves") or []:
        wall = child.get("wall_ms") or float("inf")
        total += sum(span[1] - span[0] for span in covered(child, wall))
        total += child_ms(child)
    return total


def uncovered(profile: dict) -> tuple:
    """(uncovered ms, [(ms, phase before the hole, phase after it)]) of one
    request."""
    wall = profile["wall_ms"]
    holes, at, before = [], 0.0, "start"
    for start, end, first, last in covered(profile, wall):
        if start > at:
            holes.append((start - at, before, first))
        at, before = end, last
    if wall > at:
        holes.append((wall - at, before, "end"))
    bare = sum(ms for ms, _, _ in holes)
    return max(0.0, bare - child_ms(profile)), holes


def read(run):
    totals, walls, between = [], [], {}
    for record in run.records:
        profile = record.get("profile")
        if not profile or profile.get("wall_ms") is None:
            continue
        bare, holes = uncovered(profile)
        totals.append(bare)
        walls.append(profile["wall_ms"])
        for ms, before, after in holes:
            between.setdefault((before, after), []).append(ms)
    if not totals:
        return None
    # the mean over all profiled requests, a request without such a hole
    # counting 0
    widest = sorted(((sum(v) / len(totals), key) for key, v in
                     between.items()), reverse=True)[:TOP]
    print("[uncovered] median wall %.3f ms over %d profiled requests; mean "
          "uncovered ms per request by neighbours (phase before, phase after): %s"
          % (statistics.median(walls), len(totals),
             [(round(ms, 3), key) for ms, key in widest]), flush=True)
    return float(statistics.median(totals))
