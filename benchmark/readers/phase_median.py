"""Median over the window's profiled requests of the time (ms) a request
spent in the named `?profile=true` phases, root and leaves together.
Requests that recorded none of the phases are left out. args: phases."""

import statistics


def read(run, phases: list):
    sums = []
    for record in run.records:
        if not record.get("profile"):
            continue
        found = [phase.get("duration_ms", 0.0)
                 for node in run.profile_nodes(record["profile"])
                 for phase in node.get("phases") or []
                 if phase.get("name") in phases]
        if found:
            sums.append(sum(found))
    return float(statistics.median(sums)) if sums else None
