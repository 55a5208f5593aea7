"""Sum of one profile counter over the window's profiled requests, root and
leaves together. args: counter."""


def read(run, counter: str):
    profiles = [r["profile"] for r in run.records if r.get("profile")]
    if not profiles:
        return None
    values = (node.get("counters", {}).get(counter, 0)
              for profile in profiles for node in run.profile_nodes(profile))
    return float(sum(v for v in values if isinstance(v, (int, float))))
