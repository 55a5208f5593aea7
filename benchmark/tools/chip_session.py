#!/usr/bin/env python3
"""Builder's aid: several runs of the benchmark in one call on the chip, one
fresh process each, so that they share the checkout's caches. Not part of a
benchmark run.

    python3 benchmark/tools/chip_session.py [--control] [--keep-trace] \\
        <workload>:<seed>:<seconds>:<trace> ...

Each run's output goes to chiprun_out/bench/<label>/; the result lines and a
spread table (median, and the distance between the quartiles over the
median, per workload and metric) are printed at the end.
"""

from __future__ import annotations

import glob
import json
import os
import shutil
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
RUN_DIR = os.path.join(ROOT, ".bench_cache", "run")


def describe_trace(path: str, out_dir: str) -> None:
    """What the trace holds, for a look by hand, and a small recorded form
    of its device planes for the test of the reduction."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from jax.profiler import ProfileData
    lines_out, recorded = [], {}
    for plane in ProfileData.from_file(path).planes:
        lines_out.append(f"plane {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            lines_out.append(f"  line {line.name!r}: {len(events)} events")
            for event in events[:6]:
                lines_out.append(f"    {event.name!r} start_ns="
                                 f"{event.start_ns} dur_ns="
                                 f"{event.duration_ns}")
            if plane.name.startswith("/device:"):
                recorded.setdefault(plane.name, {})[line.name] = [
                    [e.name, int(e.start_ns), int(e.duration_ns)]
                    for e in events[:400]]
    with open(os.path.join(out_dir, "trace_structure.txt"), "w") as fh:
        fh.write("\n".join(lines_out) + "\n")
    with open(os.path.join(out_dir, "trace_recorded.json"), "w") as fh:
        json.dump(recorded, fh)


def main(argv: list) -> int:
    control = "--control" in argv
    keep_trace = "--keep-trace" in argv
    plan = [a for a in argv if not a.startswith("--")]
    out_root = os.path.join(ROOT, "chiprun_out", "bench")
    os.makedirs(out_root, exist_ok=True)
    results = []
    for number, item in enumerate(plan):
        workload, seed, seconds, trace = item.split(":")
        label = f"{int(time.time())}_{number}_{workload}_{seed}_t{trace}"
        out_dir = os.path.join(out_root, label)
        os.makedirs(out_dir)
        script = "control.py" if control else "run.py"
        command = [sys.executable, os.path.join("benchmark", script),
                   "--workload", workload, "--seed", seed,
                   "--seconds", seconds, "--trace", trace]
        began = time.monotonic()
        with open(os.path.join(out_dir, "stdout.txt"), "w") as out, \
                open(os.path.join(out_dir, "stderr.txt"), "w") as err:
            code = subprocess.run(command, cwd=ROOT, stdout=out,
                                  stderr=err).returncode
        wall = time.monotonic() - began
        with open(os.path.join(out_dir, "stdout.txt")) as fh:
            lines = fh.read().splitlines()
        print(f"=== {item} exit {code} wall {wall:.0f}s", flush=True)
        for line in lines:
            if not line.startswith("{"):
                print("   ", line[:400], flush=True)
        node_log = os.path.join(RUN_DIR, "node.log")
        if os.path.exists(node_log):
            shutil.copy(node_log, os.path.join(out_dir, "node.log"))
        if code != 0 and not control:
            with open(os.path.join(out_dir, "stderr.txt")) as fh:
                print(fh.read()[-3000:], flush=True)
        for line in lines:
            if line.startswith("{"):
                record = json.loads(line)
                if "metrics" in record:
                    results.append((workload, int(trace), wall, record))
                print("   ", line[:6000], flush=True)
        if keep_trace and trace == "1":
            for path in glob.glob(os.path.join(RUN_DIR, "trace", "plugins",
                                               "profile", "*", "*.xplane.pb")):
                describe_trace(path, out_dir)
    print("=== summary", flush=True)
    table: dict = {}
    for workload, trace, wall, record in results:
        flat = {name: m["value"] for name, m in record["metrics"].items()}
        flat["wall_s"] = wall
        flat["correct"] = record["correct"]
        for name, check in record.get("checks", {}).items():
            flat["check." + name] = check["value"]
        if trace:
            flat["busy_s"] = record["device"].get("busy_s")
        flat["memory_peak_bytes"] = record["device"]["memory_peak_bytes"]
        flat["host_rss_peak_bytes"] = record["device"]["host_rss_peak_bytes"]
        for name, value in flat.items():
            table.setdefault((workload, trace, name), []).append(value)
    for (workload, trace, name), values in sorted(table.items()):
        text = f"{workload} trace={trace} {name}: {values}"
        numbers = [v for v in values if isinstance(v, (int, float))
                   and not isinstance(v, bool)]
        if len(numbers) >= 3 and len(numbers) == len(values):
            q1, _, q3 = statistics.quantiles(numbers, n=4)
            median = statistics.median(numbers)
            if median:
                text += (f" median {median:.6g} spread "
                         f"{(q3 - q1) / abs(median):.4f}")
        print(text, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
