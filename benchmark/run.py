#!/usr/bin/env python3
"""One run of one cell of the benchmark, in a fresh process.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Makes or finds the cell's data, publishes it, starts a node the way users do
(through `node_main.py`), warms up the cell's own shapes, drives the cell's
closed loop over HTTP for `--seconds`, stops the node, checks a seeded sample
of the window's own answers against the plain reference, and prints one JSON
line. A run that had to write its split first lets the write settle and then
starts the node anew, so the window always meets a node in the same state.
This process never initialises a JAX backend: it pins itself to the CPU and
hands the node the environment it was given. Every earlier line says what
ran, so a failed call can be read from its tail.

Everything that belongs to one configuration, traffic mix, shape or metric is
a file found by its name in BENCHMARK.json (see README.md).
"""

from __future__ import annotations

import time

START_WALL = time.monotonic()     # before the heavier imports: set-up counts them

import argparse
import concurrent.futures
import importlib.util
import json
import os
import shutil
import signal
import statistics
import sys
import traceback

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import data
import device_trace
import peaks
import reference
import traffic
from node import NodeFailure, NodeProcess, reap

REQUIRED_PLATFORM = "tpu"
RUN_DIR = os.path.join(data.CACHE_DIR, "run")   # this run's own files
WARMUP_TIMEOUT_S = 900      # a cold compile outlasts the 30 s root deadline
CLIENT_TIMEOUT_S = 120      # socket timeout in the window; the deadline that
                            # applies there is the product's own default
WARMUP_STRETCH_S = 4.0
WARMUP_MAX_STRETCHES = 12
BURSTS = {2: 3, 4: 5, 8: 8}  # group bucket: burst (leader + 2, 4, 7 riders)
MAX_BURST_ROUNDS = 4
TRACE_SECONDS = 4.0
WRITE_SETTLE_MAX_S = 120.0  # see settle(): after a new seed's split is written
SETTLE_STRETCH_S = 5.0
STALL_S = 1.0               # no reply for this long is a standstill


class BenchFailure(Exception):
    """The run cannot give a result."""


def say(text: str) -> None:
    print(text, flush=True)


def load_module(*parts: str):
    """A reader or a roofline function, found by name under benchmark/."""
    path = os.path.join(HERE, *parts[:-1], parts[-1] + ".py")
    spec = importlib.util.spec_from_file_location("_".join(parts), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_cell(name: str) -> dict:
    """The cell's entry of BENCHMARK.json with its configuration, its
    traffic mix and the metrics it reports, each from its own file."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        manifest = json.load(fh)
    cells = {cell["name"]: cell for cell in manifest["workloads"]}
    if name not in cells:
        raise BenchFailure(f"no workload {name!r} in BENCHMARK.json; it has "
                           f"{sorted(cells)}")
    cell = dict(cells[name])
    entry, = (c for c in manifest["configs"] if c["name"] == cell["config"])
    with open(os.path.join(ROOT, entry["file"])) as fh:
        cell["config_file"] = json.load(fh)
    cell["mix"] = traffic.load_mix(cell["traffic"])
    # a metric that lists its cells is theirs alone; a per-layer metric that
    # lists none is every cell's that reports the end-to-end metric it moves
    cell["end_to_end"] = [m for m in manifest["end_to_end"]
                          if name in m.get("workloads", [name])]
    ends = {m["name"] for m in cell["end_to_end"]}
    cell["per_layer"] = [m for m in manifest["per_layer"]
                         if name in m.get("workloads", [name])
                         and m["moves"] in ends]
    return cell


# --------------------------------------------------------------------------
# load: closed-loop clients over HTTP


def send(node: NodeProcess, index: str, shapes: dict, request: dict,
         profile: bool, timeout_s) -> dict:
    """One request, timed from the client's side; the reply's bytes are kept
    and parsed after the window."""
    shape = shapes[request["shape"]]
    body = json.dumps(traffic.es_body(shape, request["lo"], request["hi"],
                                      profile, timeout_s)).encode()
    record = dict(request, ok=True, raw=None, error=None)
    record["t_send"] = time.monotonic()
    try:
        record["raw"] = node.post(
            f"/api/v1/_elastic/{index}/_search", body,
            timeout=(timeout_s or 0) + CLIENT_TIMEOUT_S)
    except NodeFailure as exc:
        record["ok"], record["error"] = False, str(exc)
    record["t_done"] = time.monotonic()
    record["latency_ms"] = (record["t_done"] - record["t_send"]) * 1000.0
    return record


def closed_loop(node, index, mix, stream, seconds: float, profile: bool,
                timeout_s) -> tuple:
    """`clients` threads, each sending its next request when the previous
    reply arrives, until `seconds` have passed; every request sent is waited
    for. Returns (records, window start, window end)."""
    shapes = mix["shape_files"]
    owned = traffic.client_shapes(mix)
    started = time.monotonic()
    deadline = started + seconds

    def client(number: int) -> list:
        mine = []
        while time.monotonic() < deadline:
            mine.append(send(node, index, shapes, stream.take(owned[number]),
                             profile, timeout_s))
        return mine

    with concurrent.futures.ThreadPoolExecutor(mix["clients"]) as pool:
        futures = [pool.submit(client, n) for n in range(mix["clients"])]
        records = [r for f in futures for r in f.result()]
    return sorted(records, key=lambda r: r["t_send"]), started, deadline


def parse(records: list) -> None:
    """Parse each reply once, after the window: the profile for the readers,
    the rest for the check; the bytes are dropped."""
    for record in records:
        if record["ok"]:
            try:
                reply = json.loads(record["raw"])
                record["profile"] = reply.pop("profile", None)
                record["reply"] = reply
            except (ValueError, AttributeError) as exc:
                record["ok"], record["error"] = False, f"bad reply: {exc}"
        record["raw"] = None


def profile_nodes(profile: dict):
    """The root profile and every leaf profile under it."""
    stack = [profile]
    while stack:
        node = stack.pop()
        yield node
        stack.extend(node.get("leaves") or [])


def compile_misses(records: list) -> int:
    return int(sum(node.get("counters", {}).get("compile_cache_misses", 0)
                   for r in records if r.get("profile")
                   for node in profile_nodes(r["profile"])))


def expect_answered(records: list, what: str) -> None:
    bad = [r for r in records if not r["ok"]]
    if bad:
        raise BenchFailure(f"{what}: {len(bad)} of {len(records)} requests "
                           f"failed, the first with {bad[0]['error']}")


def group_buckets(metrics: dict) -> dict:
    """Stacked groups seen so far, by the histogram's upper bound."""
    name = "qw_qbatch_queries_per_dispatch_bucket"
    bounds = sorted((float(series.partition('le="')[2].rstrip('"}')), value)
                    for series, value in metrics.items()
                    if series.startswith(name) and "Inf" not in series)
    out, below = {}, 0.0
    for bound, value in bounds:
        out[bound] = value - below
        below = value
    return out


def warm_up(node, index, mix, seed: int) -> None:
    """Every program the window can need, and no other: each shape once
    alone, then bursts of one shape until a stacked group has formed in
    every size bucket (2, 4, 8: a program of their own each) that the
    shape's clients can fill, then the cell's own closed loop from a stream
    of its own until a stretch compiles nothing."""
    shapes = mix["shape_files"]
    spec = mix["range"]
    rng = np.random.default_rng([seed, 2])

    def fresh(shape: str) -> dict:
        lo = spec["origin_s"] + int(rng.integers(0, spec["lo_max_s"] + 1))
        width = int(rng.integers(spec["width_min_s"], spec["width_max_s"] + 1))
        return {"index": -1, "shape": shape, "lo": lo, "hi": lo + width}

    for shape in shapes:
        began = time.monotonic()
        record = send(node, index, shapes, fresh(shape), True,
                      WARMUP_TIMEOUT_S)
        parse([record])
        expect_answered([record], f"warm-up of {shape}")
        say(f"[warm-up] {shape} alone: {time.monotonic() - began:.1f}s, "
            f"compile misses {compile_misses([record])}")
    owners = traffic.client_shapes(mix)
    for shape in shapes:
        began, misses, before = time.monotonic(), 0, node.metrics()
        # a group is at most the clients that can send this shape at once
        most = owners.count(shape)
        buckets = [b for b in BURSTS if b // 2 < most]
        seen = {}
        for round_no in range(MAX_BURST_ROUNDS if buckets else 0):
            for size in (BURSTS[b] for b in buckets):
                with concurrent.futures.ThreadPoolExecutor(size) as pool:
                    records = list(pool.map(
                        lambda r: send(node, index, shapes, r, True,
                                       WARMUP_TIMEOUT_S),
                        [fresh(shape) for _ in range(size)]))
                parse(records)
                expect_answered(records, f"warm-up burst of {shape}")
                misses += compile_misses(records)
            now = group_buckets(node.metrics())
            was = group_buckets(before)
            seen = {b: now[b] - was.get(b, 0.0) for b in now}
            if all(seen.get(float(b), 0) > 0 for b in buckets):
                break
            if round_no == 0 and not any(seen.values()):
                # the first round stacked nothing at all: the deployment
                # does not stack this shape (one fused program over its
                # splits takes it), and further rounds would warm nothing up
                break
        say(f"[warm-up] {shape} bursts for group buckets {buckets}: "
            f"{time.monotonic() - began:.1f}s, compile misses {misses}, "
            f"stacked groups by size bucket "
            f"{ {int(b): int(n) for b, n in seen.items() if n} }")
    stream = traffic.RequestStream(mix, seed, 1)
    for stretch in range(WARMUP_MAX_STRETCHES):
        records, _, _ = closed_loop(node, index, mix, stream,
                                    WARMUP_STRETCH_S, True, WARMUP_TIMEOUT_S)
        parse(records)
        expect_answered(records, "warm-up loop")
        misses = compile_misses(records)
        say(f"[warm-up] closed loop stretch {stretch}: {len(records)} "
            f"requests, compile misses {misses}")
        if misses == 0:
            return
    raise BenchFailure(f"the warm-up loop still compiled after "
                       f"{WARMUP_MAX_STRETCHES} stretches")


def settle(node, index, mix, seed: int, written_at: float) -> None:
    """After a split was written in this run: some 55-90 s after its 1.4 GB
    reach the disk the whole machine stands still for 1-4 s, node and load
    generator alike (PERF.md, stall note). Keep the cell's loop running
    until that has been seen and is over, or until it is overdue, so that it
    does not fall into the window. The caller then starts the node anew."""
    began = time.monotonic()
    stream = traffic.RequestStream(mix, seed, 4)
    while time.monotonic() < written_at + WRITE_SETTLE_MAX_S:
        records, started, _ = closed_loop(node, index, mix, stream,
                                          SETTLE_STRETCH_S, False,
                                          WARMUP_TIMEOUT_S)
        expect_answered(records, "settling loop")
        times = [started] + sorted(r["t_done"] for r in records)
        gap = max(b - a for a, b in zip(times, times[1:]))
        if gap >= STALL_S:
            say(f"[data] the machine stood still for {gap:.1f}s, "
                f"{time.monotonic() - written_at:.0f}s after the split was "
                f"written; settled in {time.monotonic() - began:.0f}s")
            return
    say(f"[data] no standstill within {WRITE_SETTLE_MAX_S:.0f}s of the "
        f"split's write; waited {time.monotonic() - began:.0f}s")


# --------------------------------------------------------------------------
# the check, once the window has closed and the node is gone


def check_sample(records: list, count: int, seed: int) -> list:
    """A sample of the window's answered requests, drawn from the seed: the
    slowest, then equally many of each shape as far as they go."""
    answered = [r for r in records if r["ok"]]
    if not answered:
        return []
    rng = np.random.default_rng([seed, 3])
    chosen = {max(answered, key=lambda r: r["latency_ms"])["index"]}
    by_shape: dict = {}
    for record in answered:
        by_shape.setdefault(record["shape"], []).append(record)
    order = {shape: list(rng.permutation(len(rows)))
             for shape, rows in sorted(by_shape.items())}
    while len(chosen) < min(count, len(answered)):
        for shape, picks in order.items():
            if picks and len(chosen) < count:
                chosen.add(by_shape[shape][picks.pop()]["index"])
    return [r for r in answered if r["index"] in chosen]


def reference_over(config: dict, splits: list) -> reference.Reference:
    """Over the documents `data.py` drew for each split and kept beside it;
    nothing of the split file itself."""
    return reference.Reference([
        reference.Corpus(os.path.basename(s["path"])[:-len(".split")],
                         s["docs"], s["body_tokens"],
                         sorted(config["assumed"]["severities"]))
        for s in splits])


def check(cell: dict, records: list, splits: list, seed: int,
          answer=None) -> dict:
    """Each number compared, beside its limit: `{name: {"value", "limit"}}`.
    `splits` are the records `data.ensure_splits` gave. `answer(shape,
    query, record, reference)` may put another answer in the program's place
    (the control)."""
    limits = cell["config_file"]["limits"]
    mix = cell["mix"]
    ref = reference_over(cell["config_file"], splits)
    sample = check_sample(records, mix["check_sample"], seed)
    numbers = {"unanswered": sum(not r["ok"] for r in records),
               "wrong_answers": 0}
    began = time.monotonic()
    for record in sample:
        shape = mix["shape_files"][record["shape"]]
        query = traffic.shape_query(shape, record["lo"], record["hi"])
        try:
            got = (answer(shape, query, record, ref) if answer
                   else reference.normalise(record["reply"]))
            found = reference.compare(shape, query, got, ref,
                                      limits["score_rel_err"])
        except (KeyError, TypeError, ValueError, IndexError) as exc:
            found = {"wrong": [f"unreadable answer: {exc!r}"]}
        if found["wrong"]:
            numbers["wrong_answers"] += 1
            if numbers["wrong_answers"] <= 5:      # the first few say why
                say(f"[check] request {record['index']} ({record['shape']} "
                    f"[{record['lo']}, {record['hi']})): "
                    + "; ".join(found["wrong"])[:400])
        for name in ("score_rel_err", "pct_rel_err"):
            if found.get(name) is not None:
                numbers[name] = max(numbers.get(name, 0.0), found[name])
    say(f"[check] {len(sample)} of {len(records)} answers of the window "
        f"against the reference in {time.monotonic() - began:.1f}s")
    return {name: {"value": value, "limit": limits[name]}
            for name, value in numbers.items()}


# --------------------------------------------------------------------------
# metrics


class Run:
    """What a reader may look at."""

    def __init__(self, cell, records, window, device, splits):
        self.cell = cell
        self.records = records
        self.window_start, self.window_end = window
        self.device = device
        self.splits = splits
        self.shapes = cell["mix"]["shape_files"]
        self.metrics_before = self.metrics_after = None
        self.trace = self.trace_span = None
        self.load_module = load_module
        self.profile_nodes = profile_nodes

    def peak(self, what: str) -> float:
        return peaks.peak(self.device["kind"], what)


def end_to_end(run: Run, seconds: float, setup_s: float) -> dict:
    """All requests of the window, from the client's side: the rate is every
    request answered inside the window over the window's seconds, the
    percentiles are over every answered request sent inside it."""
    answered = [r for r in run.records if r["ok"]]
    latencies = [r["latency_ms"] for r in answered]
    inside = sum(r["t_done"] <= run.window_end for r in answered)
    return {"search_qps": inside / seconds,
            "search_p50_ms": float(np.percentile(latencies, 50)),
            "search_p95_ms": float(np.percentile(latencies, 95)),
            "setup_s": setup_s}


def per_layer(run: Run) -> dict:
    out = {}
    for metric in run.cell["per_layer"]:
        with open(os.path.join(HERE, "metrics", metric["name"] + ".json")) as fh:
            spec = json.load(fh)
        value = load_module("readers", spec["reader"]).read(
            run, **spec.get("args", {}))
        if value is not None:
            out[metric["name"]] = value
    return out


# --------------------------------------------------------------------------


def measure(args, cell: dict, node_env: dict, workers: list,
            node_entry: str = None) -> dict:
    config = cell["config_file"]
    mix = cell["mix"]
    shutil.rmtree(RUN_DIR, ignore_errors=True)
    os.makedirs(RUN_DIR)

    splits = data.ensure_splits(config, args.seed, workers, say)
    # host memory: each process's own peak, and in `together` the sums of
    # those that were alive at one time (this process's peak so far stands
    # for what it held then)
    generator = data.generator_peak(splits)
    together = [(generator or 0) + data.rss_peak_bytes()]
    node_peaks = []

    def stop(node: NodeProcess) -> list:
        memory = node.stop()
        node_peaks.append(node.rss_peak_bytes)
        together.append(node.rss_peak_bytes + data.rss_peak_bytes())
        return memory
    node_config = {
        "node_id": "bench",
        "metastore_uri": f"file://{RUN_DIR}/metastore",
        "default_index_root_uri": "file://" + data.index_root(config,
                                                              args.seed),
        "data_dir": f"{RUN_DIR}/node-data",
        "rest": config["node"]["rest"],
    }
    config_path = os.path.join(RUN_DIR, "node.yaml")
    with open(config_path, "w") as fh:
        json.dump(node_config, fh, indent=1)    # JSON is YAML
    began = time.monotonic()
    data.publish_splits(node_config, config["index_id"], splits)
    say(f"[data] published {len(splits)} split(s) in "
        f"{time.monotonic() - began:.1f}s")

    def warm_node(log_name: str, trace_dir: str = None) -> tuple:
        began = time.monotonic()
        node = NodeProcess(config_path, node_env,
                           os.path.join(RUN_DIR, log_name), trace_dir,
                           **({"entry": node_entry} if node_entry else {}))
        device = node.wait_ready()
        say(f"[node] devices: {json.dumps(device)} native_indexer="
            f"{node.native_indexer}; ready in "
            f"{time.monotonic() - began:.1f}s")
        if device["platform"] != REQUIRED_PLATFORM:
            raise BenchFailure(f"the node runs on platform "
                               f"{device['platform']!r}, not "
                               f"{REQUIRED_PLATFORM!r}: no accelerator, no "
                               "measurement")
        if device["count"] != cell["chips"]:
            raise BenchFailure(f"the node sees {device['count']} device(s), "
                               f"the cell needs {cell['chips']}")
        if REQUIRED_PLATFORM == "tpu":
            peaks.peak(device["kind"], "hbm_bytes_per_s")  # unknown: raises
        began = time.monotonic()
        warm_up(node, config["index_id"], mix, args.seed)
        say(f"[warm-up] {time.monotonic() - began:.1f}s in all")
        return node, device

    written = [s["written_at"] for s in splits if s["written_at"]]
    if written:
        # the settling loop fills the node's caches and its memory: the
        # window gets a node of its own, as a run on a cached split does
        node, _ = warm_node("node-settling.log")
        settle(node, config["index_id"], mix, args.seed, max(written))
        stop(node)
        say("[node] the settling node has stopped; the window gets its own")
    node, device = warm_node("node.log", os.path.join(RUN_DIR, "trace")
                             if args.trace else None)

    # -- the window ---------------------------------------------------------
    stream = traffic.RequestStream(mix, args.seed, 0)
    before = node.metrics()
    setup_s = time.monotonic() - START_WALL
    tracer = None
    traced: dict = {}
    if args.trace:
        def take_trace():
            time.sleep(max(0.0, (args.seconds - TRACE_SECONDS) / 2))
            node.start_trace()
            traced["lo"] = time.monotonic()
            time.sleep(min(TRACE_SECONDS, args.seconds / 2))
            traced["hi"] = time.monotonic()
            traced["window_s"], traced["file"] = node.stop_trace()
        tracer = concurrent.futures.ThreadPoolExecutor(1)
        trace_done = tracer.submit(take_trace)
    records, window_start, window_end = closed_loop(
        node, config["index_id"], mix, stream, args.seconds,
        bool(args.trace), None)
    if tracer:
        trace_done.result()
        tracer.shutdown()
    after = node.metrics()
    memory = stop(node)
    say(f"[node] stopped; device memory: {json.dumps(memory)}")
    parse(records)

    split_files = [data.SplitFile(s["path"]) for s in splits]
    run = Run(cell, records, (window_start, window_end), device, split_files)
    run.metrics_before, run.metrics_after = before, after
    for shape in mix["shapes"]:
        rows = [r["latency_ms"] for r in records
                if r["shape"] == shape and r["ok"]]
        say(f"[window] {shape}: {len(rows)} answered, "
            f"{100.0 * len(rows) / max(1, len(records)):.1f} % of the "
            f"requests sent, median "
            f"{statistics.median(rows) if rows else float('nan'):.1f} ms")
    done = sorted(r["t_done"] for r in records)
    gaps = [(b - a, a - window_start) for a, b in zip(done, done[1:])]
    slowest = max(records, key=lambda r: r["latency_ms"])
    say(f"[window] {len(records)} sent; slowest {slowest['shape']} "
        f"{slowest['latency_ms']:.0f} ms, sent at "
        f"{slowest['t_send'] - window_start:.1f}s; longest stretch with no "
        f"reply {max(gaps)[0] * 1000:.0f} ms from {max(gaps)[1]:.1f}s")
    if slowest.get("profile"):
        phases = sorted(((p.get("duration_ms", 0.0), p.get("name"))
                         for node_ in profile_nodes(slowest["profile"])
                         for p in node_.get("phases") or []), reverse=True)
        say(f"[window] the slowest request's longest phases: {phases[:5]}")
    device_line = dict(device, memory_peak_bytes=max(
        (entry.get("peak_bytes_in_use") or 0) for entry in memory))
    result = {"attempted": len(records),
              "failed": sum(not r["ok"] for r in records)}
    if args.trace:
        reduced = device_trace.reduce(device_trace.load(traced["file"]),
                                      traced["window_s"])
        if reduced is None:
            raise BenchFailure("the trace holds no device operation")
        run.trace, run.trace_span = reduced, (traced["lo"], traced["hi"])
        device_line["busy_s"] = reduced["busy_s"]
        device_line["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
        metrics = per_layer(run)
    else:
        metrics = end_to_end(run, args.seconds, setup_s)
    units = {m["name"]: m["unit"]
             for m in cell["end_to_end"] + cell["per_layer"]}
    # an end-to-end metric that lists its cells is left out of the others
    result["metrics"] = {name: {"value": value, "unit": units[name]}
                         for name, value in metrics.items() if name in units}
    result["device"] = device_line
    # the reference runs last: the node is gone and its peak has been read
    checks = check(cell, records, splits, args.seed)
    result["correct"] = all(c["value"] <= c["limit"] for c in checks.values())
    result["checks"] = checks
    own = data.rss_peak_bytes()
    together.append(own)
    say("[host] rss peak: generator "
        + (f"{generator / 1e6:.0f} MB (1 at once)" if generator
           else "none (every split found)")
        + f", node {max(node_peaks) / 1e6:.0f} MB, run {own / 1e6:.0f} MB; "
        f"most at one time {max(together) / 1e6:.0f} MB")
    say(f"[data] the run's own directory under .bench_cache (node logs, "
        f"metastore, trace) holds {data.tree_bytes(RUN_DIR)} bytes")
    device_line["host_rss_peak_bytes"] = max(together)
    result["_window"] = (records, splits)   # for the control; not printed
    return result


def main(argv=None, node_entry: str = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    # the node gets the environment as it was found, so JAX there picks the
    # chip; this process and its generator workers stay on the CPU
    node_env = dict(os.environ)
    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, ROOT)
    workers: list = []
    result = None
    signal.signal(signal.SIGTERM, lambda signum, frame: sys.exit(143))
    try:
        if not os.path.isdir(os.path.join(ROOT, "quickwit_tpu")):
            raise BenchFailure(f"no quickwit_tpu package beside {HERE}: "
                               "nothing to measure")
        cell = load_cell(args.workload)
        say(f"[run] {args.workload} seed {args.seed} seconds {args.seconds} "
            f"trace {args.trace}: {cell['mix']['clients']} closed-loop "
            f"clients, shapes {cell['mix']['shapes']}")
        result = measure(args, cell, node_env, workers, node_entry)
    except (BenchFailure, NodeFailure) as exc:
        say(f"FAILED: {exc}")
    except Exception as exc:  # the boundary: report, reap, exit non-zero
        traceback.print_exc()
        say(f"FAILED: {type(exc).__name__}: {exc}")
    finally:
        leftover = reap(workers)
    if leftover:
        say(f"FAILED: children were still running at the end: {leftover}")
        result = None
    say(f"[run] {time.monotonic() - START_WALL:.0f}s in all")
    if result is None:
        return 1
    ordered = {key: result[key] for key in
               ("correct", "attempted", "failed", "metrics", "device",
                "breakdown", "checks") if key in result}
    for name, entry in ordered["checks"].items():
        print(f"[check] {name} {entry['value']!r} limit {entry['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(ordered), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
