"""Concurrent mixed-workload soak: searches, SQL, aggregations, and
ingest hammering one node from many threads at once.

Role of the reference's integration stress coverage: the serving path
(convoy batcher, executor compile cache, WAL, metastore cache) must
stay correct and error-free under REAL concurrency — every response a
200, every search's num_hits monotone in the (growing) corpus, no
deadlocks (bounded wall-clock), no dropped ingest."""

import http.client
import json
import threading
import time

import pytest

from quickwit_tpu.observability.metrics import (
    SEARCH_BATCHER_DISPATCHES_TOTAL, SEARCH_BATCHER_QUERIES_TOTAL,
    SEARCH_BATCHER_QUEUE_WAIT,
)
from quickwit_tpu.serve import Node, NodeConfig, RestServer
from quickwit_tpu.storage import StorageResolver

THREADS = 8
ROUNDS = 12


def _percentile(sorted_values, q):
    assert sorted_values
    idx = min(len(sorted_values) - 1, int(q * (len(sorted_values) - 1)))
    return sorted_values[idx]


@pytest.fixture()
def api():
    node = Node(NodeConfig(node_id="soak", rest_port=0,
                           metastore_uri="ram:///soak/ms",
                           default_index_root_uri="ram:///soak/idx"),
                storage_resolver=StorageResolver.for_test())
    server = RestServer(node, host="127.0.0.1", port=0)
    server.start()
    conn = http.client.HTTPConnection("127.0.0.1", server.port,
                                      timeout=30)
    conn.request("POST", "/api/v1/indexes", json.dumps({
        "index_id": "soak",
        "doc_mapping": {"field_mappings": [
            {"name": "ts", "type": "datetime", "fast": True,
             "input_formats": ["unix_timestamp"]},
            {"name": "sev", "type": "text", "tokenizer": "raw",
             "fast": True},
            {"name": "num", "type": "f64", "fast": True},
            {"name": "body", "type": "text"}],
            "timestamp_field": "ts",
            "default_search_fields": ["body"]}}).encode())
    assert conn.getresponse().status == 200
    conn.close()
    # seed corpus so every query shape compiles BEFORE the storm
    node.ingest("soak", [
        {"ts": 1000 + i, "sev": ["a", "b"][i % 2], "num": float(i),
         "body": f"seed{i} common"} for i in range(50)], commit="force")
    yield server.port, node
    server.stop()


def _call(port, method, path, body=None):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
    conn.request(method, path, body)
    response = conn.getresponse()
    data = response.read()
    conn.close()
    return response.status, data


def test_concurrent_mixed_workload(api):
    port, node = api
    batcher = node.searcher_context.query_batcher
    queries_before = batcher.num_queries
    dispatches_before = batcher.num_dispatches
    errors: list[str] = []
    ingested = [0] * THREADS
    latencies: list[float] = []  # list.append is GIL-atomic
    barrier = threading.Barrier(THREADS)

    # arm the slow-query log over REST for the whole storm: threshold 0
    # captures every search, so the dump below is a per-query waterfall
    # census of the soak — exactly what the endpoint is for in production
    status, data = _call(port, "POST", "/api/v1/developer/slowlog",
                         json.dumps({"threshold_ms": 0.0}).encode())
    assert status == 200, data[:200]
    assert json.loads(data)["armed"]

    def timed_call(method, path, body=None):
        t0 = time.monotonic()
        result = _call(port, method, path, body)
        latencies.append(time.monotonic() - t0)
        return result

    def worker(worker_id: int) -> None:
        try:
            barrier.wait(timeout=30)
            for round_no in range(ROUNDS):
                kind = (worker_id + round_no) % 4
                if kind == 0:      # plain search
                    status, data = timed_call(
                        "GET",
                        "/api/v1/soak/search?query=common&max_hits=5")
                    assert status == 200, data[:200]
                    assert json.loads(data)["num_hits"] >= 50
                elif kind == 1:    # aggregation (same-shape: convoy)
                    status, data = timed_call(
                        "POST", "/api/v1/_elastic/soak/_search",
                        json.dumps({
                            "query": {"match": {"body": "common"}},
                            "size": 0,
                            "aggs": {"per_sev": {"terms":
                                                 {"field": "sev"}}},
                        }).encode())
                    assert status == 200, data[:200]
                    buckets = json.loads(data)["aggregations"][
                        "per_sev"]["buckets"]
                    assert sum(b["doc_count"] for b in buckets) >= 50
                elif kind == 2:    # SQL
                    status, data = timed_call(
                        "POST", "/api/v1/_sql", json.dumps({
                            "query": "SELECT sev, COUNT(*) AS n "
                                     "FROM soak GROUP BY sev"}).encode())
                    assert status == 200, data[:200]
                else:              # ingest more docs
                    docs = "\n".join(json.dumps(
                        {"ts": 2000 + worker_id * 1000 + round_no,
                         "sev": "c", "num": 1.0,
                         "body": f"w{worker_id}r{round_no} common"})
                        for _ in range(2))
                    status, data = timed_call(
                        "POST",
                        "/api/v1/soak/ingest?commit=force",
                        docs.encode())
                    assert status == 200, data[:200]
                    ingested[worker_id] += 2
        except Exception as exc:  # noqa: BLE001 - collected for report
            errors.append(f"worker {worker_id}: {exc!r}")

    workers = [threading.Thread(target=worker, args=(i,))
               for i in range(THREADS)]
    for w in workers:
        w.start()
    for w in workers:
        w.join(timeout=120)
    assert not any(w.is_alive() for w in workers), "soak deadlocked"
    assert not errors, errors

    # latency tail: every request bounded, no hidden per-request hang
    ordered = sorted(latencies)
    p50, p99 = _percentile(ordered, 0.50), _percentile(ordered, 0.99)
    print(f"\nsoak latency over {len(ordered)} requests: "
          f"p50={p50 * 1000:.1f}ms p99={p99 * 1000:.1f}ms")
    assert p99 < 30.0, f"p99 latency {p99:.1f}s — a request nearly hung"

    # convoy accounting stays sane under the storm (strict coalescing is
    # asserted by the dedicated burst test below)
    query_delta = batcher.num_queries - queries_before
    dispatch_delta = batcher.num_dispatches - dispatches_before
    print(f"convoy batcher: {query_delta} queries -> "
          f"{dispatch_delta} dispatches")
    assert dispatch_delta <= query_delta

    # every ingested doc is searchable afterwards (nothing dropped)
    status, data = _call(
        port, "GET", "/api/v1/soak/search?query=common&max_hits=0")
    assert status == 200
    assert json.loads(data)["num_hits"] == 50 + sum(ingested)

    # slow-query dump: the armed ring buffer captured real waterfalls for
    # the storm's searches — phase names, not zeros — and disarming stops
    # further capture
    try:
        status, data = _call(port, "GET", "/api/v1/developer/slowlog")
        assert status == 200, data[:200]
        dump = json.loads(data)
        assert dump["armed"]
        entries = dump["entries"]
        assert entries, "armed slowlog captured nothing during the soak"
        for entry in entries:
            assert entry["elapsed_ms"] >= 0
            assert entry["profile"]["phases"], \
                f"slowlog entry {entry['query_id']} has an empty waterfall"
        slowest = sorted(entries, key=lambda e: e["elapsed_ms"])[-3:]
        print("slowlog dump (slowest of "
              f"{len(entries)} captured):")
        for entry in reversed(slowest):
            phases = {p["name"]: round(p["duration_ms"], 2)
                      for p in entry["profile"]["phases"]}
            print(f"  {entry['query_id']} {entry['elapsed_ms']:.1f}ms "
                  f"{phases}")
    finally:
        status, data = _call(port, "POST", "/api/v1/developer/slowlog",
                             json.dumps({"threshold_ms": None}).encode())
        assert status == 200
        assert not json.loads(data)["armed"]


def test_convoy_batcher_coalesces_concurrent_burst(api):
    """Same-shape queries arriving together must share device dispatches.

    32 range queries differ ONLY in their (traced-scalar) lower bound, so
    they share one compiled plan but miss the leaf cache individually; with
    the corpus still a single split, each rides the convoy batcher — the
    burst must finish in strictly fewer dispatches than queries."""
    port, node = api
    batcher = node.searcher_context.query_batcher
    queries_before = batcher.num_queries
    dispatches_before = batcher.num_dispatches
    errors: list[str] = []
    barrier = threading.Barrier(THREADS)
    per_thread = 4

    def worker(worker_id: int) -> None:
        try:
            barrier.wait(timeout=30)
            for i in range(per_thread):
                lo = worker_id * per_thread + i  # 0..31, all distinct
                status, data = _call(
                    port, "POST", "/api/v1/_elastic/soak/_search",
                    json.dumps({
                        "query": {"range": {"num": {"gte": lo,
                                                    "lte": 49.0}}},
                        "size": 1}).encode())
                assert status == 200, data[:200]
                assert json.loads(data)["hits"]["total"]["value"] == 50 - lo
        except Exception as exc:  # noqa: BLE001 - collected for report
            errors.append(f"worker {worker_id}: {exc!r}")

    workers = [threading.Thread(target=worker, args=(i,))
               for i in range(THREADS)]
    for w in workers:
        w.start()
    for w in workers:
        w.join(timeout=120)
    assert not any(w.is_alive() for w in workers), "burst deadlocked"
    assert not errors, errors

    query_delta = batcher.num_queries - queries_before
    dispatch_delta = batcher.num_dispatches - dispatches_before
    print(f"\nburst: {query_delta} batcher queries -> "
          f"{dispatch_delta} dispatches")
    assert query_delta == THREADS * per_thread, \
        "burst queries bypassed the batcher (cache hit or fast path?)"
    assert dispatch_delta < query_delta, \
        "concurrent same-shape queries never coalesced into a batch"

    # the exported metrics must tell the same story as the instance
    # counters: operators read qw_search_batcher_* — not internals
    assert SEARCH_BATCHER_QUERIES_TOTAL.get() >= batcher.num_queries
    assert SEARCH_BATCHER_DISPATCHES_TOTAL.get() >= batcher.num_dispatches
    ratio = (SEARCH_BATCHER_QUERIES_TOTAL.get()
             / SEARCH_BATCHER_DISPATCHES_TOTAL.get())
    assert ratio > 1.0, "the batcher never coalesced a dispatch"

    # queue-wait histogram: one observation per dispatched rider, finite
    # tail (the convoy window is bounded by real dispatch latency)
    wait_p50 = SEARCH_BATCHER_QUEUE_WAIT.percentile(0.50)
    wait_p99 = SEARCH_BATCHER_QUEUE_WAIT.percentile(0.99)
    assert wait_p50 is not None and wait_p99 is not None, \
        "no queue-wait observations recorded by the batcher"
    print(f"batcher queue wait: p50<={wait_p50 * 1000:.1f}ms "
          f"p99<={wait_p99 * 1000:.1f}ms "
          f"ratio={ratio:.2f}")
    assert wait_p99 <= 10.0, \
        f"queue-wait p99 bucket {wait_p99}s — riders starved in the convoy"
