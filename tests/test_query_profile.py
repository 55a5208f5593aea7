"""Per-query execution profiles: waterfall correctness, zero-overhead-off,
slow-query capture, and truthful partial profiles under chaos.

The profile acceptance bar (ISSUE 4): `"profile": true` returns a phase
waterfall whose phases are timeline-consistent and roughly account for the
query's wall time; profiling off allocates nothing on the hot path; shed /
timed-out queries report partial phases with real durations instead of
lying with zeros.
"""

import threading

import pytest

from quickwit_tpu.common.faults import FaultInjector, FaultRule, InjectedFault
from quickwit_tpu.ingest.ingester import Ingester
from quickwit_tpu.indexing import IndexingPipeline, PipelineParams, VecSource
from quickwit_tpu.metastore import FileBackedMetastore
from quickwit_tpu.models import DocMapper, FieldMapping, FieldType
from quickwit_tpu.models.index_metadata import (IndexConfig, IndexMetadata,
                                                SourceConfig)
from quickwit_tpu.observability.metrics import FAULTS_INJECTED_TOTAL
from quickwit_tpu.observability.profile import (QueryProfile, _NULL_PHASE,
                                                current_profile, profile_scope,
                                                profiled_phase)
from quickwit_tpu.observability.slowlog import SLOW_QUERY_LOG, SlowQueryLog
from quickwit_tpu.query import parse_query_string
from quickwit_tpu.query.ast import Bool, Range, RangeBound, Term
from quickwit_tpu.search.models import SearchRequest, SortField
from quickwit_tpu.search.root import RootSearcher
from quickwit_tpu.search.service import (LocalSearchClient, SearcherContext,
                                         SearchService)
from quickwit_tpu.storage import StorageResolver

MAPPER = DocMapper(
    field_mappings=[
        FieldMapping("ts", FieldType.DATETIME, fast=True,
                     input_formats=("unix_timestamp",)),
        FieldMapping("body", FieldType.TEXT),
        FieldMapping("tenant", FieldType.U64, fast=True),
    ],
    timestamp_field="ts",
    default_search_fields=("body",),
)

NUM_DOCS = 300


@pytest.fixture(scope="module")
def cluster():
    resolver = StorageResolver.for_test()
    meta_storage = resolver.resolve("ram:///profile/metastore")
    split_uri = "ram:///profile/splits"
    metastore = FileBackedMetastore(meta_storage)
    config = IndexConfig(index_id="plogs", index_uri=split_uri,
                         doc_mapper=MAPPER, split_num_docs_target=100)
    metastore.create_index(IndexMetadata(
        index_uid="plogs:01", index_config=config,
        sources={"src": SourceConfig("src", "vec")}))
    docs = [{"ts": 1_600_000_000 + i, "body": f"event word{i % 5}",
             "tenant": i % 3} for i in range(NUM_DOCS)]
    pipeline = IndexingPipeline(
        PipelineParams(index_uid="plogs:01", source_id="src",
                       split_num_docs_target=100, batch_num_docs=50),
        MAPPER, VecSource(docs), metastore, resolver.resolve(split_uri))
    pipeline.run_to_completion()
    service = SearchService(SearcherContext(storage_resolver=resolver),
                            node_id="node-0")
    root = RootSearcher(metastore, {"node-0": LocalSearchClient(service)})
    return metastore, resolver, root


def _search(root, **kwargs):
    defaults = dict(index_ids=["plogs"],
                    query_ast=parse_query_string("word1", ["body"]),
                    max_hits=5, sort_fields=(SortField("ts", "desc"),))
    defaults.update(kwargs)
    return root.search(SearchRequest(**defaults))


# --- waterfall correctness -------------------------------------------------

def test_profile_waterfall_phases_and_wall(cluster):
    _, _, root = cluster
    # "word0" is used by THIS test only: a leaf-cache hit from a sibling
    # test would short-circuit the very phases being asserted
    response = _search(root, profile=True,
                       query_ast=parse_query_string("word0", ["body"]))
    assert response.num_hits > 0
    profile = response.profile
    assert profile is not None
    phases = profile["phases"]
    assert phases, "profiled query returned an empty waterfall"
    names = {p["name"] for p in phases}
    # the leaf hot path and the root merge must both be attributed
    assert "plan_build" in names
    assert "root_merge" in names
    assert names & {"compile", "execute"}, \
        "neither compile nor execute time was attributed"
    wall_ms = profile["wall_ms"]
    assert wall_ms > 0
    starts = [p["start_ms"] for p in phases]
    assert starts == sorted(starts), "phases not sorted by start time"
    for p in phases:
        assert p["start_ms"] >= 0
        assert p["duration_ms"] >= 0
        # timeline consistency: no phase extends past the query wall by
        # more than scheduling slack
        assert p["start_ms"] + p["duration_ms"] <= wall_ms * 1.2 + 20.0
    # the waterfall accounts for the query without double-counting: the
    # summed phase time cannot exceed wall by more than overlap slack
    # (admission/staging/batcher waits overlap across pool threads, and
    # the splits of a wave run side by side: their phases overlap as many
    # times over as the wave is wide)
    width = profile["counters"]["split_wave_width"]
    assert width == 3.0
    total = sum(p["duration_ms"] for p in phases)
    assert 0 < total <= wall_ms * (width + 1.0) + 20.0
    # device counters rolled up from the leaf's resource stats
    assert "num_splits_pruned_by_threshold" in profile["counters"]


def test_profile_counts_compile_cache(cluster):
    _, _, root = cluster
    # word2/word3 appear in the same number of docs → identical padded
    # posting shapes → the SAME jit signature, but distinct leaf-cache
    # keys: the second query must dispatch and hit the compile cache
    first = _search(root, profile=True,
                    query_ast=parse_query_string("word2", ["body"]))
    second = _search(root, profile=True,
                     query_ast=parse_query_string("word3", ["body"]))
    c1, c2 = first.profile["counters"], second.profile["counters"]
    # every dispatch is attributed to exactly one of hit/miss
    assert c1.get("compile_cache_hits", 0) + c1.get("compile_cache_misses", 0) \
        >= 1
    assert c2.get("compile_cache_misses", 0) == 0
    assert c2.get("compile_cache_hits", 0) >= 1


def test_zonemap_pruned_splits_in_profile(cluster):
    _, _, root = cluster
    # tenant is always in [0, 2]: a required tenant >= 100 constraint
    # zonemap-prunes every split before any byte is fetched
    ast = Bool(must=(parse_query_string("word1", ["body"]),),
               filter=(Range(field="tenant",
                             lower=RangeBound(100, inclusive=True)),))
    response = _search(root, profile=True, query_ast=ast)
    assert response.num_hits == 0
    counters = response.profile["counters"]
    assert counters.get("splits_pruned_zonemap", 0) >= 1


# --- zero-overhead-off -----------------------------------------------------

def test_profile_off_allocates_nothing(cluster):
    _, _, root = cluster
    response = _search(root)
    assert response.profile is None
    assert "profile" not in response.to_dict()
    # with no ambient profile the phase hook returns the SHARED null
    # context manager: no per-call allocation on the hot path
    assert current_profile() is None
    assert profiled_phase("staging") is _NULL_PHASE
    assert profiled_phase("execute") is _NULL_PHASE


def test_profile_scope_rebinding():
    profile = QueryProfile(query_id="q1")
    with profile_scope(profile):
        assert current_profile() is profile
        assert profiled_phase("execute") is not _NULL_PHASE
        seen = []
        t = threading.Thread(target=lambda: seen.append(current_profile()))
        t.start()
        t.join()
        # fresh threads do NOT inherit the binding — fan-out paths must
        # rebind explicitly (root._fan_out, service prefetch pool do)
        assert seen == [None]
    assert current_profile() is None


# --- slow-query log --------------------------------------------------------

def test_slowlog_fifo_eviction():
    log = SlowQueryLog(capacity=3, threshold_ms=1.0)
    for i in range(5):
        log.record({"query_id": f"q{i}", "elapsed_ms": 10.0 + i})
    entries = log.entries()
    assert len(entries) == 3
    assert [e["query_id"] for e in entries] == ["q2", "q3", "q4"]
    assert all("recorded_at" in e for e in entries)


def test_slowlog_captures_armed_queries(cluster):
    _, _, root = cluster
    SLOW_QUERY_LOG.clear()
    SLOW_QUERY_LOG.configure(0.0)  # every query is "slow"
    try:
        response = _search(root)  # NOT profile-flagged
        assert response.profile is None  # response shape unchanged
        entries = SLOW_QUERY_LOG.entries()
        assert entries, "armed slowlog captured nothing"
        entry = entries[-1]
        assert entry["indexes"] == ["plogs"]
        assert entry["elapsed_ms"] > 0
        assert entry["profile"]["phases"], \
            "slowlog entry is missing the waterfall"
    finally:
        SLOW_QUERY_LOG.configure(None)
        SLOW_QUERY_LOG.clear()
    assert not SLOW_QUERY_LOG.should_capture(10_000.0, timed_out=True)


# --- trace stitching: root → leaf → kernel ---------------------------------

def test_profiled_query_stitches_one_trace(cluster):
    """A profiled query emits one trace from the root span through the
    leaf fan-out down to the device phases, and the whole path survives
    the OTLP rendering used by the exporter."""
    from quickwit_tpu.observability.tracing import TRACER, spans_to_otlp

    _, _, root = cluster
    finished = []
    TRACER.add_processor(finished.append)
    try:
        # "word4" is this test's own term: a leaf-cache hit would skip the
        # kernel phases and with them the deepest spans of the trace
        response = _search(root, profile=True,
                           query_ast=parse_query_string("word4", ["body"]))
    finally:
        TRACER.remove_processor(finished.append)
    assert response.num_hits > 0
    roots = [s for s in finished if s.name == "root_search"]
    assert roots, "no root_search span recorded"
    trace_id = roots[-1].trace_id
    stitched = [s for s in finished if s.trace_id == trace_id]
    names = {s.name for s in stitched}
    # the acceptance bar: >= 5 spans of ONE trace covering the hop from
    # root admission to the device kernel dispatch
    assert len(stitched) >= 5, sorted(names)
    assert "leaf_dispatch" in names
    assert "leaf_search" in names
    assert names & {"phase.compile", "phase.execute"}, sorted(names)
    # every non-root span is parented inside the same trace
    span_ids = {s.span_id for s in stitched}
    orphans = [s.name for s in stitched
               if s is not roots[-1] and s.parent_span_id not in span_ids]
    assert not orphans, f"spans joined the trace without a parent: {orphans}"
    otlp = spans_to_otlp(stitched, "quickwit-tpu", node_id="node-0")
    exported = otlp["resourceSpans"][0]["scopeSpans"][0]["spans"]
    assert len(exported) == len(stitched)
    assert {s["traceId"] for s in exported} == {trace_id}


# --- chaos: partial profiles must be truthful ------------------------------

def test_expired_query_profile_reports_partial(cluster):
    """A query whose budget expires mid-flight keeps the phases it actually
    ran, with real durations, and is marked partial — not all-zeros."""
    _, _, root = cluster
    SLOW_QUERY_LOG.clear()
    SLOW_QUERY_LOG.configure(1e9)  # armed: timed-out queries always capture
    try:
        response = _search(root, profile=True, timeout_millis=1)
        assert response.timed_out
        profile = response.profile
        assert profile is not None
        assert profile.get("partial"), \
            "timed-out query profile not marked partial"
        for p in profile["phases"]:
            assert "duration_ms" in p and p["duration_ms"] >= 0
        # shed/timed-out queries are always slowlog-worthy when armed
        entries = SLOW_QUERY_LOG.entries()
        assert entries and entries[-1]["timed_out"]
    finally:
        SLOW_QUERY_LOG.configure(None)
        SLOW_QUERY_LOG.clear()


def test_storage_fault_query_profile_reports_partial(cluster):
    """When every split fails on injected storage faults the root raises —
    but the armed slowlog still captured the profile, marked partial, with
    the phases that actually ran (plus the injected-fault audit counter)."""
    from quickwit_tpu.common.faults import FaultyStorageResolver

    metastore, resolver, _ = cluster
    injector = FaultInjector(seed=7, rules=[
        FaultRule(operation="storage.get_slice", kind="error")])
    faulty = FaultyStorageResolver(resolver, injector)
    service = SearchService(SearcherContext(storage_resolver=faulty),
                            node_id="node-f")
    root = RootSearcher(metastore, {"node-f": LocalSearchClient(service)})
    before = FAULTS_INJECTED_TOTAL.get(op="storage.get_slice", kind="error")
    SLOW_QUERY_LOG.clear()
    SLOW_QUERY_LOG.configure(0.0)  # capture everything
    try:
        with pytest.raises(ValueError):
            _search(root, profile=True)
        assert FAULTS_INJECTED_TOTAL.get(op="storage.get_slice",
                                         kind="error") > before
        entries = SLOW_QUERY_LOG.entries()
        assert entries, "failed query was not captured by the armed slowlog"
        profile = entries[-1]["profile"]
        assert profile.get("partial"), "failed query profile not partial"
        # the phases that ran are retained with real timings — never
        # fabricated zeros (root_merge ran; fetch_docs never did)
        names = {p["name"] for p in profile["phases"]}
        assert "root_merge" in names
        assert "fetch_docs" not in names
        assert all("duration_ms" in p for p in profile["phases"])
    finally:
        SLOW_QUERY_LOG.configure(None)
        SLOW_QUERY_LOG.clear()


# --- chaos: ingest write path ----------------------------------------------

def test_wal_fsync_fault_rejects_batch_cleanly(tmp_path):
    injector = FaultInjector(seed=11, rules=[
        FaultRule(operation="wal.fsync", kind="error", max_fires=1)])
    ingester = Ingester(str(tmp_path / "wal"), fault_injector=injector)
    before = FAULTS_INJECTED_TOTAL.get(op="wal.fsync", kind="error")
    with pytest.raises(InjectedFault):
        ingester.persist("idx:01", "src", "s0", [{"n": 1}])
    assert FAULTS_INJECTED_TOTAL.get(op="wal.fsync", kind="error") \
        == before + 1
    # the failed fsync rejected the batch without corrupting the log:
    # the next persist lands at position 0 and is readable
    first, last = ingester.persist("idx:01", "src", "s0", [{"n": 2}])
    assert (first, last) == (0, 0)
    assert ingester.fetch("idx:01", "src", "s0", 0) == [(0, {"n": 2})]


def test_replication_drop_rolls_back_leader_tail(tmp_path):
    calls = []

    def replicate(index_uid, source_id, shard_id, first, payloads):
        calls.append(first)

    injector = FaultInjector(seed=13, rules=[
        FaultRule(operation="ingest.replicate", kind="error", max_fires=1)])
    ingester = Ingester(str(tmp_path / "wal2"), replicate_to=replicate,
                        fault_injector=injector)
    with pytest.raises(InjectedFault):
        ingester.persist("idx:01", "src", "s0", [{"n": 1}, {"n": 2}])
    shard = ingester.shard("idx:01", "src", "s0")
    # durable on both or neither: the dropped replication rolled the
    # leader's tail back and the follower never saw the batch
    assert shard.log.next_position == 0
    assert calls == []
    first, last = ingester.persist("idx:01", "src", "s0", [{"n": 3}])
    assert (first, last) == (0, 0)
    assert calls == [0]
