"""One device, several splits: a leaf group's per-split programs launch
together (search/service.py::_execute_per_split).

The suite runs on 8 virtual CPU devices (tests/conftest.py), where a group
of several splits finds a mesh and takes the fused collective program. A
node with ONE device — what `len(jax.devices()) == 1` is on a chip — is
made here by a `device_mesh` that returns None, which is all the service
asks of the device count. On such a node a group never reaches
`fanout.build_batch`: its splits run as a wave, one worker each, and the
calling thread merges the answers in split-id order. The claim under test
is that the wave's answers EQUAL the mesh program's (`build_batch` +
`execute_batch` called directly, one split a device) — hits, sort values,
counts, aggregation states, the kept subset of an all-ties sort — and that
failure, shed, cancel and deadline semantics hold split by split.

Fixture latencies are integral so stats sums are exact under any
reassociation — agg equality here is ==, not approx.
"""

import threading

import pytest

from quickwit_tpu.common.deadline import (
    CancellationToken, Deadline, cancel_scope, deadline_scope,
)
from quickwit_tpu.index import SplitReader, SplitWriter
from quickwit_tpu.models import DocMapper, FieldMapping, FieldType
from quickwit_tpu.observability.metrics import (
    MESH_DISPATCHES_TOTAL, SPLIT_WAVE_WIDTH,
)
from quickwit_tpu.observability.profile import (
    QueryProfile, current_profile, profile_scope,
)
from quickwit_tpu.parallel import fanout
from quickwit_tpu.query.ast import Bool, FullText, Range, RangeBound, Term
from quickwit_tpu.search import (
    IncrementalCollector, SearchRequest, SortField, finalize_aggregations,
)
from quickwit_tpu.search import service as service_module
from quickwit_tpu.search.models import LeafSearchRequest, SplitIdAndFooter
from quickwit_tpu.search.service import SearcherContext, SearchService
from quickwit_tpu.storage import StorageResolver
from quickwit_tpu.tenancy.context import (
    TenantContext, current_tenant, tenant_scope,
)

N_SPLITS = 4
DOCS_PER_SPLIT = 150
SEVERITIES = ["DEBUG", "INFO", "WARN", "ERROR"]
SPLIT_URI = "ram:///splitwaves/splits"
SPLIT_IDS = [f"split-{s}" for s in range(N_SPLITS)]

MAPPER = DocMapper(
    field_mappings=[
        FieldMapping("timestamp", FieldType.DATETIME, fast=True,
                     input_formats=("unix_timestamp",)),
        FieldMapping("severity_text", FieldType.TEXT, tokenizer="raw",
                     fast=True),
        FieldMapping("tenant_id", FieldType.U64, fast=True),
        FieldMapping("body", FieldType.TEXT),
        FieldMapping("latency", FieldType.F64, fast=True),
    ],
    timestamp_field="timestamp",
    default_search_fields=("body",),
)

# the fused program wants uniform column packings: every split spans the
# same value ranges, and only the timestamps move on from split to split
def _doc(n: int) -> dict:
    return {"timestamp": 1_600_000_000 + n * 60,
            "severity_text": SEVERITIES[n % 4],
            "tenant_id": n % 4,
            "body": ["alpha beta", "alpha", "beta beta", "alpha alpha"][n % 4],
            "latency": float((n * 37) % 5_000)}


@pytest.fixture(scope="module")
def corpus():
    """(resolver, readers by split id, the leaf request's split list)."""
    resolver = StorageResolver.for_test()
    storage = resolver.resolve(SPLIT_URI)
    readers, splits = {}, []
    for number, split_id in enumerate(SPLIT_IDS):
        writer = SplitWriter(MAPPER)
        first = number * DOCS_PER_SPLIT
        for n in range(first, first + DOCS_PER_SPLIT):
            writer.add_json_doc(_doc(n))
        storage.put(f"{split_id}.split", writer.finish())
        readers[split_id] = SplitReader(storage, f"{split_id}.split")
        lo = (1_600_000_000 + first * 60) * 1_000_000
        hi = lo + (DOCS_PER_SPLIT - 1) * 60 * 1_000_000
        splits.append(SplitIdAndFooter(
            split_id=split_id, storage_uri=SPLIT_URI,
            num_docs=DOCS_PER_SPLIT, time_range=(lo, hi)))
    return resolver, readers, splits


def _service(corpus, one_device: bool = True) -> SearchService:
    """A node with caches of its own; with `one_device`, one whose devices
    form no mesh, as a single chip's do."""
    context = SearcherContext(storage_resolver=corpus[0])
    if one_device:
        context.device_mesh = lambda n_splits: None
    return SearchService(context, node_id="node-waves")


def _leaf(service, corpus, request, splits=None, **kwargs):
    return service.leaf_search(LeafSearchRequest(
        search_request=request, index_uid="waves:01",
        doc_mapping=MAPPER.to_dict(),
        splits=list(corpus[2] if splits is None else splits), **kwargs))


def _fused(corpus, request, ids=None):
    """The mesh program over the splits, one a device, called directly."""
    readers = corpus[1]
    ids = SPLIT_IDS if ids is None else ids
    batch = fanout.build_batch(request, MAPPER,
                               [readers[i] for i in ids], ids)
    return fanout.execute_batch(batch, request,
                                fanout.make_mesh(len(ids), 1))


def _hit_rows(response):
    return [(h.split_id, h.doc_id, h.sort_value, h.sort_value2,
             h.raw_sort_value, h.raw_sort_value2)
            for h in response.partial_hits]


def _aggs(response):
    collector = IncrementalCollector(max_hits=0)
    collector.add_leaf_response(response)
    return finalize_aggregations(collector.aggregation_states())


def _wave_widths() -> tuple:
    """(count, sum) of `qw_leaf_split_wave_width`, as /metrics shows them."""
    lines = dict(line.rsplit(" ", 1) for line in SPLIT_WAVE_WIDTH.expose()
                 if not line.startswith("#"))
    return (int(lines.get("qw_leaf_split_wave_width_count", 0)),
            float(lines.get("qw_leaf_split_wave_width_sum", 0.0)))


@pytest.fixture
def build_batch_calls(monkeypatch):
    """Every call the service makes to `fanout.build_batch`."""
    calls = []
    real = service_module.build_batch

    def spy(*args, **kwargs):
        calls.append(args)
        return real(*args, **kwargs)

    monkeypatch.setattr(service_module, "build_batch", spy)
    return calls


@pytest.fixture
def executions(monkeypatch):
    """Patches `execute_prepared_split` as the service calls it. Yields a
    dict: `before` is called with the split id on the executing thread
    before the real function runs; `threads` collects those threads."""
    hook = {"before": lambda split_id: None, "threads": []}
    real = service_module.execute_prepared_split

    def patched(request, doc_mapper, reader, split_id, *args, **kwargs):
        hook["threads"].append(threading.current_thread())
        hook["before"](split_id)
        return real(request, doc_mapper, reader, split_id, *args, **kwargs)

    monkeypatch.setattr(service_module, "execute_prepared_split", patched)
    return hook


SCORE_SORTED = SearchRequest(
    index_ids=["waves"], query_ast=FullText("body", "beta", "or"),
    max_hits=13)
FILTERED_WITH_AGGS = SearchRequest(
    index_ids=["waves"],
    query_ast=Bool(must=(FullText("body", "alpha", "or"),),
                   filter=(Range("tenant_id", RangeBound(1, True),
                                 RangeBound(3, True)),)),
    max_hits=10,
    aggs={"sev": {"terms": {"field": "severity_text", "size": 10}},
          "lat": {"stats": {"field": "latency"}},
          "ot": {"date_histogram": {"field": "timestamp",
                                    "fixed_interval": "1h"}}})
TIMESTAMP_SORTED = SearchRequest(
    index_ids=["waves"], query_ast=Term("severity_text", "ERROR"),
    max_hits=9, sort_fields=(SortField("timestamp", "desc"),))
AGG_ONLY = SearchRequest(
    index_ids=["waves"], query_ast=FullText("body", "beta", "or"),
    max_hits=0,
    aggs={"sev": {"terms": {"field": "severity_text"}},
          "avg": {"avg": {"field": "latency"}}})
# every candidate shares one sort value and k < matches: the kept subset is
# decided by the collector's total order alone (split_id asc, doc asc)
ALL_TIES = SearchRequest(
    index_ids=["waves"], query_ast=Term("severity_text", "WARN"),
    max_hits=7, sort_fields=(SortField("tenant_id", "asc"),))
REQUESTS = {"score_sorted": SCORE_SORTED,
            "filtered_with_aggs": FILTERED_WITH_AGGS,
            "timestamp_sorted": TIMESTAMP_SORTED,
            "agg_only": AGG_ONLY,
            "all_ties": ALL_TIES}


@pytest.mark.parametrize("name", sorted(REQUESTS))
def test_wave_equals_fused_program_and_never_builds_a_batch(
        corpus, build_batch_calls, name):
    request = REQUESTS[name]
    got = _leaf(_service(corpus), corpus, request)
    assert build_batch_calls == [], "the one-device node stacked the group"
    want = _fused(corpus, request)
    assert got.failed_splits == []
    assert got.num_successful_splits == N_SPLITS
    assert got.num_hits == want.num_hits > 0
    assert _hit_rows(got) == _hit_rows(want)
    assert len(got.partial_hits) == request.max_hits
    assert _aggs(got) == _aggs(want)


def test_all_ties_subset_is_the_collectors_total_order(corpus):
    got = _leaf(_service(corpus), corpus, ALL_TIES)
    values = [h.sort_value for h in got.partial_hits]
    assert len(set(values)) == 1, "the tie class is not exercised"
    keys = [(h.split_id, h.doc_id) for h in got.partial_hits]
    assert keys == sorted(keys)
    assert {h.split_id for h in got.partial_hits} == {SPLIT_IDS[0]}


def test_wave_is_independent_of_the_order_the_splits_arrive_in(corpus):
    forward = _leaf(_service(corpus), corpus, ALL_TIES)
    backward = _leaf(_service(corpus), corpus, ALL_TIES,
                     splits=list(reversed(corpus[2])))
    assert _hit_rows(forward) == _hit_rows(backward)


def test_all_executions_are_in_flight_before_any_returns(corpus, executions):
    barrier = threading.Barrier(N_SPLITS, timeout=30)
    executions["before"] = lambda split_id: barrier.wait()
    before = _wave_widths()
    got = _leaf(_service(corpus), corpus, SCORE_SORTED)
    # a split that ran after another had returned would have broken the
    # barrier and failed
    assert got.failed_splits == []
    assert got.num_successful_splits == N_SPLITS
    assert len(set(executions["threads"])) == N_SPLITS
    assert threading.current_thread() not in executions["threads"]
    assert _hit_rows(got) == _hit_rows(_fused(corpus, SCORE_SORTED))
    count, total = _wave_widths()
    assert (count - before[0], total - before[1]) == (1, float(N_SPLITS))


def test_group_of_one_runs_on_the_calling_thread(corpus, executions):
    before = _wave_widths()
    got = _leaf(_service(corpus), corpus, SCORE_SORTED,
                splits=corpus[2][1:2])
    assert got.num_successful_splits == 1 and got.num_hits > 0
    assert executions["threads"] == [threading.current_thread()]
    assert _wave_widths() == before, "a group of one is not a wave"


def test_a_failing_split_is_one_error_beside_the_other_answers(
        corpus, executions):
    def fail_one(split_id):
        if split_id == SPLIT_IDS[2]:
            raise RuntimeError("injected: split-2 broke")
    executions["before"] = fail_one
    got = _leaf(_service(corpus), corpus, TIMESTAMP_SORTED)
    assert [(e.split_id, e.retryable) for e in got.failed_splits] == \
        [(SPLIT_IDS[2], True)]
    assert "injected" in got.failed_splits[0].error
    assert got.num_successful_splits == N_SPLITS - 1
    # the others' answers: the mesh program over the three that ran
    want = _fused(corpus, TIMESTAMP_SORTED,
                  [i for i in SPLIT_IDS if i != SPLIT_IDS[2]])
    assert got.num_hits == want.num_hits
    assert _hit_rows(got) == _hit_rows(want)


def _execute_prepared(service, corpus, request):
    """`_execute_per_split` over the four prepared splits, under whatever
    scopes the caller has bound; the collector it filled."""
    prepared = service._prepare_per_split(corpus[2], MAPPER, request)
    collector = IncrementalCollector(max_hits=request.max_hits)
    service._execute_per_split(prepared, MAPPER, request, collector)
    return collector


def test_expired_deadline_reports_every_remaining_split(corpus, executions):
    service = _service(corpus)
    with deadline_scope(Deadline.from_millis(0)):
        collector = _execute_prepared(service, corpus, SCORE_SORTED)
    assert executions["threads"] == [], "a split ran past the deadline"
    assert sorted(e.split_id for e in collector.failed_splits) == SPLIT_IDS
    assert all(e.retryable and "deadline exceeded before split executed"
               in e.error for e in collector.failed_splits)
    assert collector.num_hits == 0


def test_cancelled_token_reports_every_remaining_split(corpus, executions):
    service = _service(corpus)
    token = CancellationToken()
    token.cancel("user pressed stop")
    with cancel_scope(token):
        collector = _execute_prepared(service, corpus, SCORE_SORTED)
    assert executions["threads"] == []
    assert sorted(e.split_id for e in collector.failed_splits) == SPLIT_IDS
    assert all(not e.retryable
               and "query cancelled before split executed: user pressed stop"
               in e.error for e in collector.failed_splits)


def test_cancel_in_flight_is_seen_split_by_split(corpus, executions):
    """A token cancelled while the wave flies: every split that checks it
    reports a cancel that is never retryable, and none is left running."""
    token = CancellationToken()
    barrier = threading.Barrier(N_SPLITS, timeout=30)

    def cancel_together(split_id):
        barrier.wait()
        token.cancel("mid-wave")
    executions["before"] = cancel_together
    service = _service(corpus)
    with cancel_scope(token):
        collector = _execute_prepared(service, corpus, SCORE_SORTED)
    assert len(executions["threads"]) == N_SPLITS
    assert all(not e.retryable for e in collector.failed_splits)
    assert (len(collector.failed_splits)
            + collector.num_successful_splits) == N_SPLITS


def test_workers_carry_the_requests_tenant_and_profile(corpus, executions):
    seen = []
    executions["before"] = lambda split_id: seen.append(
        (current_tenant(), current_profile()))
    tenant = TenantContext.for_class("acme", "interactive")
    profile = QueryProfile(query_id="wave-q")
    with tenant_scope(tenant), profile_scope(profile):
        got = _leaf(_service(corpus), corpus, SCORE_SORTED)
    assert got.num_successful_splits == N_SPLITS
    assert len(seen) == N_SPLITS
    assert all(t is tenant and p is profile for t, p in seen)
    assert threading.current_thread() not in executions["threads"]
    # each split's blocking readback is an `execute` phase of its own
    executes = [p for p in profile.phases()
                if p["name"] == "execute" and p.get("stage") == "readback"]
    assert len(executes) == N_SPLITS
    assert profile.counters()["split_wave_width"] == float(N_SPLITS)


def test_pins_are_returned_when_the_wave_ends(corpus, executions):
    def fail_one(split_id):
        if split_id == SPLIT_IDS[0]:
            raise RuntimeError("injected")
    executions["before"] = fail_one
    service = _service(corpus)
    _leaf(service, corpus, FILTERED_WITH_AGGS)
    budget = service.context.hbm_budget
    assert budget._pinned == 0
    assert not any(budget._pin_counts.values())


def test_backpressure_of_one_split_rejects_the_whole_query(
        corpus, executions):
    from quickwit_tpu.tenancy.overload import OverloadShed

    def shed_one(split_id):
        if split_id == SPLIT_IDS[1]:
            raise OverloadShed("batcher", 1.0)
    executions["before"] = shed_one
    service = _service(corpus)
    with pytest.raises(OverloadShed):
        _leaf(service, corpus, SCORE_SORTED)
    # raised once every worker had ended and returned its pins
    assert len(executions["threads"]) == N_SPLITS
    assert service.context.hbm_budget._pinned == 0
    assert not any(service.context.hbm_budget._pin_counts.values())


def test_with_a_mesh_the_same_group_still_takes_the_fused_program(
        corpus, build_batch_calls):
    """Under the suite's own 8 devices nothing has changed: the
    `_score`-sorted group is stacked and dispatched on the mesh."""
    before = MESH_DISPATCHES_TOTAL.get()
    widths = _wave_widths()
    got = _leaf(_service(corpus, one_device=False), corpus, SCORE_SORTED)
    assert len(build_batch_calls) == 1
    assert MESH_DISPATCHES_TOTAL.get() == before + 1
    assert _wave_widths() == widths
    assert _hit_rows(got) == _hit_rows(_fused(corpus, SCORE_SORTED))


def test_concurrent_requests_ride_the_batcher_split_by_split(corpus):
    """Waves of several requests at once: every unit goes through the
    node's QueryBatcher, so requests of one shape may share a dispatch on
    a split, and each is answered as it is alone."""
    service = _service(corpus)
    bounds = [(lo, lo + 2) for lo in range(6)]

    def request(lo, hi):
        return SearchRequest(
            index_ids=["waves"],
            query_ast=Bool(must=(FullText("body", "alpha", "or"),),
                           filter=(Range("tenant_id", RangeBound(lo, True),
                                         RangeBound(hi, True)),)),
            max_hits=10)

    alone = [_leaf(_service(corpus), corpus, request(lo, hi))
             for lo, hi in bounds]
    batcher = service.context.query_batcher
    before = batcher.num_queries
    got = [None] * len(bounds)
    start = threading.Barrier(len(bounds), timeout=30)

    def client(number):
        start.wait()
        got[number] = _leaf(service, corpus, request(*bounds[number]))

    clients = [threading.Thread(target=client, args=(n,))
               for n in range(len(bounds))]
    for thread in clients:
        thread.start()
    for thread in clients:
        thread.join()
    assert batcher.num_queries - before == len(bounds) * N_SPLITS
    for mine, want in zip(got, alone):
        assert mine.failed_splits == []
        assert mine.num_hits == want.num_hits
        assert _hit_rows(mine) == _hit_rows(want)
    assert service.context.hbm_budget._pinned == 0
    assert not any(service.context.hbm_budget._pin_counts.values())
