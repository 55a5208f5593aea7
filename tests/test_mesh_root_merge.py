"""Multi-chip collective root merge: device ≡ host equivalence suite.

The collective whole-query program (parallel/fanout.mesh_batch_fn) runs
score + threshold-exchange + top-K merge + agg reduction ON the mesh and
reads back one packed scalar array. The claim under test is BIT-IDENTITY
with the host-merge twin — what one device serves: leaf search per split,
merged by the IncrementalCollector in split-id order — same hits in the
same total order — (key desc, split_id asc, doc asc), including tie
subsets under truncation — same counts, and same agg states, for every
mesh shape that divides the batch (one that does not is refused). Around
that sit the routing rules that keep the host path alive (search_after,
Tier A/B cache consultation), the cross-query mesh-resident stacks (warm multi-split query uploads
zero column bytes to any chip), the chunked × fused interplay, and the
DST fanout scenario's cache≡cold invariant against the mesh path.

Fixture latencies are integral so stats sums are exact under any
reassociation — agg equality here is ==, not approx.
"""

import threading

import jax
import numpy as np
import pytest

from quickwit_tpu.common.uri import Uri
from quickwit_tpu.index import SplitReader, SplitWriter
from quickwit_tpu.index.format import DOC_PAD
from quickwit_tpu.models import DocMapper, FieldMapping, FieldType
from quickwit_tpu.parallel import build_batch, execute_batch, make_mesh
from quickwit_tpu.parallel import fanout
from quickwit_tpu.query.ast import Bool, FullText, MatchAll, Range, RangeBound, Term
from quickwit_tpu.search import (
    IncrementalCollector, SearchRequest, SortField, finalize_aggregations,
    leaf_search_single_split,
)
from quickwit_tpu.storage import RamStorage

N_SPLITS = 8
DOCS_PER_SPLIT = 150
SEVERITIES = ["DEBUG", "INFO", "WARN", "ERROR"]

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


def _docs(split: int, n=DOCS_PER_SPLIT):
    rng = np.random.RandomState(split)
    return [{
        "timestamp": 1_600_000_000 + split * 40_000 + i * 60,
        "severity_text": SEVERITIES[int(rng.randint(0, 4))],
        "tenant_id": int(rng.randint(0, 4)),
        "body": " ".join(["alpha"] * int(rng.randint(1, 3))
                         + ["beta"] * int(rng.randint(0, 2))),
        # integral-valued floats: stats/avg sums are exact under any
        # reduction order, so device vs host agg equality can be ==
        "latency": float(rng.randint(0, 5_000)),
    } for i in range(n)]


def _build_readers(all_docs, ram, env=None):
    import os
    old = {k: os.environ.get(k) for k in (env or {})}
    os.environ.update(env or {})
    try:
        storage = RamStorage(Uri.parse(ram))
        out = {}
        for split_id, docs in all_docs.items():
            w = SplitWriter(MAPPER)
            for d in docs:
                w.add_json_doc(d)
            storage.put(f"{split_id}.split", w.finish())
            out[split_id] = SplitReader(storage, f"{split_id}.split")
        return out
    finally:
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


@pytest.fixture(scope="module")
def readers():
    return _build_readers(
        {f"split-{s}": _docs(s) for s in range(N_SPLITS)}, "ram:///meshmerge")


def _batch(request, readers, mesh, pad_to=None):
    ids = sorted(readers.keys())
    batch = build_batch(request, MAPPER, [readers[i] for i in ids], ids,
                       pad_to_splits=pad_to)
    return execute_batch(batch, request, mesh)


def _host(request, readers):
    """The host-merge twin: per-split leaf search, collector merge in
    split-id order."""
    coll = IncrementalCollector(max_hits=request.max_hits,
                                start_offset=request.start_offset)
    for split_id in sorted(readers):
        coll.add_leaf_response(leaf_search_single_split(
            request, MAPPER, readers[split_id], split_id))
    return coll.to_leaf_response()


def _hit_rows(resp):
    return [(h.split_id, h.doc_id, h.sort_value, h.sort_value2,
             h.raw_sort_value, h.raw_sort_value2) for h in resp.partial_hits]


def _aggs(resp):
    coll = IncrementalCollector(max_hits=0)
    coll.add_leaf_response(resp)
    return finalize_aggregations(coll.aggregation_states())


def _assert_identical(mesh_resp, host_resp):
    """Bit-identity: every field of every hit, counts, and finalized aggs
    must be EXACTLY equal — no approx anywhere."""
    assert mesh_resp.num_hits == host_resp.num_hits
    assert _hit_rows(mesh_resp) == _hit_rows(host_resp)
    assert _aggs(mesh_resp) == _aggs(host_resp)


REQUESTS = [
    # BM25-scored full text (default sort: _score)
    SearchRequest(index_ids=["x"], query_ast=FullText("body", "beta", "or"),
                  max_hits=13),
    # single-key column sort, descending
    SearchRequest(index_ids=["x"], query_ast=Term("severity_text", "ERROR"),
                  max_hits=9, sort_fields=(SortField("timestamp", "desc"),)),
    # 2-key sort with heavy primary ties: the secondary + lane-order
    # tie-break genuinely decide the truncated tail
    SearchRequest(index_ids=["x"], query_ast=MatchAll(), max_hits=11,
                  sort_fields=(SortField("tenant_id", "asc"),
                               SortField("timestamp", "desc"))),
    # filtered aggs alongside hits
    SearchRequest(
        index_ids=["x"],
        query_ast=Bool(must=(FullText("body", "alpha", "or"),),
                       filter=(Range("tenant_id", RangeBound(1, True),
                                     RangeBound(2, True)),)),
        max_hits=10,
        aggs={"sev": {"terms": {"field": "severity_text", "size": 10}},
              "lat": {"stats": {"field": "latency"}},
              "ot": {"date_histogram": {"field": "timestamp",
                                        "fixed_interval": "1h"}}}),
    # k=0 count/agg-only: the collective program skips the top-k merge
    # entirely (psum count + reduced agg states only)
    SearchRequest(index_ids=["x"], query_ast=FullText("body", "beta", "or"),
                  max_hits=0,
                  aggs={"sev": {"terms": {"field": "severity_text"}},
                        "avg": {"avg": {"field": "latency"}}}),
]

MESH_SHAPES = [(2, 1), (4, 2), (8, 1)]


@pytest.mark.parametrize("shape", MESH_SHAPES,
                         ids=[f"{a}x{d}" for a, d in MESH_SHAPES])
@pytest.mark.parametrize("req_idx", range(len(REQUESTS)))
def test_collective_matches_host_merge_bit_identical(readers, shape, req_idx):
    """1/2/4/8-way split sharding (x doc sharding): the on-mesh root merge
    must equal the host-merge twin exactly."""
    request = REQUESTS[req_idx]
    host = _host(request, readers)
    mesh = _batch(request, readers, mesh=make_mesh(*shape))
    _assert_identical(mesh, host)


def test_collective_matches_sequential_collector_merge(readers):
    """Transitively: mesh result ≡ per-split leaf search merged through the
    IncrementalCollector (the reference's merge-tree order)."""
    request = REQUESTS[1]
    coll = IncrementalCollector(max_hits=request.max_hits)
    for split_id in sorted(readers):
        coll.add_leaf_response(leaf_search_single_split(
            request, MAPPER, readers[split_id], split_id))
    mesh = _batch(request, readers, mesh=make_mesh(4, 2))
    assert mesh.num_hits == coll.num_hits
    assert [(h.split_id, h.doc_id) for h in mesh.partial_hits] == \
        [(h.split_id, h.doc_id) for h in coll.partial_hits()]


def test_all_ties_truncation(readers):
    """Every candidate shares one sort value and k < matches: the kept tie
    subset is decided purely by the collector total order (split_id asc,
    doc asc). The PR 14 bug class — a mesh lane permutation would keep a
    DIFFERENT (but individually valid) subset; bit-identity forbids it."""
    request = SearchRequest(
        index_ids=["x"], query_ast=Term("severity_text", "WARN"), max_hits=7,
        # tenant_id asc over docs filtered to one severity still carries
        # massive ties; add a constant-ish secondary-free single key
        sort_fields=(SortField("tenant_id", "asc"),))
    host = _host(request, readers)
    for shape in MESH_SHAPES:
        mesh = _batch(request, readers, mesh=make_mesh(*shape))
        _assert_identical(mesh, host)
    # sanity: the tie class is actually exercised (first k share a value)
    vals = [h.sort_value for h in host.partial_hits]
    assert len(set(vals)) < len(vals)


def test_nondivisible_mesh_is_refused(readers):
    """A mesh whose split axis does not divide the batch has no program:
    the dispatch raises (the service then goes per split) — no collective
    dispatch, no ragged sharding error from deep inside staging."""
    from quickwit_tpu.observability.metrics import MESH_DISPATCHES_TOTAL
    request = REQUESTS[0]
    ids = sorted(readers.keys())[:3]          # 3 splits, axis 2: ragged
    sub = {i: readers[i] for i in ids}
    before = MESH_DISPATCHES_TOTAL.get()
    with pytest.raises(ValueError, match="does not shard"):
        _batch(request, sub, mesh=make_mesh(2, 1))
    assert MESH_DISPATCHES_TOTAL.get() == before
    assert not fanout._MESH_DISPATCH_LOCK.locked()


def test_padded_batch_on_mesh(readers):
    """Dummy pad lanes (split_id == "") must contribute nothing through the
    collective merge either."""
    request = REQUESTS[0]
    ids = sorted(readers.keys())[:3]
    sub = {i: readers[i] for i in ids}
    host = _host(request, sub)
    mesh = _batch(request, sub, mesh=make_mesh(4, 1), pad_to=4)
    _assert_identical(mesh, host)
    assert all(h.split_id for h in mesh.partial_hits)


@pytest.mark.parametrize("env", [
    pytest.param(None, id="v3"),
    pytest.param({"QW_DISABLE_IMPACT": "1"}, id="v2-doc-ordered"),
    pytest.param({"QW_DISABLE_PACKED": "1"}, id="v1-unpacked"),
])
def test_collective_across_split_formats(env):
    """v1 (unpacked columns), v2 (doc-ordered postings), v3 (impact-ordered
    + packed + threshold pushdown): the collective merge must be
    bit-identical to the host twin for each on-disk format."""
    tag = "-".join(sorted(env)) if env else "v3"
    readers = _build_readers({f"s{i}": _docs(i, 120) for i in range(4)},
                             f"ram:///meshfmt-{tag}", env=env)
    for request in (REQUESTS[0], REQUESTS[1], REQUESTS[4]):
        host = _host(request, readers)
        mesh = _batch(request, readers, mesh=make_mesh(4, 2))
        _assert_identical(mesh, host)


def test_chunked_fused_interplay():
    """A chunked per-split scan (cross-chunk threshold tightening) merged
    on the host must equal the fused collective mesh program: the two
    execution strategies answer from opposite ends — resumable slabs vs
    one whole-query dispatch — and must agree exactly."""
    from quickwit_tpu.search.chunkexec import CHUNKING
    readers = _build_readers(
        {f"big-{i}": _docs(i, DOC_PAD + 90) for i in range(2)},
        "ram:///meshchunk")
    request = SearchRequest(
        index_ids=["x"], query_ast=Term("severity_text", "ERROR"),
        max_hits=10, sort_fields=(SortField("timestamp", "desc"),))
    CHUNKING.set(doc_span=DOC_PAD)  # force >=2 dense chunks per split
    try:
        coll = IncrementalCollector(max_hits=request.max_hits)
        for split_id in sorted(readers):
            coll.add_leaf_response(leaf_search_single_split(
                request, MAPPER, readers[split_id], split_id))
    finally:
        CHUNKING.set(doc_span=None)
    mesh = _batch(request, readers, mesh=make_mesh(2, 1))
    assert mesh.num_hits == coll.num_hits
    assert [(h.split_id, h.doc_id) for h in mesh.partial_hits] == \
        [(h.split_id, h.doc_id) for h in coll.partial_hits()]


def test_property_seeded_equivalence(readers):
    """Seeded property sweep: randomized sorts/filters/aggs/k through one
    mesh shape, every draw bit-identical to the host twin."""
    rng = np.random.RandomState(1234)
    mesh = make_mesh(4, 2)
    sortable = ["timestamp", "tenant_id", "latency"]
    queries = [MatchAll(),
               FullText("body", "beta", "or"),
               Term("severity_text", "INFO"),
               Bool(must=(MatchAll(),),
                    filter=(Range("tenant_id", RangeBound(0, True),
                                  RangeBound(2, False)),))]
    for _ in range(6):
        q = queries[int(rng.randint(0, len(queries)))]
        k = int(rng.randint(0, 16))
        n_sort = int(rng.randint(0, 3))
        fields = list(rng.choice(sortable, size=n_sort, replace=False))
        sorts = tuple(SortField(f, ["asc", "desc"][int(rng.randint(0, 2))])
                      for f in fields)
        aggs = None
        if k == 0 or rng.randint(0, 2):
            aggs = {"sev": {"terms": {"field": "severity_text"}},
                    "lat": {"stats": {"field": "latency"}}}
        request = SearchRequest(index_ids=["x"], query_ast=q, max_hits=k,
                                sort_fields=sorts, aggs=aggs)
        host = _host(request, readers)
        got = _batch(request, readers, mesh=mesh)
        _assert_identical(got, host)


# --- mesh-resident stacks ---------------------------------------------------

def test_warm_stack_zero_column_upload(readers):
    """Second query over the same split set on the same mesh must serve
    every column-family slot from the mesh-resident stack: zero column
    bytes uploaded to any chip, full staging-cache hit recorded, and the
    per-device accounting pinned under the stack owner."""
    from quickwit_tpu.search.admission import HbmBudget
    from quickwit_tpu.search.residency import (
        RESIDENT_COLUMN_MISSES, RESIDENT_STAGING_CACHE_HITS,
        ResidentColumnStore,
    )
    store = ResidentColumnStore()
    budget = HbmBudget()
    mesh = make_mesh(4, 2)
    request = SearchRequest(index_ids=["x"], query_ast=MatchAll(), max_hits=6,
                            sort_fields=(SortField("latency", "asc"),))
    ids = sorted(readers.keys())

    def run_once():
        batch = build_batch(request, MAPPER, [readers[i] for i in ids], ids)
        fanout.stage_device_inputs(batch, mesh, resident_store=store,
                                   budget=budget)
        resp = execute_batch(batch, request, mesh)
        fanout.release_stack_pin(batch, budget)
        return resp

    cold = run_once()
    misses_after_cold = RESIDENT_COLUMN_MISSES.get()
    full_hits_before = RESIDENT_STAGING_CACHE_HITS.get()
    warm = run_once()
    assert RESIDENT_COLUMN_MISSES.get() == misses_after_cold  # zero uploads
    assert RESIDENT_STAGING_CACHE_HITS.get() == full_hits_before + 1
    _assert_identical(warm, cold)
    # the resident bytes are the PER-DEVICE shard footprint, pinned under
    # the synthetic meshstack owner
    stats = store.stats()
    assert stats["splits"] == 1
    (stack_id,) = stats["by_split"]
    assert stack_id.startswith("meshstack:")
    assert 0 < stats["bytes"] < sum(
        a.nbytes for a in build_batch(
            request, MAPPER, [readers[i] for i in ids], ids).arrays)


def test_mesh_metrics_counters(readers):
    """qw_mesh_* counters move with a collective dispatch (the exposition
    grammar itself is covered by test_metrics_format's registry sweep)."""
    from quickwit_tpu.observability.metrics import (
        MESH_COLLECTIVE_BYTES_TOTAL, MESH_DEVICES, MESH_DISPATCHES_TOTAL,
        MESH_THRESHOLD_EXCHANGE_ROUNDS_TOTAL,
    )
    d0 = MESH_DISPATCHES_TOTAL.get()
    b0 = MESH_COLLECTIVE_BYTES_TOTAL.get()
    t0 = MESH_THRESHOLD_EXCHANGE_ROUNDS_TOTAL.get()
    _batch(REQUESTS[1], readers, mesh=make_mesh(8, 1))
    assert MESH_DISPATCHES_TOTAL.get() >= d0 + 1
    assert MESH_COLLECTIVE_BYTES_TOTAL.get() > b0
    assert MESH_THRESHOLD_EXCHANGE_ROUNDS_TOTAL.get() >= t0 + 1
    assert MESH_DEVICES.get() == 8
    # k=0 dispatch carries no threshold exchange
    t1 = MESH_THRESHOLD_EXCHANGE_ROUNDS_TOTAL.get()
    _batch(REQUESTS[4], readers, mesh=make_mesh(8, 1))
    assert MESH_THRESHOLD_EXCHANGE_ROUNDS_TOTAL.get() == t1


def test_abandoned_dispatch_releases_guard(readers):
    """Deadline-shed seam: abandoning a mesh dispatch must complete the
    cross-procedural critical section (CPU host platform holds the
    dispatch lock from enqueue to completion) so the next collective
    program can fly."""
    request = REQUESTS[0]
    ids = sorted(readers.keys())
    batch = build_batch(request, MAPPER, [readers[i] for i in ids], ids)
    mesh = make_mesh(4, 2)
    dispatched = fanout.dispatch_batch(batch, request, mesh)
    fanout.abandon_dispatch(dispatched)
    assert not fanout._MESH_DISPATCH_LOCK.locked()
    # a subsequent dispatch must not deadlock on a leaked guard
    done = []

    def next_query():
        done.append(_batch(request, readers, mesh=mesh))

    t = threading.Thread(target=next_query)
    t.start()
    t.join(timeout=60)
    assert done and done[0].num_hits > 0


# --- service-level routing: where the host path survives --------------------

@pytest.fixture(scope="module")
def cluster():
    """One searcher node over a 6-split index: multi-split groups route
    through `_prepare_group`, whose fused path now dispatches on the mesh."""
    from quickwit_tpu.indexing import IndexingPipeline, PipelineParams, VecSource
    from quickwit_tpu.metastore import FileBackedMetastore
    from quickwit_tpu.models.index_metadata import (
        IndexConfig, IndexMetadata, SourceConfig,
    )
    from quickwit_tpu.search.root import RootSearcher
    from quickwit_tpu.search.service import (
        LocalSearchClient, SearcherContext, SearchService,
    )
    from quickwit_tpu.storage import StorageResolver

    resolver = StorageResolver.for_test()
    metastore = FileBackedMetastore(resolver.resolve("ram:///meshsvc/meta"))
    config = IndexConfig(index_id="logs", index_uri="ram:///meshsvc/splits",
                         doc_mapper=MAPPER, split_num_docs_target=100)
    metastore.create_index(IndexMetadata(
        index_uid="logs:01", index_config=config,
        sources={"src": SourceConfig("src", "vec")}))
    # uniform per-split value spans: the fused batch requires uniform
    # column packings, and the pipeline cuts splits by arrival order
    docs = [{"timestamp": 1_600_000_000 + i * 60,
             "severity_text": SEVERITIES[i % 4],
             "tenant_id": i % 4,
             "body": ["alpha beta", "alpha", "beta beta", "alpha alpha"][i % 4],
             "latency": float((i * 37) % 5_000)}
            for i in range(600)]
    IndexingPipeline(
        PipelineParams(index_uid="logs:01", source_id="src",
                       split_num_docs_target=100, batch_num_docs=50),
        MAPPER, VecSource(docs), metastore,
        resolver.resolve("ram:///meshsvc/splits")).run_to_completion()
    # Tier A/B caches ON: the per-split cache-routing rule is live
    service = SearchService(SearcherContext(storage_resolver=resolver),
                            node_id="node-0")
    root = RootSearcher(metastore, {"node-0": LocalSearchClient(service)})
    return service, root


def _mesh_dispatches():
    from quickwit_tpu.observability.metrics import MESH_DISPATCHES_TOTAL
    return MESH_DISPATCHES_TOTAL.get()


def test_service_scored_query_rides_mesh_and_warm_equals_cold(cluster):
    """A scored multi-split search is mask-cache-ineligible, so it stays
    fused — and the fused path now IS the collective mesh. Cold and warm
    (mesh-resident stacks) answers must match exactly."""
    _service, root = cluster
    request = SearchRequest(index_ids=["logs"],
                            query_ast=FullText("body", "beta", "or"),
                            max_hits=10)
    before = _mesh_dispatches()
    cold = root.search(request)
    assert _mesh_dispatches() > before
    warm = root.search(request)
    assert [(h.split_id, h.doc_id) for h in warm.hits] == \
        [(h.split_id, h.doc_id) for h in cold.hits]
    assert warm.num_hits == cold.num_hits


def test_service_search_after_routes_per_split(cluster):
    """search_after pushdown is a per-split predicate: such requests keep
    the host merge path (no mesh dispatch) and must page consistently."""
    _service, root = cluster
    base = SearchRequest(index_ids=["logs"], query_ast=MatchAll(),
                         max_hits=20,
                         sort_fields=(SortField("timestamp", "desc"),))
    full = root.search(base)
    pivot = full.hits[9]
    marker = list(pivot.sort_values) + [pivot.split_id, pivot.doc_id]
    before = _mesh_dispatches()
    paged = root.search(SearchRequest(
        index_ids=["logs"], query_ast=MatchAll(), max_hits=10,
        sort_fields=(SortField("timestamp", "desc"),),
        search_after=marker))
    assert _mesh_dispatches() == before
    assert [(h.split_id, h.doc_id) for h in paged.hits] == \
        [(h.split_id, h.doc_id) for h in full.hits[10:20]]


def test_service_cache_routing_rule_keeps_host_path(cluster):
    """PR 10 Tier A/B caches consult and fill PER SPLIT — they cannot be
    reached from inside a collective program. The routing rule
    (`_split_caches_route_per_split`) must therefore keep mask-eligible
    sorted queries and Tier-B-eligible agg-only queries off the mesh."""
    service, root = cluster
    assert service.context.mask_cache is not None  # rule is live
    before = _mesh_dispatches()
    sorted_resp = root.search(SearchRequest(
        index_ids=["logs"], query_ast=Term("severity_text", "ERROR"),
        max_hits=10, sort_fields=(SortField("timestamp", "desc"),)))
    agg_resp = root.search(SearchRequest(
        index_ids=["logs"], query_ast=Term("severity_text", "ERROR"),
        max_hits=0, aggs={"t": {"terms": {"field": "tenant_id"}}}))
    assert _mesh_dispatches() == before
    assert sorted_resp.num_hits == agg_resp.num_hits > 0


def test_service_caches_off_restores_fused_mesh_routing(cluster):
    """Both cache kill switches off: the same sorted query re-fuses onto
    the mesh, bit-identical to the cache-routed per-split answer."""
    from quickwit_tpu.search.root import RootSearcher
    from quickwit_tpu.search.service import (
        LocalSearchClient, SearcherContext, SearchService,
    )
    service, root = cluster
    request = SearchRequest(
        index_ids=["logs"], query_ast=Term("severity_text", "ERROR"),
        max_hits=12, sort_fields=(SortField("timestamp", "desc"),))
    expected = root.search(request)
    bare = SearchService(
        SearcherContext(storage_resolver=service.context.storage_resolver,
                        enable_mask_cache=False, enable_agg_cache=False),
        node_id="node-bare")
    from quickwit_tpu.metastore import FileBackedMetastore
    metastore = FileBackedMetastore(
        service.context.storage_resolver.resolve("ram:///meshsvc/meta"))
    bare_root = RootSearcher(metastore,
                             {"node-bare": LocalSearchClient(bare)})
    before = _mesh_dispatches()
    got = bare_root.search(request)
    assert _mesh_dispatches() > before
    assert [(h.split_id, h.doc_id) for h in got.hits] == \
        [(h.split_id, h.doc_id) for h in expected.hits]
    assert got.num_hits == expected.num_hits


# --- DST: the fanout scenario drives the mesh path --------------------------

def test_dst_fanout_invariants_over_mesh_path():
    """The DST fanout scenario (offload fan-out, sorted searches, cancels)
    now routes its fused multi-split groups through the collective mesh;
    cache_cold_equivalence and cancel_responsiveness must still hold, and
    the trace must stay seed-deterministic."""
    from quickwit_tpu.dst import SCENARIOS, run_scenario
    for seed in (0, 3):
        result = run_scenario(SCENARIOS["fanout"], seed=seed,
                              break_publish=False, break_wal=False)
        assert result.ok, [v.to_dict() for v in result.violations]
