"""Multi-split mesh-sharded execution parity.

The collective batch program's merged result must equal what one device
serves: leaf search per split, merged through the IncrementalCollector in
split-id order (the reference's merge-tree invariant) — on every mesh
layout.
"""

import jax
import numpy as np
import pytest

from quickwit_tpu.common.uri import Uri
from quickwit_tpu.index import SplitReader, SplitWriter
from quickwit_tpu.models import DocMapper, FieldMapping, FieldType
from quickwit_tpu.parallel import build_batch, execute_batch, make_mesh
from quickwit_tpu.query.ast import Bool, FullText, MatchAll, Range, RangeBound, Term
from quickwit_tpu.search import (
    IncrementalCollector, SearchRequest, SortField, finalize_aggregations,
    leaf_search_single_split,
)
from quickwit_tpu.storage import RamStorage

N_SPLITS = 4
DOCS_PER_SPLIT = 300


def mapper():
    return DocMapper(
        field_mappings=[
            FieldMapping("timestamp", FieldType.DATETIME, fast=True,
                         input_formats=("unix_timestamp",)),
            FieldMapping("severity_text", FieldType.TEXT, tokenizer="raw", fast=True),
            FieldMapping("tenant_id", FieldType.U64, fast=True),
            FieldMapping("body", FieldType.TEXT),
            FieldMapping("latency", FieldType.F64, fast=True),
        ],
        timestamp_field="timestamp",
        default_search_fields=("body",),
    )


MAPPER = mapper()
SEVERITIES = ["DEBUG", "INFO", "WARN", "ERROR"]


def make_corpus(split: int):
    rng = np.random.RandomState(split)
    docs = []
    for i in range(DOCS_PER_SPLIT):
        docs.append({
            "timestamp": 1_600_000_000 + split * 50_000 + i * 60,
            "severity_text": SEVERITIES[int(rng.randint(0, 4))],
            "tenant_id": int(rng.randint(0, 4)),
            "body": " ".join(["alpha"] * int(rng.randint(1, 3))
                             + ["beta"] * int(rng.randint(0, 2))),
            "latency": float(rng.gamma(2.0, 40.0)),
        })
    return docs


ALL_DOCS = {f"split-{s}": make_corpus(s) for s in range(N_SPLITS)}


@pytest.fixture(scope="module")
def readers():
    storage = RamStorage(Uri.parse("ram:///parallel"))
    out = {}
    for split_id, docs in ALL_DOCS.items():
        w = SplitWriter(MAPPER)
        for d in docs:
            w.add_json_doc(d)
        storage.put(f"{split_id}.split", w.finish())
        out[split_id] = SplitReader(storage, f"{split_id}.split")
    return out


def reference_merge(request, readers):
    coll = IncrementalCollector(max_hits=request.max_hits,
                                start_offset=request.start_offset)
    for split_id, reader in readers.items():
        coll.add_leaf_response(
            leaf_search_single_split(request, MAPPER, reader, split_id))
    return coll


def batch_result(request, readers, mesh=None, pad_to=None):
    """The batch on `mesh` (default: two devices on the split axis)."""
    ids = list(readers.keys())
    batch = build_batch(request, MAPPER, [readers[i] for i in ids], ids,
                        pad_to_splits=pad_to)
    return execute_batch(batch, request, mesh or make_mesh(2, 1))


REQUESTS = [
    SearchRequest(index_ids=["x"], query_ast=FullText("body", "beta", "or"),
                  max_hits=12),
    SearchRequest(index_ids=["x"], query_ast=Term("severity_text", "ERROR"),
                  max_hits=7, sort_fields=(SortField("timestamp", "desc"),)),
    SearchRequest(index_ids=["x"], query_ast=MatchAll(), max_hits=5,
                  sort_fields=(SortField("latency", "asc"),)),
    SearchRequest(
        index_ids=["x"],
        query_ast=Bool(must=(FullText("body", "alpha", "or"),),
                       filter=(Range("tenant_id", RangeBound(1, True),
                                     RangeBound(2, True)),)),
        max_hits=10,
        aggs={"sev": {"terms": {"field": "severity_text", "size": 10}},
              "over_time": {"date_histogram": {"field": "timestamp",
                                               "fixed_interval": "1h"}},
              "lat": {"stats": {"field": "latency"}}},
    ),
    # count/agg-only: k=0 batch path skips the cross-split hit merge
    SearchRequest(index_ids=["x"], query_ast=FullText("body", "beta", "or"),
                  max_hits=0,
                  aggs={"sev": {"terms": {"field": "severity_text"}}}),
    # 2-key sorts ride the batch path (lexicographic cross-split re-top-k);
    # tenant_id has heavy ties so the secondary key genuinely decides
    SearchRequest(index_ids=["x"], query_ast=MatchAll(), max_hits=8,
                  sort_fields=(SortField("tenant_id", "asc"),
                               SortField("timestamp", "desc"))),
    SearchRequest(index_ids=["x"],
                  query_ast=Term("severity_text", "ERROR"), max_hits=6,
                  sort_fields=(SortField("timestamp", "desc"),
                               SortField("latency", "asc"))),
]


# the two layouts tests/test_tpu_compile.py compiles for a described chip
@pytest.mark.parametrize("layout", [(4, 1), (2, 2)],
                         ids=lambda l: f"{l[0]}x{l[1]}")
@pytest.mark.parametrize("req_idx", range(len(REQUESTS)))
def test_batch_matches_sequential_merge(readers, req_idx, layout):
    request = REQUESTS[req_idx]
    expected = reference_merge(request, readers)
    got = batch_result(request, readers, make_mesh(*layout))

    assert got.num_hits == expected.num_hits
    exp_hits = [(h.split_id, h.doc_id, h.sort_value, h.sort_value2,
                 h.raw_sort_value2) for h in expected.partial_hits()]
    got_hits = [(h.split_id, h.doc_id, h.sort_value, h.sort_value2,
                 h.raw_sort_value2) for h in got.partial_hits]
    assert [(s, d) for s, d, *_ in got_hits] == \
        [(s, d) for s, d, *_ in exp_hits]
    for (_, _, gv, gv2, gr2), (_, _, ev, ev2, er2) in zip(got_hits, exp_hits):
        assert gv == pytest.approx(ev, rel=1e-5)
        assert gv2 == pytest.approx(ev2, rel=1e-5)
        if er2 is not None and isinstance(er2, int):
            assert gr2 == er2

    if request.aggs:
        exp_aggs = finalize_aggregations(expected.aggregation_states())
        got_coll = IncrementalCollector(max_hits=0)
        got_coll.add_leaf_response(got)
        got_aggs = finalize_aggregations(got_coll.aggregation_states())
        assert _normalize(got_aggs) == _normalize(exp_aggs)


def _normalize(aggs):
    """Float reduction order differs between device tree-sums and host
    sequential merges; compare to 9 significant digits."""
    import json

    def round_floats(obj):
        if isinstance(obj, float):
            return float(f"{obj:.9g}")
        if isinstance(obj, dict):
            return {k: round_floats(v) for k, v in obj.items()}
        if isinstance(obj, list):
            return [round_floats(v) for v in obj]
        return obj

    return round_floats(json.loads(json.dumps(aggs, default=float, sort_keys=True)))


def test_mesh_sharded_matches_sequential_merge(readers):
    n_dev = len(jax.devices())
    assert n_dev >= 8, "tests expect 8 virtual cpu devices (conftest)"
    request = REQUESTS[3]
    mesh = make_mesh(4, 2)  # 4-way split parallel x 2-way doc parallel
    got_mesh = batch_result(request, readers, mesh)
    expected = reference_merge(request, readers)
    assert got_mesh.num_hits == expected.num_hits
    assert [(h.split_id, h.doc_id) for h in got_mesh.partial_hits] == \
        [(h.split_id, h.doc_id) for h in expected.partial_hits()]
    ma = IncrementalCollector(0); ma.add_leaf_response(got_mesh)
    assert _normalize(finalize_aggregations(ma.aggregation_states())) == \
        _normalize(finalize_aggregations(expected.aggregation_states()))


def test_dispatch_batch_refuses_a_mesh_that_does_not_divide_the_batch(readers):
    """Four splits on a three-way split axis: no program to run, and no
    other program is run in its place."""
    from quickwit_tpu.parallel import fanout
    request = REQUESTS[0]
    ids = list(readers.keys())
    batch = build_batch(request, MAPPER, [readers[i] for i in ids], ids)
    mesh = make_mesh(3, 1)
    launches = fanout.SEARCH_KERNEL_LAUNCHES_TOTAL.get()
    with pytest.raises(ValueError, match="does not shard"):
        fanout.dispatch_batch(batch, request, mesh)
    with pytest.raises(ValueError, match="does not shard"):
        fanout.stage_device_inputs(batch, mesh)
    assert fanout.SEARCH_KERNEL_LAUNCHES_TOTAL.get() == launches
    assert not getattr(batch, "_device_inputs", None)


def test_fanout_has_one_program_family(readers):
    """parallel/fanout.py builds one program, the collective whole-query
    program over a mesh: the one-device fused batch and the query-group
    family are gone, and every compiled entry is keyed by its Mesh."""
    from jax.sharding import Mesh
    from quickwit_tpu.parallel import fanout
    gone = [name for name in dir(fanout)
            if name in ("batch_fn", "abstract_batch_program", "_usable_mesh",
                        "_donate_batch_inputs", "_merge_agg_stack")
            or "group" in name]
    assert gone == []
    batch_result(REQUESTS[0], readers)
    assert fanout._BATCH_JIT_CACHE
    assert all(any(isinstance(part, Mesh) for part in key)
               for key in fanout._BATCH_JIT_CACHE)


def test_batch_with_padding_splits(readers):
    """Batch padded to a multiple of the mesh axis: dummy splits must not
    contribute hits or counts."""
    request = REQUESTS[0]
    expected = reference_merge(request, readers)
    got = batch_result(request, readers, make_mesh(3, 1), pad_to=6)
    assert got.num_hits == expected.num_hits
    assert all(h.split_id for h in got.partial_hits)


def test_batch_term_missing_in_some_splits(readers):
    """A term present in only some splits must lower uniformly (empty
    postings elsewhere) and still produce correct global results."""
    request = SearchRequest(index_ids=["x"],
                            query_ast=FullText("body", "beta", "or"), max_hits=50)
    expected = reference_merge(request, readers)
    got = batch_result(request, readers)
    assert got.num_hits == expected.num_hits


def test_batch_rejects_nonuniform_queries(readers):
    from quickwit_tpu.query.ast import Wildcard
    request = SearchRequest(index_ids=["x"], query_ast=Wildcard("body", "alp*"),
                            max_hits=5)
    ids = list(readers.keys())
    try:
        batch = build_batch(request, MAPPER, [readers[i] for i in ids], ids)
    except ValueError:
        return  # expected: non-uniform structure rejected
    # if it built (all splits expanded identically), execution must still work
    execute_batch(batch, request, make_mesh(2, 1))


def test_batch_numeric_histogram_origin_alignment():
    """Regression: plain histogram aggs must use a batch-global origin."""
    from quickwit_tpu.models import DocMapper, FieldMapping, FieldType
    from quickwit_tpu.index import SplitWriter, SplitReader

    m = DocMapper(field_mappings=[FieldMapping("v", FieldType.F64, fast=True)])
    storage = RamStorage(Uri.parse("ram:///histalign"))
    rs = []
    for s, values in enumerate([[0, 10, 49], [50, 60, 99]]):
        w = SplitWriter(m)
        for v in values:
            w.add_json_doc({"v": v})
        storage.put(f"{s}.split", w.finish())
        rs.append(SplitReader(storage, f"{s}.split"))
    req = SearchRequest(index_ids=["x"], query_ast=MatchAll(), max_hits=0,
                        aggs={"h": {"histogram": {"field": "v", "interval": 50}}})
    batch = build_batch(req, m, rs, ["a", "b"])
    resp = execute_batch(batch, req, make_mesh(2, 1))
    coll = IncrementalCollector(0)
    coll.add_leaf_response(resp)
    got = {b["key"]: b["doc_count"]
           for b in finalize_aggregations(coll.aggregation_states())["h"]["buckets"]}
    assert got == {0.0: 3, 50.0: 3}


def test_batch_histogram_bucket_limit(readers):
    from quickwit_tpu.search.plan import PlanError
    req = SearchRequest(index_ids=["x"], query_ast=MatchAll(), max_hits=0,
                        aggs={"h": {"date_histogram": {"field": "timestamp",
                                                       "fixed_interval": "1s"}}})
    ids = list(readers.keys())
    with pytest.raises(PlanError, match="buckets"):
        build_batch(req, MAPPER, [readers[i] for i in ids], ids)


def test_batch_phrase_with_term_missing_in_one_split():
    from quickwit_tpu.models import DocMapper, FieldMapping, FieldType
    from quickwit_tpu.index import SplitWriter, SplitReader

    m = DocMapper(field_mappings=[
        FieldMapping("body", FieldType.TEXT, record="position")],
        default_search_fields=("body",))
    storage = RamStorage(Uri.parse("ram:///phrasebatch"))
    rs = []
    for s, bodies in enumerate([["hello world x", "other text"],
                                ["hello there", "no match"]]):
        w = SplitWriter(m)
        for b in bodies:
            w.add_json_doc({"body": b})
        storage.put(f"{s}.split", w.finish())
        rs.append(SplitReader(storage, f"{s}.split"))
    req = SearchRequest(index_ids=["x"],
                        query_ast=FullText("body", "hello world", "phrase"),
                        max_hits=10)
    batch = build_batch(req, m, rs, ["a", "b"])  # "world" absent from split b
    resp = execute_batch(batch, req, make_mesh(2, 1))
    assert resp.num_hits == 1
    assert resp.partial_hits[0].split_id == "a"


def test_batch_nested_aggregation_parity(readers):
    """Nested terms>date_histogram through the batched device path must
    equal the sequential per-split merge."""
    request = SearchRequest(
        index_ids=["x"], query_ast=MatchAll(), max_hits=0,
        aggs={"sev": {"terms": {"field": "severity_text"},
                      "aggs": {"ot": {"date_histogram": {
                          "field": "timestamp", "fixed_interval": "1h"}}}}})
    expected = reference_merge(request, readers)
    got = batch_result(request, readers)
    got_coll = IncrementalCollector(max_hits=0)
    got_coll.add_leaf_response(got)
    assert _normalize(finalize_aggregations(got_coll.aggregation_states())) == \
        _normalize(finalize_aggregations(expected.aggregation_states()))


def test_batch_nested_histogram_name_collision(readers):
    """Regression: a nested date_histogram child sharing a name with a
    top-level date_histogram must keep its own batch-global bucket space
    (overrides key by parent>child path)."""
    request = SearchRequest(
        index_ids=["x"], query_ast=MatchAll(), max_hits=0,
        aggs={
            "h": {"date_histogram": {"field": "timestamp",
                                     "fixed_interval": "1h"}},
            "t": {"terms": {"field": "severity_text"},
                  "aggs": {"h": {"date_histogram": {"field": "timestamp",
                                                    "fixed_interval": "1d"}}}},
        })
    expected = reference_merge(request, readers)
    got = batch_result(request, readers)
    got_coll = IncrementalCollector(max_hits=0)
    got_coll.add_leaf_response(got)
    assert _normalize(finalize_aggregations(got_coll.aggregation_states())) == \
        _normalize(finalize_aggregations(expected.aggregation_states()))


def test_batch_dynamic_field_absent_from_one_split():
    """A dynamic-mode path that one split never ingested must contribute
    zero hits from that split — not crash on the missing fieldnorm array
    (regression: _fieldnorm_slot zeros fallback)."""
    from quickwit_tpu.models import DocMapper, FieldMapping, FieldType
    from quickwit_tpu.index import SplitWriter, SplitReader

    m = DocMapper(field_mappings=[FieldMapping("title", FieldType.TEXT)],
                  mode="dynamic")
    storage = RamStorage(Uri.parse("ram:///dynbatch"))
    rs = []
    for s, docs in enumerate([[{"title": "a", "service": "gw"}],
                              [{"title": "b"}]]):  # no `service` in split 1
        w = SplitWriter(m)
        for d in docs:
            w.add_json_doc(d)
        storage.put(f"{s}.split", w.finish())
        rs.append(SplitReader(storage, f"{s}.split"))
    req = SearchRequest(index_ids=["x"],
                        query_ast=Term(field="service", value="gw"),
                        max_hits=10)
    batch = build_batch(req, m, rs, ["a", "b"])
    resp = execute_batch(batch, req, make_mesh(2, 1))
    assert resp.num_hits == 1
    assert resp.partial_hits[0].split_id == "a"
