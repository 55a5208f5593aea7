"""Frame-of-reference packed columns + block-sparse predicates (format v2).

Property: a packed/zonemapped split and a raw full-width split built from
the SAME corpus are indistinguishable through the whole search surface —
hits, exact sort values, counts, aggregation buckets — across dtypes
(i64 with negatives, u64, f64, datetime micros), null masks, and format
versions (v1 splits stay searchable). Plus the tentpole's byte claim:
a c2-style bool+range plan stages >= 2x fewer column bytes than the
raw-column path (valid on CPU fallback — staged bytes are host-visible).
"""

import json
import os

import numpy as np
import pytest

from quickwit_tpu.common.uri import Uri
from quickwit_tpu.index import SplitReader, SplitWriter
from quickwit_tpu.index import format as split_format
from quickwit_tpu.index.format import SplitFileBuilder, SplitFooter
from quickwit_tpu.index.writer import _column_zonemaps, _pack_numeric
from quickwit_tpu.models import DocMapper, FieldMapping, FieldType
from quickwit_tpu.query.ast import Bool, MatchAll, Range, RangeBound, Term
from quickwit_tpu.search import (
    SearchRequest, SortField, leaf_search_single_split,
)
from quickwit_tpu.search.plan import lower_request
from quickwit_tpu.storage import RamStorage

NUM_DOCS = 1300  # crosses DOC_PAD -> padded 2048, several zonemap blocks
T0 = 1_600_000_000


def corpus():
    rng = np.random.RandomState(11)
    docs = []
    for i in range(NUM_DOCS):
        d = {
            "timestamp": T0 + i * 60,                  # minute cadence
            "tenant_id": int(rng.randint(0, 7)),       # u64, packs to u8
            "severity_text": ["INFO", "WARN", "ERROR"][i % 3],
            "latency": float(rng.gamma(2.0, 50.0)),    # f64, never packed
            "shard": 42,                               # all-equal column
        }
        if i % 13 != 0:
            d["code"] = int(rng.randint(-500, 500))    # negatives + nulls
        docs.append(d)
    return docs


def mapper():
    return DocMapper(
        field_mappings=[
            FieldMapping("timestamp", FieldType.DATETIME, fast=True,
                         input_formats=("unix_timestamp",)),
            FieldMapping("tenant_id", FieldType.U64, fast=True),
            FieldMapping("severity_text", FieldType.TEXT, tokenizer="raw",
                         fast=True),
            FieldMapping("latency", FieldType.F64, fast=True),
            FieldMapping("shard", FieldType.I64, fast=True),
            FieldMapping("code", FieldType.I64, fast=True),
        ],
        timestamp_field="timestamp",
    )


DOCS = corpus()
MAPPER = mapper()


def build_reader(packed: bool, name: str = "s.split") -> SplitReader:
    prev = os.environ.get("QW_DISABLE_PACKED")
    os.environ["QW_DISABLE_PACKED"] = "0" if packed else "1"
    try:
        writer = SplitWriter(MAPPER)
        for doc in DOCS:
            writer.add_json_doc(doc)
        storage = RamStorage(Uri.parse("ram:///packedcols"))
        storage.put(name, writer.finish())
        return SplitReader(storage, name)
    finally:
        if prev is None:
            os.environ.pop("QW_DISABLE_PACKED", None)
        else:
            os.environ["QW_DISABLE_PACKED"] = prev


@pytest.fixture(scope="module")
def packed_reader():
    return build_reader(packed=True)


@pytest.fixture(scope="module")
def raw_reader():
    return build_reader(packed=False)


def run(reader, **kwargs):
    defaults = dict(index_ids=["t"], query_ast=MatchAll(), max_hits=20)
    defaults.update(kwargs)
    return leaf_search_single_split(
        SearchRequest(**defaults), MAPPER, reader, "split-0")


def assert_same_response(a, b):
    assert a.num_hits == b.num_hits
    assert [(h.doc_id, h.raw_sort_value, h.raw_sort_value2)
            for h in a.partial_hits] == \
           [(h.doc_id, h.raw_sort_value, h.raw_sort_value2)
            for h in b.partial_hits]
    assert json.dumps(a.intermediate_aggs, sort_keys=True, default=repr) == \
        json.dumps(b.intermediate_aggs, sort_keys=True, default=repr)


# --- packing decisions ------------------------------------------------------

def test_width_selection_and_scale(packed_reader):
    ts = packed_reader.column_packing("timestamp")
    # minute-quantized micros: GCD collapses to 60s steps -> u16 lanes
    assert ts["for_scale"] == 60_000_000
    assert ts["bit_width"] == 16
    assert ts["for_min"] == T0 * 1_000_000
    assert packed_reader.column_packed("timestamp")[0].dtype == np.uint16

    tenant = packed_reader.column_packing("tenant_id")
    assert tenant["bit_width"] == 8

    shard = packed_reader.column_packing("shard")  # all-equal -> u8 zeros
    assert shard["bit_width"] == 8
    assert not packed_reader.column_packed("shard")[0].any()

    assert packed_reader.column_packing("latency") is None  # f64 never packs
    assert packed_reader.has_array("col.latency.values")


def test_high_dynamic_range_falls_back_raw():
    vals = np.array([0, 1, (1 << 62) + 5], dtype=np.int64)
    assert _pack_numeric(FieldType.I64, vals) is None   # subtract overflow
    vals = np.array([0, 3, (1 << 40)], dtype=np.int64)  # span_scaled > i32
    assert _pack_numeric(FieldType.I64, vals) is None


def test_zonemaps_present_and_inverted_on_empty_blocks(packed_reader):
    zmin, zmax = packed_reader.column_zonemaps("code")
    padded = packed_reader.num_docs_padded
    assert zmin.shape[0] == padded // split_format.ZONEMAP_BLOCK
    # the pad-tail blocks hold no present docs: inverted envelope
    assert zmin[-1] > zmax[-1]
    # real blocks are ordered envelopes
    assert (zmin[:2] <= zmax[:2]).all()


def test_reconstruction_bit_identity(packed_reader, raw_reader):
    for field in ("timestamp", "tenant_id", "code", "shard", "latency"):
        pv, pp = packed_reader.column_values(field)
        rv, rp = raw_reader.column_values(field)
        assert pv.dtype == rv.dtype
        np.testing.assert_array_equal(pv, rv)  # incl. absent lanes == 0
        np.testing.assert_array_equal(pp, rp)


# --- equivalence suite ------------------------------------------------------

RANGE_CASES = [
    # (field, lower (value, incl), upper (value, incl)) in column domain
    ("timestamp", ((T0 + 100 * 60) * 10**6, True),
     ((T0 + 900 * 60) * 10**6, False)),
    ("timestamp", ((T0 + 100 * 60) * 10**6 + 1, True),   # off-lattice bounds
     ((T0 + 900 * 60) * 10**6 - 1, True)),
    ("timestamp", None, ((T0 + 5 * 60) * 10**6, True)),  # one-sided
    ("timestamp", ((T0 + NUM_DOCS * 60) * 10**6, True), None),  # empty
    ("code", (-120, False), (333, True)),
    ("code", (-10**9, True), (10**9, True)),             # clamps to frame
    ("tenant_id", (2, True), (4, False)),
    ("shard", (42, True), (42, True)),
    ("shard", (43, True), None),                         # nothing matches
    ("latency", (30.0, True), (200.0, False)),           # raw f64 both sides
]


@pytest.mark.parametrize("field,lo,hi", RANGE_CASES)
def test_range_equivalence(packed_reader, raw_reader, field, lo, hi):
    q = Range(field,
              lower=RangeBound(lo[0], lo[1]) if lo else None,
              upper=RangeBound(hi[0], hi[1]) if hi else None)
    a = run(packed_reader, query_ast=q, max_hits=1000)
    b = run(raw_reader, query_ast=q, max_hits=1000)
    assert_same_response(a, b)
    # and against brute force over the corpus
    def keep(doc):
        v = doc.get(field)
        if v is None:
            return False
        if field == "timestamp":
            v *= 10**6
        ok = True
        if lo:
            ok &= v >= lo[0] if lo[1] else v > lo[0]
        if hi:
            ok &= v <= hi[0] if hi[1] else v < hi[0]
        return ok
    assert a.num_hits == sum(1 for d in DOCS if keep(d))


@pytest.mark.parametrize("field,order", [
    ("code", "asc"), ("code", "desc"),
    ("timestamp", "desc"), ("tenant_id", "asc"),
])
def test_sort_equivalence(packed_reader, raw_reader, field, order):
    kw = dict(query_ast=Term("severity_text", "ERROR"), max_hits=25,
              sort_fields=[SortField(field, order)])
    assert_same_response(run(packed_reader, **kw), run(raw_reader, **kw))


def test_two_key_sort_equivalence(packed_reader, raw_reader):
    kw = dict(max_hits=30,
              sort_fields=[SortField("tenant_id", "desc"),
                           SortField("code", "asc")])
    assert_same_response(run(packed_reader, **kw), run(raw_reader, **kw))


AGGS = {
    "per_hour": {
        "date_histogram": {"field": "timestamp", "fixed_interval": "1h"},
        "aggs": {"avg_code": {"avg": {"field": "code"}},
                 "tenants": {"cardinality": {"field": "tenant_id"}}},
    },
    "code_stats": {"extended_stats": {"field": "code"}},
    "tenant_terms": {"terms": {"field": "tenant_id"}},
    "lat_ranges": {"range": {"field": "code",
                             "ranges": [{"to": 0}, {"from": 0, "to": 250},
                                        {"from": 250}]}},
}


def test_agg_equivalence(packed_reader, raw_reader):
    kw = dict(query_ast=Bool(must_not=(Term("severity_text", "WARN"),)),
              max_hits=0, aggs=AGGS)
    assert_same_response(run(packed_reader, **kw), run(raw_reader, **kw))


def test_bool_range_equivalence(packed_reader, raw_reader):
    q = Bool(
        must=(Term("severity_text", "ERROR"),),
        filter=(Range("timestamp",
                      lower=RangeBound((T0 + 50 * 60) * 10**6, True),
                      upper=RangeBound((T0 + 1000 * 60) * 10**6, False)),
                Range("tenant_id", lower=RangeBound(1, True),
                      upper=RangeBound(5, False))),
    )
    kw = dict(query_ast=q, max_hits=100,
              sort_fields=[SortField("timestamp", "desc")], aggs=AGGS)
    assert_same_response(run(packed_reader, **kw), run(raw_reader, **kw))


# --- format versioning ------------------------------------------------------

def build_v1_reader() -> SplitReader:
    """A faithful v1 split: raw full-width columns, NO zonemap arrays,
    format_version 1 in the footer — what pre-v2 writers produced."""
    prev_add = SplitFileBuilder.add_array

    def add_skipping_zonemaps(self, name, array):
        if name.endswith((".zmin", ".zmax")):
            return
        prev_add(self, name, array)

    prev_ver = split_format.FORMAT_VERSION
    prev_env = os.environ.get("QW_DISABLE_PACKED")
    os.environ["QW_DISABLE_PACKED"] = "1"
    SplitFileBuilder.add_array = add_skipping_zonemaps
    split_format.FORMAT_VERSION = 1
    try:
        writer = SplitWriter(MAPPER)
        for doc in DOCS:
            writer.add_json_doc(doc)
        storage = RamStorage(Uri.parse("ram:///v1"))
        storage.put("v1.split", writer.finish())
    finally:
        SplitFileBuilder.add_array = prev_add
        split_format.FORMAT_VERSION = prev_ver
        if prev_env is None:
            os.environ.pop("QW_DISABLE_PACKED", None)
        else:
            os.environ["QW_DISABLE_PACKED"] = prev_env
    return SplitReader(storage, "v1.split")


def test_v1_split_still_searchable(packed_reader):
    r1 = build_v1_reader()
    assert r1.column_packing("timestamp") is None
    assert r1.column_zonemaps("timestamp") is None
    q = Bool(must=(Term("severity_text", "ERROR"),),
             filter=(Range("code", lower=RangeBound(-100, True),
                           upper=RangeBound(400, False)),))
    kw = dict(query_ast=q, max_hits=50,
              sort_fields=[SortField("code", "desc")], aggs=AGGS)
    assert_same_response(run(r1, **kw), run(packed_reader, **kw))


def test_unsupported_format_version_rejected():
    footer = SplitFooter(num_docs=0, num_docs_padded=0, arrays={}, fields={})
    doc = json.loads(footer.to_json_bytes())
    doc["format_version"] = 99
    with pytest.raises(ValueError, match="format version"):
        SplitFooter.from_json_bytes(json.dumps(doc).encode())


# --- the byte claim ---------------------------------------------------------

def c2_style_query():
    return Bool(
        must=(Term("severity_text", "ERROR"),),
        filter=(Range("timestamp",
                      lower=RangeBound((T0 + 60 * 60) * 10**6, True),
                      upper=RangeBound((T0 + 1200 * 60) * 10**6, False)),
                Range("tenant_id", lower=RangeBound(1, True),
                      upper=RangeBound(6, False))),
    )


def test_c2_style_plan_stages_half_the_column_bytes(packed_reader,
                                                    raw_reader):
    """The tentpole's acceptance number: the bool+range plan's
    range-touching columns ship >= 2x fewer bytes to the device than the
    raw-column path. Plan-array nbytes IS what HBM admission pins
    (warmup_device_arrays sums arr.nbytes), so this is the hbm_bytes
    quantity, valid without a TPU."""
    def staged(reader):
        plan = lower_request(c2_style_query(), MAPPER, reader, [],
                             sort_field="_score", sort_order="desc")
        col = sum(a.nbytes for k, a in zip(plan.array_keys, plan.arrays)
                  if k.startswith("col."))
        return col, sum(a.nbytes for a in plan.arrays)

    packed_col, packed_total = staged(packed_reader)
    raw_col, raw_total = staged(raw_reader)
    assert packed_col * 2 <= raw_col, (packed_col, raw_col)
    assert packed_total < raw_total


def test_packed_results_match_on_c2_style_query(packed_reader, raw_reader):
    kw = dict(query_ast=c2_style_query(), max_hits=100)
    assert_same_response(run(packed_reader, **kw), run(raw_reader, **kw))


# --- batch (fanout) ---------------------------------------------------------

def test_batch_over_packed_splits(packed_reader):
    from quickwit_tpu.parallel.fanout import (
        build_batch, execute_batch, make_mesh,
    )
    other = build_reader(packed=True, name="s2.split")
    req = SearchRequest(index_ids=["t"], query_ast=c2_style_query(),
                        max_hits=40,
                        sort_fields=[SortField("timestamp", "desc")])
    batch = build_batch(req, MAPPER, [packed_reader, other], ["s1", "s2"])
    resp = execute_batch(batch, req, make_mesh(2, 1))
    single = run(packed_reader, query_ast=c2_style_query(), max_hits=40,
                 sort_fields=[SortField("timestamp", "desc")])
    assert resp.num_hits == 2 * single.num_hits
    # both splits hold the same corpus: winners interleave pairwise with
    # identical sort values
    assert [h.raw_sort_value for h in resp.partial_hits] == sorted(
        [h.raw_sort_value for h in single.partial_hits] * 2,
        reverse=True)[:40]


def test_batch_rejects_mixed_packings(packed_reader, raw_reader):
    from quickwit_tpu.parallel.fanout import build_batch
    req = SearchRequest(index_ids=["t"], query_ast=c2_style_query(),
                        max_hits=10)
    with pytest.raises(ValueError):
        build_batch(req, MAPPER, [packed_reader, raw_reader], ["s1", "s2"])
