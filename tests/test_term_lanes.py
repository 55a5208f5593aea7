"""Dense terms as resident per-doc tf lanes (search/plan.py::PTermLane).

Every case runs twice over one split: once as lowered (a term with
df * TERM_LANE_DF_RATIO >= num_docs reads its lane) and once with the
crossing forced above every df (each term keeps its postings). Counts,
buckets, hit ids and their order must agree exactly, `_score` within one
ulp. Then the shapes that keep postings, and the two lowering counters.
"""

from contextlib import contextmanager

import jax
import numpy as np
import pytest

from quickwit_tpu.common.uri import Uri
from quickwit_tpu.index import SplitReader, SplitWriter
from quickwit_tpu.index.format import DOC_PAD
from quickwit_tpu.models import DocMapper, FieldMapping, FieldType
from quickwit_tpu.observability.metrics import (
    PLAN_TERM_LANES_TOTAL, PLAN_TERM_POSTINGS_TOTAL,
)
from quickwit_tpu.query.aggregations import DateHistogramAgg, TermsAgg
from quickwit_tpu.query.ast import Bool, Range, RangeBound, Term
from quickwit_tpu.search import executor
from quickwit_tpu.search import plan as plan_mod
from quickwit_tpu.search.chunkexec import execute_plan_chunked
from quickwit_tpu.search.plan import (
    PBool, PPostings, PTermLane, lower_request,
)
from quickwit_tpu.storage import RamStorage

MAPPER = DocMapper(
    field_mappings=[
        FieldMapping("timestamp", FieldType.DATETIME, fast=True,
                     input_formats=("unix_timestamp",)),
        FieldMapping("severity_text", FieldType.TEXT, tokenizer="raw",
                     fast=True),
        FieldMapping("tenant_id", FieldType.U64, fast=True),
        FieldMapping("body", FieldType.TEXT),
    ],
    timestamp_field="timestamp",
    default_search_fields=("body",),
)
SEVERITIES = ("DEBUG", "INFO", "WARN", "ERROR")
NUM_DOCS = 2100   # pads to 3 * DOC_PAD: three dense chunks
T0 = 1_700_000_000
# doc 0 repeats "gamma" past a byte: that term's lane widens to uint16
GAMMA_TF = 300


def _docs():
    rng = np.random.RandomState(11)
    docs = []
    for i in range(NUM_DOCS):
        body = (["alpha"] * int(rng.randint(0, 3))
                + ["beta"] * int(rng.randint(0, 2))
                + ["gamma"] * (GAMMA_TF if i == 0 else int(i % 4 == 0))
                # sparse: 20 docs, 20 * 32 < NUM_DOCS
                + ["rare"] * int(i % 105 == 7)
                + ["filler%d" % int(rng.randint(0, 5))])
        docs.append({
            "timestamp": T0 + i * 60,
            "severity_text": SEVERITIES[int(rng.randint(0, 4))],
            "tenant_id": int(rng.randint(0, 4)),
            "body": " ".join(body),
        })
    return docs


@pytest.fixture(scope="module")
def reader():
    writer = SplitWriter(MAPPER)
    for doc in _docs():
        writer.add_json_doc(doc)
    storage = RamStorage(Uri.parse("ram:///term_lanes"))
    storage.put("lanes.split", writer.finish())
    return SplitReader(storage, "lanes.split")


@contextmanager
def _postings_only():
    """The crossing forced above every term's df (test-only)."""
    saved = plan_mod.TERM_LANE_DF_RATIO
    plan_mod.TERM_LANE_DF_RATIO = 0
    try:
        yield
    finally:
        plan_mod.TERM_LANE_DF_RATIO = saved


def _window(lo_min=30, hi_min=1900):
    return Range("timestamp",
                 lower=RangeBound((T0 + lo_min * 60) * 10**6, True),
                 upper=RangeBound((T0 + hi_min * 60) * 10**6, False))


def _nodes(node):
    if isinstance(node, PBool):
        for child in (*node.must, *node.must_not, *node.should,
                      *node.filter):
            yield from _nodes(child)
    else:
        yield node


def _lower(reader, query, lanes: bool, **kw):
    if lanes:
        plan = lower_request(query, MAPPER, reader, kw.pop("aggs", []), **kw)
    else:
        with _postings_only():
            plan = lower_request(query, MAPPER, reader, kw.pop("aggs", []),
                                 **kw)
    kinds = {type(n) for n in _nodes(plan.root)}
    # the comparison is not vacuous: one side reads lanes, the other none
    assert (PTermLane in kinds) == lanes, kinds
    return plan


def _run(plan, k):
    return executor.execute_plan(plan, k, list(plan.arrays))


def _assert_answers_alike(got, want):
    assert int(got["count"]) == int(want["count"])
    np.testing.assert_array_equal(np.asarray(got["doc_ids"]),
                                  np.asarray(want["doc_ids"]))
    np.testing.assert_array_max_ulp(
        np.asarray(got["scores"], np.float32),
        np.asarray(want["scores"], np.float32), maxulp=1)
    for key in ("sort_values", "sort_values2"):
        a, b = got[key], want[key]
        if a is None or b is None:
            assert a is None and b is None, key
            continue
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        finite = np.isfinite(b)
        np.testing.assert_array_equal(np.isfinite(a), finite, err_msg=key)
        np.testing.assert_array_equal(a[~finite], b[~finite], err_msg=key)
        np.testing.assert_array_max_ulp(a[finite], b[finite], maxulp=1)
    got_aggs = jax.tree_util.tree_leaves(got["aggs"])
    want_aggs = jax.tree_util.tree_leaves(want["aggs"])
    assert len(got_aggs) == len(want_aggs)
    for a, b in zip(got_aggs, want_aggs):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _aggs():
    return [DateHistogramAgg(name="per_hour", field="timestamp",
                             interval_micros=3_600 * 10**6),
            TermsAgg(name="sev", field="severity_text", size=10)]


# --- one query, lane against postings ----------------------------------------

_QUERIES = {
    "must": (Bool(must=(Term("severity_text", "ERROR"),
                        Term("body", "alpha")),
                  filter=(_window(),)), 10, {}),
    "should": (Bool(should=(Term("body", "alpha"), Term("body", "beta")),
                    filter=(_window(),)), 25, {}),
    "filter": (Bool(must=(Term("body", "beta"),),
                    filter=(Term("severity_text", "WARN"), _window())),
               10, {}),
    "must_not": (Bool(must=(Term("body", "alpha"),),
                      must_not=(Term("severity_text", "DEBUG"),
                                Term("body", "beta"))), 10, {}),
    "minimum_should_match": (Bool(should=(Term("body", "alpha"),
                                          Term("body", "beta"),
                                          Term("body", "gamma")),
                                  minimum_should_match=2), 40, {}),
    "aggs_count_only": (Bool(must=(Term("severity_text", "ERROR"),),
                             filter=(_window(),)), 0, {"aggs": _aggs()}),
    "top10_aggs": (Bool(must=(Term("severity_text", "ERROR"),),
                        should=(Term("body", "alpha"),),
                        filter=(_window(),)), 10, {"aggs": _aggs()}),
    "timestamp_sort": (Bool(must=(Term("severity_text", "INFO"),),
                            filter=(_window(),)), 10,
                       {"sort_field": "timestamp", "sort_order": "desc"}),
    "uint16_lane": (Bool(should=(Term("body", "gamma"),
                                 Term("body", "beta"))), 20, {}),
    "absent_term": (Bool(should=(Term("body", "alpha"),
                                 Term("body", "nosuchterm")),
                         filter=(_window(),)), 10, {}),
}


def _query_case(name):
    def case(reader):
        query, k, kw = _QUERIES[name]
        lane = _lower(reader, query, True, **dict(kw))
        post = _lower(reader, query, False, **dict(kw))
        if name == "uint16_lane":
            dtypes = {lane.arrays[n.lane_slot].dtype
                      for n in _nodes(lane.root) if isinstance(n, PTermLane)}
            assert np.dtype(np.uint16) in dtypes, dtypes
        return [(_run(lane, k), _run(post, k))]
    return case


# --- the programs that share the evaluator -----------------------------------

def _mask_fill_case(reader):
    query = Bool(must=(Term("severity_text", "ERROR"),),
                 should=(Term("body", "alpha"),), minimum_should_match=1,
                 filter=(_window(),))
    lane = _lower(reader, query, True, sort_field="timestamp")
    post = _lower(reader, query, False, sort_field="timestamp")
    lane_mask, _ = executor.compute_packed_mask(
        lane, jax.device_put(list(lane.arrays)))
    post_mask, _ = executor.compute_packed_mask(
        post, jax.device_put(list(post.arrays)))
    assert lane_mask.dtype == np.uint8 and lane_mask.any()
    np.testing.assert_array_equal(lane_mask, post_mask)
    return []


def _stacked_case(reader):
    """A group of four distinct severities in one stacked program: each
    lane's answer against its term's postings run solo (posting lists of
    different lengths could not have stacked at all)."""
    queries = [Bool(must=(Term("severity_text", sev),),
                    should=(Term("body", "alpha"),),
                    filter=(_window(),)) for sev in SEVERITIES]
    lanes = [_lower(reader, q, True) for q in queries]
    assert len({p.structure_digest(10) for p in lanes}) == 1
    _shared, stacked_slots = executor.stacked_slot_split(lanes)
    assert stacked_slots, "distinct terms must stack their lanes"
    stacked = executor.readback_plan_stacked(executor.dispatch_plan_stacked(
        lanes, 10, [jax.device_put(list(p.arrays)) for p in lanes]))
    return [(got, _run(_lower(reader, q, False), 10))
            for got, q in zip(stacked, queries)]


def _chunked_case(reader):
    query = Bool(must=(Term("severity_text", "WARN"),),
                 should=(Term("body", "alpha"), Term("body", "gamma")),
                 filter=(_window(),))
    lane = _lower(reader, query, True)
    assert plan_mod.chunk_slot_plan(lane) is not None
    assert lane.num_docs_padded == 3 * DOC_PAD
    chunked = execute_plan_chunked(lane, 10, list(lane.arrays),
                                   span=DOC_PAD)
    assert chunked is not None, "the lane plan refused to chunk"
    return [(chunked, _run(_lower(reader, query, False), 10))]


CASES = {name: _query_case(name) for name in _QUERIES}
CASES.update({"mask_fill": _mask_fill_case, "stacked_group_of_4":
              _stacked_case, "dense_chunks_3": _chunked_case})


@pytest.mark.parametrize("case", sorted(CASES))
def test_lane_answers_as_postings(reader, case):
    for got, want in CASES[case](reader):
        _assert_answers_alike(got, want)


# --- what keeps postings ------------------------------------------------------

def _batch_overrides():
    return {"histograms": {}, "terms_dicts": {}, "terms_cards": {},
            "terms_keys": {}}


@pytest.mark.parametrize("shape", ["sparse_term", "lone_term_root",
                                   "lone_full_text_root", "batch_overrides"])
def test_shapes_that_keep_postings(reader, shape):
    if shape == "sparse_term":
        plan = lower_request(Bool(must=(Term("body", "rare"),),
                                  filter=(_window(),)), MAPPER, reader, [])
        assert reader.lookup_term("body", "rare").df * 32 < NUM_DOCS
    elif shape == "lone_term_root":
        # dense, but the root alone: the posting-space path serves it
        plan = lower_request(Term("body", "alpha"), MAPPER, reader, [])
        assert isinstance(plan.root, PPostings)
        assert executor._posting_space_eligible(plan)
    elif shape == "lone_full_text_root":
        # not a boolean query, no time window, no search_after: a root that
        # may be one term keeps postings, here two tokens of it
        from quickwit_tpu.query.ast import FullText
        plan = lower_request(FullText("body", "alpha beta", "and"),
                             MAPPER, reader, [])
    else:
        plan = lower_request(Bool(must=(Term("severity_text", "ERROR"),),
                                  filter=(_window(),)), MAPPER, reader, [],
                             batch_overrides=_batch_overrides())
    kinds = {type(n) for n in _nodes(plan.root)}
    assert PPostings in kinds and PTermLane not in kinds


def test_lane_replaces_postings_and_is_built_once(reader):
    query = Bool(must=(Term("severity_text", "ERROR"),),
                 filter=(_window(),))
    first = lower_request(query, MAPPER, reader, [])
    second = lower_request(query, MAPPER, reader, [])
    info = reader.lookup_term("severity_text", "ERROR")
    key = f"lane.severity_text.{info.ordinal}"
    slot = first.array_keys.index(key)
    assert not any(k.startswith("post.") for k in first.array_keys)
    # memoized on the reader: the very same host array on every lowering
    assert second.arrays[second.array_keys.index(key)] is first.arrays[slot]
    lane = first.arrays[slot]
    ids, tfs = reader.postings("severity_text", info)
    real = ids < reader.num_docs
    assert lane.dtype == np.uint8 and lane.shape == (reader.num_docs_padded,)
    assert np.count_nonzero(lane) == info.df
    np.testing.assert_array_equal(lane[ids[real]], tfs[real])


def test_counters_count_terms_per_lowering(reader):
    lanes0, posts0 = PLAN_TERM_LANES_TOTAL.get(), PLAN_TERM_POSTINGS_TOTAL.get()
    lower_request(Bool(must=(Term("severity_text", "ERROR"),),
                       should=(Term("body", "alpha"), Term("body", "rare"),
                               Term("body", "nosuchterm")),
                       filter=(_window(),)), MAPPER, reader, [])
    # two dense terms, one sparse, one absent (neither)
    assert PLAN_TERM_LANES_TOTAL.get() - lanes0 == 2
    assert PLAN_TERM_POSTINGS_TOTAL.get() - posts0 == 1
    # a lone-term root keeps its postings
    lower_request(Term("body", "alpha"), MAPPER, reader, [])
    assert PLAN_TERM_LANES_TOTAL.get() - lanes0 == 2
    assert PLAN_TERM_POSTINGS_TOTAL.get() - posts0 == 2
    # a time window makes the same root a boolean: its term is a lane
    lower_request(Term("body", "alpha"), MAPPER, reader, [],
                  start_timestamp=T0 * 10**6)
    assert PLAN_TERM_LANES_TOTAL.get() - lanes0 == 3
    assert PLAN_TERM_POSTINGS_TOTAL.get() - posts0 == 2
