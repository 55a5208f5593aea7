"""Multi-query single-dispatch executor: B same-structure queries vmapped
into ONE XLA program with ONE packed readback must match B independent
single dispatches exactly.

Motivation: each dispatch round costs a fixed host-side overhead
regardless of program content, while work inside one dispatch runs at
device speed — the same
reason the reference batches leaf requests per node
(`quickwit-search/src/leaf.rs:81`)."""

import numpy as np
import pytest

from quickwit_tpu.common.uri import Uri
from quickwit_tpu.index import SplitReader, SplitWriter
from quickwit_tpu.models import DocMapper, FieldMapping, FieldType
from quickwit_tpu.query.ast import Range, RangeBound, Term
from quickwit_tpu.search import SearchRequest
from quickwit_tpu.search import executor as ex
from quickwit_tpu.search.leaf import prepare_single_split
from quickwit_tpu.storage import RamStorage

MAPPER = DocMapper(
    field_mappings=[
        FieldMapping("ts", FieldType.DATETIME, fast=True,
                     input_formats=("unix_timestamp",)),
        FieldMapping("sev", FieldType.TEXT, tokenizer="raw", fast=True),
        FieldMapping("body", FieldType.TEXT),
    ],
    timestamp_field="ts", default_search_fields=("body",))

NUM_DOCS = 400


@pytest.fixture(scope="module")
def reader():
    rng = np.random.RandomState(3)
    writer = SplitWriter(MAPPER)
    for i in range(NUM_DOCS):
        writer.add_json_doc({
            "ts": 1_600_000_000 + i * 60,
            "sev": ["INFO", "WARN", "ERROR"][int(rng.randint(0, 3))],
            "body": f"msg term{int(rng.randint(0, 6)):02d}",
        })
    storage = RamStorage(Uri.parse("ram:///multidispatch"))
    storage.put("s.split", writer.finish())
    return SplitReader(storage, "s.split")


def _range_request(lo_s: int, hi_s: int) -> SearchRequest:
    return SearchRequest(
        index_ids=["t"], max_hits=5,
        query_ast=Range("ts",
                        lower=RangeBound(lo_s * 1_000_000, True),
                        upper=RangeBound(hi_s * 1_000_000, False)),
        aggs={"per_hour": {"date_histogram": {"field": "ts",
                                              "fixed_interval": "1h"}}})


def _result_tuple(res: dict):
    return (res["count"],
            tuple(np.asarray(res["sort_values"]).tolist()),
            tuple(np.asarray(res["doc_ids"]).tolist()),
            tuple(np.asarray(res["aggs"][0]["counts"]).tolist()))


def test_multi_dispatch_matches_singles(reader):
    """4 range queries with different bounds (same structure) in one
    dispatch == 4 independent dispatches."""
    windows = [(1_600_000_000, 1_600_003_600),
               (1_600_003_600, 1_600_012_000),
               (1_600_000_000, 1_600_024_000),
               (1_600_005_000, 1_600_006_000)]
    plans = []
    for lo, hi in windows:
        request = _range_request(lo, hi)
        plan, device_arrays, _ = prepare_single_split(
            request, MAPPER, reader, "s")
        plans.append((request, plan, device_arrays))

    # all four lower to the same structure on the same split
    base_sig = plans[0][1].signature(5)
    assert all(p.signature(5) == base_sig for _, p, _ in plans)

    singles = [ex.execute_plan(plan, 5, arrs)
               for _, plan, arrs in plans]

    plan0, arrs0 = plans[0][1], plans[0][2]
    scalar_sets = [p.scalars for _, p, _ in plans]
    batch = ex.readback_plan_multi(
        ex.dispatch_plan_multi(plan0, 5, arrs0, scalar_sets))

    assert len(batch) == 4
    for single, lane in zip(singles, batch):
        assert _result_tuple(single) == _result_tuple(lane)
    # the windows genuinely differ (the test would be vacuous otherwise)
    counts = {lane["count"] for lane in batch}
    assert len(counts) >= 3


def test_multi_dispatch_identical_queries(reader):
    """B identical queries: every lane equals the single result (the
    serving batcher's common case: concurrent same-shape queries)."""
    request = SearchRequest(index_ids=["t"], max_hits=3,
                            query_ast=Term("sev", "ERROR"))
    plan, arrs, _ = prepare_single_split(request, MAPPER, reader, "s")
    single = ex.execute_plan(plan, 3, arrs)
    batch = ex.readback_plan_multi(
        ex.dispatch_plan_multi(plan, 3, arrs, [plan.scalars] * 6))
    assert len(batch) == 6
    for lane in batch:
        assert _result_tuple_hits(lane) == _result_tuple_hits(single)


def _result_tuple_hits(res: dict):
    return (res["count"],
            tuple(np.asarray(res["sort_values"]).tolist()),
            tuple(np.asarray(res["doc_ids"]).tolist()),
            tuple(np.asarray(res["scores"]).tolist()))


def test_multi_dispatch_agg_only(reader):
    """k=0 (agg-only) batched path: empty hit arrays, exact bucket parity."""
    windows = [(1_600_000_000, 1_600_010_000),
               (1_600_010_000, 1_600_020_000)]
    plans = []
    for lo, hi in windows:
        request = _range_request(lo, hi)
        request = SearchRequest(
            index_ids=["t"], max_hits=0, query_ast=request.query_ast,
            aggs=request.aggs)
        plan, arrs, _ = prepare_single_split(request, MAPPER, reader, "s")
        plans.append((plan, arrs))
    singles = [ex.execute_plan(plan, 0, arrs) for plan, arrs in plans]
    plan0, arrs0 = plans[0]
    batch = ex.readback_plan_multi(ex.dispatch_plan_multi(
        plan0, 0, arrs0, [p.scalars for p, _ in plans]))
    for single, lane in zip(singles, batch):
        assert single["count"] == lane["count"]
        np.testing.assert_array_equal(
            np.asarray(single["aggs"][0]["counts"]),
            np.asarray(lane["aggs"][0]["counts"]))
