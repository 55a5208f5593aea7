"""Native CPU comparator (native/leafbench.cpp) vs the engine.

The benchmark's honesty rests on the native denominator computing the
SAME answer as the device path; the bench drops the denominator on a
count mismatch, so these tests prove the agreement holds — including the
boolean AND/OR + timestamp-range shape (c2).
"""

import pytest

from quickwit_tpu.native import load_leafbench


def _c2_style_request():
    from quickwit_tpu.index.synthetic import body_term
    from quickwit_tpu.query.ast import Bool, Range, RangeBound, Term
    from quickwit_tpu.search.models import SearchRequest

    day_us = 86400 * 1_000_000
    t0_us = 1_600_000_000 * 1_000_000
    return SearchRequest(
        index_ids=["hdfs-logs"],
        query_ast=Bool(
            must=(Term("severity_text", "ERROR"),),
            should=(Term("body", body_term(3)),
                    Term("body", body_term(7))),
            filter=(Range("timestamp",
                          lower=RangeBound(t0_us + day_us, True),
                          upper=RangeBound(t0_us + 4 * day_us, False)),),
        ),
        max_hits=100,
    )


def test_leaf_bool_range_agrees_with_engine():
    lib = load_leafbench()
    if lib is None:
        pytest.skip("native toolchain unavailable")
    import bench
    from quickwit_tpu.index.synthetic import HDFS_MAPPER
    from quickwit_tpu.search.leaf import (
        leaf_search_single_split, prepare_single_split,
    )

    request = _c2_style_request()
    reader = bench._hdfs_reader(5000)
    resp = leaf_search_single_split(request, HDFS_MAPPER, reader, "bench")
    assert resp.num_hits > 0, "empty c2 window: corpus shape changed"
    plan, _, _ = prepare_single_split(request, HDFS_MAPPER, reader, "bench")
    # non-None means the comparator's count matched the engine's exactly
    # (the function drops the denominator on ANY disagreement)
    stats = bench._native_cpu_bool_range(plan, request, int(resp.num_hits),
                                         iters=3)
    assert stats is not None, \
        "native bool+range comparator disagreed with the engine"
    assert stats["native_cpu_ms"] >= 0


def test_leaf_bool_range_rejects_foreign_shapes():
    lib = load_leafbench()
    if lib is None:
        pytest.skip("native toolchain unavailable")
    import bench
    from quickwit_tpu.index.synthetic import HDFS_MAPPER
    from quickwit_tpu.query.ast import Term
    from quickwit_tpu.search.leaf import prepare_single_split
    from quickwit_tpu.search.models import SearchRequest

    # a plain term query lowers to posting space, not PBool: the bool
    # comparator must decline it (leaf_term_aggs owns that shape)
    request = SearchRequest(index_ids=["hdfs-logs"],
                            query_ast=Term("severity_text", "ERROR"),
                            max_hits=10)
    reader = bench._hdfs_reader(5000)
    plan, _, _ = prepare_single_split(request, HDFS_MAPPER, reader, "bench")
    assert bench._native_cpu_bool_range(plan, request, 0, iters=1) is None
