"""Native CPU comparator (native/leafbench.cpp) vs the engine.

A native denominator is honest only if it computes the SAME answer as the
device path; the driver below drops it on a count mismatch, so these tests
prove the agreement holds — including the boolean AND/OR + timestamp-range
shape (c2).
"""

import sys
import time

import pytest

from quickwit_tpu.native import load_leafbench


@pytest.fixture(scope="module")
def hdfs_reader():
    from quickwit_tpu.common.uri import Uri
    from quickwit_tpu.index.reader import SplitReader
    from quickwit_tpu.index.synthetic import synthetic_hdfs_split
    from quickwit_tpu.storage.ram import RamStorage
    storage = RamStorage(Uri.parse("ram:///bench"))
    storage.put("hdfs.split", synthetic_hdfs_split(5000, seed=7))
    return SplitReader(storage, "hdfs.split")


def _native_cpu_bool_range(plan, request, reference_count: int,
                           iters: int) -> "dict | None":
    """Native comparator for the c2 shape (leafbench.cpp leaf_bool_range):
    one scored MUST term AND'ed with an integer range filter, up to two
    scored SHOULD terms on a shared field. Range bounds are fed in the
    column's own on-disk domain (raw values, or scaled deltas for
    FOR-packed columns), so the comparison is domain-invariant. Returns
    p50 ms or None when the plan is outside this shape."""
    import ctypes

    import numpy as np
    from quickwit_tpu.search.plan import PBool, PPostings, PRange, PTermLane

    lib = load_leafbench()
    k = request.start_offset + request.max_hits
    if lib is None or not isinstance(plan.root, PBool) or plan.aggs or k <= 0:
        return None
    node = plan.root
    if (len(node.must) != 1 or node.must_not or len(node.filter) != 1
            or len(node.should) > 2 or node.minimum_should_match):
        return None
    must, rng = node.must[0], node.filter[0]
    shoulds = list(node.should)
    terms = (PPostings, PTermLane)
    if (not isinstance(must, terms) or not must.scoring
            or not isinstance(rng, PRange)):
        return None
    for s in shoulds:
        if not isinstance(s, terms) or not s.scoring:
            return None
    if len(shoulds) == 2 and shoulds[0].norm_slot != shoulds[1].norm_slot:
        return None  # the C++ models ONE shared should field
    for p in [must] + shoulds:
        if (isinstance(p, PPostings)
                and not plan.array_keys[p.ids_slot].startswith("post.")):
            return None  # phrase/precomputed postings: out of scope

    def arr(slot, dt=None):
        a = np.ascontiguousarray(plan.arrays[slot])
        return a.astype(dt, copy=False) if dt is not None else a

    def postings(node):
        """(ids, tfs) as the C++ reads them; a dense term's tf lane is
        turned back into its posting list."""
        if isinstance(node, PPostings):
            return arr(node.ids_slot), arr(node.tfs_slot)
        lane = plan.arrays[node.lane_slot]
        ids = np.flatnonzero(lane).astype(np.int32)
        return ids, lane[ids].astype(np.int32)

    ts_values = arr(rng.values_slot)
    if ts_values.dtype.kind not in "iu" or ts_values.dtype == np.uint64:
        return None  # float ranges / full-width u64: not modeled
    ts_values = ts_values.astype(np.int64, copy=False)
    ts_present = arr(rng.present_slot, np.uint8)

    def bound(slot, default):
        return (int(np.asarray(plan.scalars[slot])) if slot >= 0
                else default)

    lo = bound(rng.lo_slot, -(2 ** 63))
    hi = bound(rng.hi_slot, 2 ** 63 - 1)
    if not rng.lo_incl:
        lo += 1
    if not rng.hi_incl:
        hi -= 1

    must_ids, must_tfs = postings(must)
    must_norms = arr(must.norm_slot, np.int32)
    must_idf = float(np.asarray(plan.scalars[must.idf_slot]))
    must_avg = float(np.asarray(plan.scalars[must.avg_len_slot]))
    empty = np.zeros(0, np.int32)
    s_arrs = [postings(s) for s in shoulds]
    while len(s_arrs) < 2:
        s_arrs.append((empty, empty))
    if shoulds:
        should_norms = arr(shoulds[0].norm_slot, np.int32)
        should_avg = float(np.asarray(plan.scalars[shoulds[0].avg_len_slot]))
    else:
        should_norms = np.zeros(1, np.int32)
        should_avg = 1.0
    s_idfs = [float(np.asarray(plan.scalars[s.idf_slot])) for s in shoulds]
    while len(s_idfs) < 2:
        s_idfs.append(0.0)

    topk_scores = np.zeros(max(k, 1), np.float32)
    topk_docs = np.zeros(max(k, 1), np.int32)
    count_out = np.zeros(1, np.int64)

    def ptr(a, t):
        return a.ctypes.data_as(ctypes.POINTER(t))

    def run_once():
        lib.leaf_bool_range(
            ptr(must_ids, ctypes.c_int32), ptr(must_tfs, ctypes.c_int32),
            ctypes.c_int64(len(must_ids)), ptr(must_norms, ctypes.c_int32),
            ctypes.c_double(must_idf), ctypes.c_double(must_avg),
            ptr(s_arrs[0][0], ctypes.c_int32),
            ptr(s_arrs[0][1], ctypes.c_int32),
            ctypes.c_int64(len(s_arrs[0][0])),
            ptr(s_arrs[1][0], ctypes.c_int32),
            ptr(s_arrs[1][1], ctypes.c_int32),
            ctypes.c_int64(len(s_arrs[1][0])),
            ptr(should_norms, ctypes.c_int32),
            ctypes.c_double(s_idfs[0]), ctypes.c_double(s_idfs[1]),
            ctypes.c_double(should_avg),
            ptr(ts_values, ctypes.c_int64), ptr(ts_present, ctypes.c_uint8),
            ctypes.c_int64(lo), ctypes.c_int64(hi),
            ctypes.c_int64(plan.num_docs), ctypes.c_int32(k),
            ptr(topk_scores, ctypes.c_float), ptr(topk_docs, ctypes.c_int32),
            ptr(count_out, ctypes.c_int64))

    run_once()
    if int(count_out[0]) != reference_count:
        print(f"# native bool+range comparator count mismatch: "
              f"{int(count_out[0])} vs {reference_count} — dropping "
              "denominator", file=sys.stderr)
        return None
    lat = []
    for _ in range(iters):
        t0 = time.monotonic()
        run_once()
        lat.append(time.monotonic() - t0)
    return {"native_cpu_ms": round(sorted(lat)[len(lat) // 2] * 1000, 3)}


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


def test_leaf_bool_range_agrees_with_engine(hdfs_reader):
    lib = load_leafbench()
    if lib is None:
        pytest.skip("native toolchain unavailable")
    from quickwit_tpu.index.synthetic import HDFS_MAPPER
    from quickwit_tpu.search.leaf import (
        leaf_search_single_split, prepare_single_split,
    )

    request = _c2_style_request()
    resp = leaf_search_single_split(request, HDFS_MAPPER, hdfs_reader,
                                    "bench")
    assert resp.num_hits > 0, "empty c2 window: corpus shape changed"
    plan, _, _ = prepare_single_split(request, HDFS_MAPPER, hdfs_reader,
                                      "bench")
    # non-None means the comparator's count matched the engine's exactly
    # (the function drops the denominator on ANY disagreement)
    stats = _native_cpu_bool_range(plan, request, int(resp.num_hits),
                                   iters=3)
    assert stats is not None, \
        "native bool+range comparator disagreed with the engine"
    assert stats["native_cpu_ms"] >= 0


def test_leaf_bool_range_rejects_foreign_shapes(hdfs_reader):
    lib = load_leafbench()
    if lib is None:
        pytest.skip("native toolchain unavailable")
    from quickwit_tpu.index.synthetic import HDFS_MAPPER
    from quickwit_tpu.query.ast import Term
    from quickwit_tpu.search.leaf import prepare_single_split
    from quickwit_tpu.search.models import SearchRequest

    # a plain term query lowers to posting space, not PBool: the bool
    # comparator must decline it (leaf_term_aggs owns that shape)
    request = SearchRequest(index_ids=["hdfs-logs"],
                            query_ast=Term("severity_text", "ERROR"),
                            max_hits=10)
    plan, _, _ = prepare_single_split(request, HDFS_MAPPER, hdfs_reader,
                                      "bench")
    assert _native_cpu_bool_range(plan, request, 0, iters=1) is None
