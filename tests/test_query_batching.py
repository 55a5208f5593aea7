"""Device-side multi-query batching (ROADMAP item 2): N DISTINCT
shape-compatible queries stack into ONE compiled dispatch along a query
axis, and every lane's results are bit-identical to running that query
solo — across sorts, ties, thresholds, search_after markers, aggs, and
all three split format versions. `QW_DISABLE_QBATCH=1` must restore the
convoy-only seed behavior byte for byte, and a rider shed AFTER group
formation must be masked (validity lane zeroed) without a second launch
or a recompile."""

import os
import threading
import time
from contextlib import contextmanager

import numpy as np
import pytest

import jax

from quickwit_tpu.common.deadline import (
    CancellationToken, CancelledQuery, cancel_scope,
)
from quickwit_tpu.common.uri import Uri
from quickwit_tpu.index import SplitReader, SplitWriter
from quickwit_tpu.models import DocMapper, FieldMapping, FieldType
from quickwit_tpu.observability.metrics import (
    QBATCH_GROUPS_TOTAL, QBATCH_INCOMPATIBLE_TOTAL,
    QBATCH_MASKED_RIDERS_TOTAL, QBATCH_QUERIES_PER_DISPATCH,
    QBATCH_SHARED_BYTES_AVOIDED_TOTAL, SEARCH_KERNEL_LAUNCHES_TOTAL,
)
from quickwit_tpu.observability.profile import (
    PHASE_BATCHER_QUEUE, PHASE_QBATCH_GROUP, QueryProfile, profile_scope,
)
from quickwit_tpu.query.ast import MatchAll, Range, RangeBound, Term
from quickwit_tpu.search import SearchRequest, SortField
from quickwit_tpu.search import chunkexec
from quickwit_tpu.search import executor as ex
from quickwit_tpu.search.batcher import (
    QueryBatcher, QueryGroupPlanner, _PriorityLock, qbatch_enabled,
)
from quickwit_tpu.search.leaf import prepare_single_split
from quickwit_tpu.storage import RamStorage

MAPPER = DocMapper(
    field_mappings=[
        FieldMapping("ts", FieldType.DATETIME, fast=True,
                     input_formats=("unix_timestamp",)),
        FieldMapping("sev", FieldType.TEXT, tokenizer="raw", fast=True),
        FieldMapping("tenant", FieldType.U64, fast=True),
        FieldMapping("lat", FieldType.F64, fast=True),
        FieldMapping("body", FieldType.TEXT),
    ],
    timestamp_field="ts", default_search_fields=("body",))

T0 = 1_600_000_000
SEVS = ("INFO", "WARN", "ERROR")


def _docs(n, seed):
    rng = np.random.RandomState(seed)
    for i in range(n):
        yield {
            "ts": T0 + i * 60,
            "sev": SEVS[int(rng.randint(0, 3))],
            "tenant": int(rng.randint(0, 4)),
            # integral latencies: float aggs stay exactly associative, so
            # solo-vs-stacked agg comparisons can demand bit equality
            "lat": float(rng.randint(1, 500)),
            "body": f"m{int(rng.randint(0, 4))}",
        }


@contextmanager
def _writer_env(**kv):
    old = {k: os.environ.get(k) for k in kv}
    os.environ.update({k: str(v) for k, v in kv.items()})
    try:
        yield
    finally:
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def _build_reader(n_docs, seed, name, env=None):
    with _writer_env(**(env or {})):
        writer = SplitWriter(MAPPER)
        for doc in _docs(n_docs, seed):
            writer.add_json_doc(doc)
        data = writer.finish()
    storage = RamStorage(Uri.parse("ram:///qbatch"))
    storage.put(name, data)
    return SplitReader(storage, name)


@pytest.fixture(scope="module")
def reader():
    return _build_reader(300, 11, "v3.split")


@pytest.fixture(scope="module")
def reader_v2():
    return _build_reader(300, 11, "v2.split", env={"QW_DISABLE_IMPACT": "1"})


@pytest.fixture(scope="module")
def reader_v1():
    return _build_reader(300, 11, "v1.split", env={"QW_DISABLE_PACKED": "1"})


@pytest.fixture(scope="module")
def big_reader():
    # large enough that posting chunking spans multiple chunks at a
    # forced span (the group-chunked equivalence tests); seed chosen so
    # all three severity posting lists pad to the same bucket (the
    # shape-compatibility invariant the planner would otherwise enforce)
    return _build_reader(3000, 7, "big.split")


def _prep(rdr, request, split_id="s"):
    plan, arrs, _ = prepare_single_split(request, MAPPER, rdr, split_id)
    return plan, arrs


def _sev_req(sev, **kw):
    return SearchRequest(index_ids=["t"], query_ast=Term("sev", sev), **kw)


def _window_req(lo_s, hi_s, **kw):
    return SearchRequest(
        index_ids=["t"],
        query_ast=Range("ts", lower=RangeBound(lo_s * 1_000_000, True),
                        upper=RangeBound(hi_s * 1_000_000, False)), **kw)


def _assert_same(got, want):
    """Bit-identity between a stacked lane's result dict and its solo
    twin: counts, hit addresses, both sort keys, scores, and every agg
    leaf."""
    assert got is not None and want is not None
    assert int(got["count"]) == int(want["count"])
    for f in ("doc_ids", "sort_values", "sort_values2", "scores"):
        np.testing.assert_array_equal(np.asarray(got[f]), np.asarray(want[f]),
                                      err_msg=f)
    got_aggs = jax.tree_util.tree_leaves(got["aggs"])
    want_aggs = jax.tree_util.tree_leaves(want["aggs"])
    assert len(got_aggs) == len(want_aggs)
    for a, b in zip(got_aggs, want_aggs):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _stack_and_compare(prepped, k, valid=None):
    plans = [p for p, _ in prepped]
    arrays = [a for _, a in prepped]
    solos = [ex.execute_plan(p, k, a) for p, a in prepped]
    stacked = ex.readback_plan_stacked(
        ex.dispatch_plan_stacked(plans, k, arrays, valid=valid))
    assert len(stacked) == len(plans)
    for lane, (got, want) in enumerate(zip(stacked, solos)):
        if valid is not None and not valid[lane]:
            assert got is None
        else:
            _assert_same(got, want)
    return stacked, solos


# --- stacked executor: bit-identity across query shapes ---------------------

def test_stacked_matches_solo_score_sort(reader):
    prepped = [_prep(reader, _sev_req(s, max_hits=10)) for s in SEVS]
    _stack_and_compare(prepped, 10)


def test_stacked_matches_solo_column_sort_asc(reader):
    prepped = [_prep(reader, _window_req(T0 + 600 * i, T0 + 600 * i + 7200,
                                         max_hits=8,
                                         sort_fields=[SortField("ts", "asc")]))
               for i in range(3)]
    _stack_and_compare(prepped, 8)


def test_stacked_matches_solo_column_sort_desc(reader):
    prepped = [_prep(reader, _window_req(T0 + 600 * i, T0 + 600 * i + 7200,
                                         max_hits=8,
                                         sort_fields=[SortField("ts",
                                                                "desc")]))
               for i in range(3)]
    _stack_and_compare(prepped, 8)


def test_stacked_matches_solo_two_key_sort(reader):
    prepped = [_prep(reader, _sev_req(
        s, max_hits=10, sort_fields=[SortField("lat", "desc"),
                                     SortField("ts", "asc")]))
        for s in SEVS]
    _stack_and_compare(prepped, 10)


def test_stacked_tie_breaks_identical_to_solo(reader):
    """tenant has only 4 distinct values over 400 docs — a tenant sort is
    almost all ties, so identical doc_id order proves the stacked top-k's
    tie-breaks are bit-compatible with solo."""
    prepped = [_prep(reader, _window_req(
        T0, T0 + 60 * 400, max_hits=12,
        sort_fields=[SortField("tenant", "desc")]))
        for _ in range(2)] + [_prep(reader, _window_req(
            T0 + 6000, T0 + 60 * 400, max_hits=12,
            sort_fields=[SortField("tenant", "desc")]))]
    _stack_and_compare(prepped, 12)


def test_stacked_matches_solo_search_after(reader):
    """Each lane carries its OWN search_after marker (scalar lane vector):
    pagination cursors stay per-query inside one stacked dispatch."""
    sa = [[(T0 + 60 * (100 + 50 * i)) * 1_000_000, "s", 5 * i]
          for i in range(3)]
    prepped = [_prep(reader, _window_req(
        T0, T0 + 60 * 300, max_hits=6,
        sort_fields=[SortField("ts", "desc")], search_after=sa[i]))
        for i in range(3)]
    _stack_and_compare(prepped, 6)


def test_stacked_matches_solo_aggs(reader):
    aggs = {"per_hour": {
        "date_histogram": {"field": "ts", "fixed_interval": "1h"},
        "aggs": {"lat_avg": {"avg": {"field": "lat"}}}}}
    prepped = [_prep(reader, _sev_req(s, max_hits=5, aggs=aggs))
               for s in SEVS]
    _stack_and_compare(prepped, 5)


def test_stacked_matches_solo_count_only_k0(reader):
    prepped = [_prep(reader, _sev_req(s, max_hits=0,
                                      aggs={"lat_stats": {
                                          "stats": {"field": "lat"}}}))
               for s in SEVS]
    _stack_and_compare(prepped, 0)


def test_stacked_matches_solo_v2_split(reader_v2):
    prepped = [_prep(reader_v2, _sev_req(s, max_hits=10)) for s in SEVS]
    _stack_and_compare(prepped, 10)


def test_stacked_matches_solo_v1_split(reader_v1):
    prepped = [_prep(reader_v1, _sev_req(s, max_hits=10)) for s in SEVS]
    _stack_and_compare(prepped, 10)


# --- stacked executor: masking, bucketing, cache mirror ---------------------

def test_stacked_valid_mask_zeroes_lane_keeps_survivors(reader):
    prepped = [_prep(reader, _sev_req(s, max_hits=10)) for s in SEVS]
    _stack_and_compare(prepped, 10, valid=[True, False, True])


def test_stacked_lane_count_pads_to_bucket(reader):
    prepped = [_prep(reader, _sev_req(s, max_hits=5)) for s in SEVS]
    plans = [p for p, _ in prepped]
    stacked, _ = _stack_and_compare(prepped, 5)
    assert len(stacked) == 3          # surplus pad lanes never surface
    key = ex.stacked_program_cache_key(plans, 5)
    assert key[1] == 4                # 3 lanes bucket to the next pow2
    assert key in ex._STACKED_CACHE


def test_stacked_cache_key_mirror_in_lockstep(reader):
    """`stacked_program_cache_key` is the R1 closure mirror: after a
    dispatch, exactly that key must be present in the live cache."""
    prepped = [_prep(reader, _window_req(T0, T0 + 7200, max_hits=4)),
               _prep(reader, _window_req(T0 + 900, T0 + 9000, max_hits=4))]
    plans = [p for p, _ in prepped]
    ex.readback_plan_stacked(ex.dispatch_plan_stacked(
        plans, 4, [a for _, a in prepped]))
    assert ex.stacked_program_cache_key(plans, 4) in ex._STACKED_CACHE


def test_stacked_slot_split_shares_columns_stacks_postings(reader):
    """sev-term lanes read the same fast columns (shared slots, one
    broadcast buffer) but different posting lists (stacked slots)."""
    plans = [_prep(reader, _sev_req(s, max_hits=5))[0] for s in SEVS]
    shared, stacked = ex.stacked_slot_split(plans)
    assert shared and stacked
    assert sorted(shared + stacked) == list(range(len(plans[0].arrays)))
    keys0 = plans[0].array_keys
    for s in shared:
        assert all(p.array_keys[s] == keys0[s] for p in plans)
    for s in stacked:
        assert any(p.array_keys[s] != keys0[s] for p in plans)


def test_stacked_program_reused_across_groups(reader):
    """A second same-shape group is one launch, zero new compile-cache
    entries — the stacked program is keyed on structure + bucket, never on
    the queries riding it."""
    first = [_prep(reader, _sev_req(s, max_hits=7)) for s in SEVS]
    _stack_and_compare(first, 7)
    cache_size = len(ex._STACKED_CACHE)
    again = [_prep(reader, _sev_req(s, max_hits=7))
             for s in ("ERROR", "INFO", "WARN")]
    launches0 = SEARCH_KERNEL_LAUNCHES_TOTAL.get()
    stacked = ex.readback_plan_stacked(ex.dispatch_plan_stacked(
        [p for p, _ in again], 7, [a for _, a in again]))
    assert SEARCH_KERNEL_LAUNCHES_TOTAL.get() - launches0 == 1
    assert len(ex._STACKED_CACHE) == cache_size
    assert all(r is not None for r in stacked)


# --- grouping rules (QueryGroupPlanner) -------------------------------------

def test_group_key_stacks_distinct_terms_separates_structures(reader):
    plans = [_prep(reader, _sev_req(s, max_hits=5))[0] for s in SEVS]
    keys = {QueryGroupPlanner.key_for(p, 5, "s", True) for p in plans}
    assert len(keys) == 1             # distinct terms, one group
    other = _prep(reader, _window_req(T0, T0 + 7200, max_hits=5))[0]
    assert QueryGroupPlanner.key_for(other, 5, "s", True) not in keys
    # a different split never groups
    assert QueryGroupPlanner.key_for(plans[0], 5, "s2", True) not in keys


def test_group_key_kill_switch_restores_convoy_key(reader):
    """Under QW_DISABLE_QBATCH the key carries the array cache keys again:
    ERROR and INFO (different posting arrays) must NOT share."""
    plans = [_prep(reader, _sev_req(s, max_hits=5))[0]
             for s in ("ERROR", "INFO")]
    k_on = {QueryGroupPlanner.key_for(p, 5, "s", True) for p in plans}
    k_off = {QueryGroupPlanner.key_for(p, 5, "s", False) for p in plans}
    assert len(k_on) == 1 and len(k_off) == 2
    assert k_off == {(p.signature(5), tuple(p.array_keys), "s")
                     for p in plans}


def test_group_key_falls_back_without_structure_digest():
    class BarePlan:
        array_keys = ("x",)
        scalars = ()

        def signature(self, k):
            return ("bare", k)

    key = QueryGroupPlanner.key_for(BarePlan(), 3, "s", True)
    assert key == (("bare", 3), ("x",), "s")


def test_incompatible_metric_reasons(reader):
    plan = _prep(reader, _sev_req("ERROR", max_hits=5))[0]
    key = QueryGroupPlanner.key_for(plan, 5, "s", True)
    other = _prep(reader, _window_req(T0, T0 + 7200, max_hits=5))[0]
    other_key = QueryGroupPlanner.key_for(other, 5, "s", True)
    full0 = QBATCH_INCOMPATIBLE_TOTAL.get(reason="group_full")
    shape0 = QBATCH_INCOMPATIBLE_TOTAL.get(reason="plan_shape")
    # leading a fresh queue while the same key's queue is full
    QueryGroupPlanner.note_reject({key: [object()]}, key, True)
    assert QBATCH_INCOMPATIBLE_TOTAL.get(reason="group_full") == full0 + 1
    # leading a fresh queue while a different-shape group is open on the
    # same split
    QueryGroupPlanner.note_reject({other_key: [object()]}, key, True)
    assert QBATCH_INCOMPATIBLE_TOTAL.get(reason="plan_shape") == shape0 + 1
    # kill switch: no attribution at all
    QueryGroupPlanner.note_reject({key: [object()]}, key, False)
    assert QBATCH_INCOMPATIBLE_TOTAL.get(reason="group_full") == full0 + 1


def test_shared_staging_accounting(reader):
    from quickwit_tpu.search.residency import note_group_shared_staging
    plans = [_prep(reader, _sev_req(s, max_hits=5))[0] for s in SEVS]
    before = QBATCH_SHARED_BYTES_AVOIDED_TOTAL.get()
    saved = note_group_shared_staging(plans, 3)
    shared, _stacked = ex.stacked_slot_split(plans)
    expect = sum(plans[0].arrays[s].nbytes for s in shared) * 2
    assert saved == expect > 0
    assert QBATCH_SHARED_BYTES_AVOIDED_TOTAL.get() - before == expect
    # a lone lane shares with nobody
    assert note_group_shared_staging(plans, 1) == 0


# --- batcher integration: group formation, masking, kill switch -------------

def _run_group_through_batcher(batcher, prepped, k, cancel_idx=None,
                               profiles=None):
    """Deterministic group formation: hold the dispatch lock so riders
    pile into one queue, optionally cancel one AFTER it joined, then
    release and let the leader dispatch."""
    plans = [p for p, _ in prepped]
    key = batcher.planner.key_for(plans[0], k, "s", qbatch_enabled())
    assert all(batcher.planner.key_for(p, k, "s", qbatch_enabled()) == key
               for p in plans)
    entry = batcher._dispatch_locks.setdefault(key, [_PriorityLock(), 1])
    entry[0].acquire()
    results = [None] * len(prepped)
    tokens = [CancellationToken() for _ in prepped]

    def rider(i):
        plan, arrs = prepped[i]
        try:
            with cancel_scope(tokens[i]):
                if profiles is not None:
                    with profile_scope(profiles[i]):
                        results[i] = batcher.execute(plan, k, arrs,
                                                     split_key="s")
                else:
                    results[i] = batcher.execute(plan, k, arrs,
                                                 split_key="s")
        except Exception as exc:  # noqa: BLE001 - recorded for asserts
            results[i] = exc

    threads = [threading.Thread(target=rider, args=(i,), daemon=True)
               for i in range(len(prepped))]
    for t in threads:
        t.start()
    deadline = time.monotonic() + 10.0
    while (len(batcher._queues.get(key, ())) < len(prepped)
           and time.monotonic() < deadline):
        time.sleep(0.005)
    assert len(batcher._queues.get(key, ())) == len(prepped)
    if cancel_idx is not None:
        tokens[cancel_idx].cancel("shed after group formation")
    entry[0].release()
    for t in threads:
        t.join(timeout=30.0)
    with batcher._lock:
        entry[1] -= 1
        if entry[1] <= 0:
            batcher._dispatch_locks.pop(key, None)
    return results


def test_batcher_groups_distinct_queries_into_one_launch(reader):
    prepped = [_prep(reader, _window_req(T0 + 600 * i, T0 + 600 * i + 9000,
                                         max_hits=6,
                                         sort_fields=[SortField("ts",
                                                                "desc")]))
               for i in range(3)]
    solos = [ex.execute_plan(p, 6, a) for p, a in prepped]
    batcher = QueryBatcher()
    groups0 = QBATCH_GROUPS_TOTAL.get()
    launches0 = SEARCH_KERNEL_LAUNCHES_TOTAL.get()
    results = _run_group_through_batcher(batcher, prepped, 6)
    assert SEARCH_KERNEL_LAUNCHES_TOTAL.get() - launches0 == 1
    assert QBATCH_GROUPS_TOTAL.get() - groups0 == 1
    for got, want in zip(results, solos):
        assert not isinstance(got, Exception)
        _assert_same(got, want)
    assert batcher.num_dispatches == 1 and batcher.num_queries == 3
    assert not batcher._dispatch_locks


def test_masked_rider_keeps_single_launch_and_survivors_exact(reader):
    """THE satellite regression: a rider cancelled after group formation
    but before launch is masked out (validity lane), not rebuilt around —
    launch count stays 1, no new compiled program, survivors bit-identical
    to solo, and the doomed rider gets a typed CancelledQuery."""
    prepped = [_prep(reader, _window_req(T0 + 600 * i, T0 + 600 * i + 9000,
                                         max_hits=6,
                                         sort_fields=[SortField("ts",
                                                                "desc")]))
               for i in range(3)]
    solos = [ex.execute_plan(p, 6, a) for p, a in prepped]
    # warm the stacked program for this exact shape+bucket so a recompile
    # (cache growth) below would be visible
    ex.readback_plan_stacked(ex.dispatch_plan_stacked(
        [p for p, _ in prepped], 6, [a for _, a in prepped]))
    cache_size = len(ex._STACKED_CACHE)
    batcher = QueryBatcher()
    launches0 = SEARCH_KERNEL_LAUNCHES_TOTAL.get()
    masked0 = QBATCH_MASKED_RIDERS_TOTAL.get()
    results = _run_group_through_batcher(batcher, prepped, 6, cancel_idx=1)
    assert SEARCH_KERNEL_LAUNCHES_TOTAL.get() - launches0 == 1
    assert len(ex._STACKED_CACHE) == cache_size
    assert QBATCH_MASKED_RIDERS_TOTAL.get() - masked0 == 1
    assert isinstance(results[1], CancelledQuery)
    _assert_same(results[0], solos[0])
    _assert_same(results[2], solos[2])
    assert batcher.num_dispatches == 1


def test_group_riders_get_group_wait_phase(reader):
    """Grouped riders' profiles attribute the formation wait to
    `qbatch_group_wait` (not the convoy's `batcher_queue`), so dashboards
    can separate stacking wait from convoy wait."""
    prepped = [_prep(reader, _sev_req(s, max_hits=5)) for s in SEVS]
    profiles = [QueryProfile(f"q{i}") for i in range(3)]
    batcher = QueryBatcher()
    results = _run_group_through_batcher(batcher, prepped, 5,
                                         profiles=profiles)
    assert not any(isinstance(r, Exception) for r in results)
    for prof in profiles:
        names = [p["name"] for p in prof.phases()]
        assert PHASE_QBATCH_GROUP in names
        assert PHASE_BATCHER_QUEUE not in names
        group = next(p for p in prof.phases()
                     if p["name"] == PHASE_QBATCH_GROUP)
        assert group["riders"] == 3


def test_queries_per_dispatch_histogram_observes_live_lanes(reader):
    prepped = [_prep(reader, _sev_req(s, max_hits=5)) for s in SEVS]
    before = QBATCH_QUERIES_PER_DISPATCH._totals.get((), 0)
    batcher = QueryBatcher()
    _run_group_through_batcher(batcher, prepped, 5)
    assert QBATCH_QUERIES_PER_DISPATCH._totals.get((), 0) == before + 1
    # the 3-lane group lands in the le=4 bucket
    assert QBATCH_QUERIES_PER_DISPATCH.percentile(0.5) <= 4.0


def test_kill_switch_restores_convoy_behavior(reader, monkeypatch):
    """QW_DISABLE_QBATCH: distinct-term queries lead separate queues
    (per-array keys), each dispatches alone, qbatch metrics stay silent,
    and results equal the stacking-on results bit for bit."""
    stacked_results = []
    batcher_on = QueryBatcher()
    for s in SEVS:
        plan, arrs = _prep(reader, _sev_req(s, max_hits=10))
        stacked_results.append(batcher_on.execute(plan, 10, arrs,
                                                  split_key="s"))
    monkeypatch.setenv("QW_DISABLE_QBATCH", "1")
    assert not qbatch_enabled()
    groups0 = QBATCH_GROUPS_TOTAL.get()
    batcher = QueryBatcher()
    for s, want in zip(SEVS, stacked_results):
        plan, arrs = _prep(reader, _sev_req(s, max_hits=10))
        got = batcher.execute(plan, 10, arrs, split_key="s")
        _assert_same(got, want)
    assert batcher.num_dispatches == batcher.num_queries == 3
    assert QBATCH_GROUPS_TOTAL.get() == groups0


def test_solo_rider_result_identical_on_and_off(reader, monkeypatch):
    """A lone query must be byte-identical with stacking on, with it off,
    and with no batcher at all — the kill switch changes routing, never
    results."""
    plan, arrs = _prep(reader, _sev_req("ERROR", max_hits=10))
    base = ex.execute_plan(plan, 10, arrs)
    on = QueryBatcher().execute(plan, 10, arrs, split_key="s")
    monkeypatch.setenv("QW_DISABLE_QBATCH", "1")
    off = QueryBatcher().execute(plan, 10, arrs, split_key="s")
    _assert_same(on, base)
    _assert_same(off, base)


# --- chunked group composition ----------------------------------------------

def test_group_chunked_matches_solo(big_reader):
    """The chunked stacked scan (carried state with a query dim, one
    stacked dispatch per chunk) returns the same results as each query's
    solo run."""
    prepped = [_prep(big_reader, _sev_req(s, max_hits=10)) for s in SEVS]
    plans = [p for p, _ in prepped]
    assert len({p.structure_digest(10) for p in plans}) == 1
    assert chunkexec.chunk_mode(plans[0]) is not None
    solos = [ex.execute_plan(p, 10, a) for p, a in prepped]
    results = chunkexec.execute_group_chunked(
        plans, 10, [a for _, a in prepped], span=256)
    assert results is not None
    for got, want in zip(results, solos):
        _assert_same(got, want)


def test_group_chunked_masks_and_cancels_lanes(big_reader):
    prepped = [_prep(big_reader, _sev_req(s, max_hits=10)) for s in SEVS]
    plans = [p for p, _ in prepped]
    solos = [ex.execute_plan(p, 10, a) for p, a in prepped]
    doomed = CancellationToken()
    doomed.cancel("lane cancelled before the scan")
    results = chunkexec.execute_group_chunked(
        plans, 10, [a for _, a in prepped],
        valid=[True, False, True],
        cancels=[doomed, None, None], span=256)
    assert results is not None
    assert results[1] is None                       # masked on entry
    lane0 = results[0]
    assert isinstance(lane0, CancelledQuery) or (
        isinstance(lane0, dict) and lane0.get("partial"))
    _assert_same(results[2], solos[2])
