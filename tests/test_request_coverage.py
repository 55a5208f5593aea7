"""A served search accounts for itself (ISSUE 25): every blocking wait and
device round trip is a phase on the profile of the request that pays for
it, a phase is also a `qw.<name>` event on the `jax.profiler`'s clock, and
every jitted program of the served path carries a static readable name and
the scope vocabulary on its stages — all as functions of the program's
cache key alone, so nothing new is compiled.
"""

import dataclasses
import re
import threading
import time
import traceback
from contextlib import contextmanager

import jax
import numpy as np
import pytest

from quickwit_tpu.common.uri import Uri
from quickwit_tpu.index.reader import SplitReader
from quickwit_tpu.index.synthetic import (HDFS_MAPPER, body_term,
                                          synthetic_hdfs_split)
from quickwit_tpu.observability import profile as profile_mod
from quickwit_tpu.observability.metrics import SEARCH_KERNEL_LAUNCHES_TOTAL
from quickwit_tpu.observability.profile import (
    PHASE_BATCHER_QUEUE, PHASE_GROUP_EXECUTE_WAIT, PHASE_MASK_FILL,
    PHASE_QBATCH_GROUP, QueryProfile, profile_scope, profiled_phase)
from quickwit_tpu.parallel import fanout
from quickwit_tpu.query.ast import Bool, Range, RangeBound, Term
from quickwit_tpu.search import SearchRequest, SortField, executor
from quickwit_tpu.search.batcher import (QueryBatcher, _PriorityLock,
                                         qbatch_enabled)
from quickwit_tpu.search.chunkexec import CHUNKING
from quickwit_tpu.search.leaf import prepare_plan_only, prepare_single_split
from quickwit_tpu.search.models import LeafSearchRequest, SplitIdAndFooter
from quickwit_tpu.search.service import SearcherContext, SearchService
from quickwit_tpu.storage import RamStorage, StorageResolver
from quickwit_tpu.storage.base import Protocol

NUM_DOCS = 4_000
T0_US = 1_600_000_000 * 1_000_000
DAY_US = 86_400 * 1_000_000
ERROR = Term("severity_text", "ERROR")
AGGS = {"over_time": {"date_histogram": {"field": "timestamp",
                                         "fixed_interval": "1d"}},
        "severities": {"terms": {"field": "severity_text", "size": 10}}}
# the scope vocabulary, as docs/observability.md lists it
VOCABULARY = {"term_mask", "bm25_score", "range_filter", "sort_key", "topk",
              "aggs", "pack", "mask_fill"}


def _window(lo_days: float, hi_days: float) -> Range:
    return Range("timestamp",
                 lower=RangeBound(T0_US + int(lo_days * DAY_US), True),
                 upper=RangeBound(T0_US + int(hi_days * DAY_US), False))


def _newest(lo: float, hi: float, max_hits: int = 10) -> SearchRequest:
    """The benchmark's `term_newest10`: a term in a range, newest first."""
    return SearchRequest(
        index_ids=["hdfs-logs"], max_hits=max_hits,
        query_ast=Bool(must=(ERROR,), filter=(_window(lo, hi),)),
        sort_fields=(SortField("timestamp", "desc"),), profile=True)


@pytest.fixture(scope="module")
def storage():
    store = RamStorage(Uri.parse("ram:///coverage"))
    store.put("s0.split", synthetic_hdfs_split(NUM_DOCS, seed=3))
    return store


@pytest.fixture(scope="module")
def reader(storage):
    return SplitReader(storage, "s0.split")


@pytest.fixture()
def service(storage):
    resolver = StorageResolver()
    resolver.register(Protocol.RAM, lambda uri: storage)
    return SearchService(SearcherContext(storage_resolver=resolver,
                                         batch_size=1, prefetch=False))


def _leaf(service, storage, request) -> dict:
    split = SplitIdAndFooter(split_id="s0", storage_uri=str(storage.uri),
                             file_len=len(storage.get_all("s0.split")),
                             num_docs=NUM_DOCS)
    response = service.leaf_search(LeafSearchRequest(
        search_request=request, index_uid="hdfs-logs:0",
        doc_mapping=HDFS_MAPPER.to_dict(), splits=[split]))
    assert not response.failed_splits
    return response.profile


@pytest.fixture()
def launches(monkeypatch):
    """The launches this test's own thread counts: the counter is the
    process's, and a worker's earlier tests may still have threads about."""
    mine, me = [], threading.get_ident()
    real = SEARCH_KERNEL_LAUNCHES_TOTAL.inc
    # one fused program per dispatch: a worker whose earlier tests chunked
    # has taught the process-wide sizer a span, and a scan then launches one
    # program per chunk (tests/test_chunked_execution.py before this file)
    monkeypatch.setattr(CHUNKING, "enabled", False)

    def inc(*args, **labels):
        if threading.get_ident() == me:
            mine.append(1)
        return real(*args, **labels)

    monkeypatch.setattr(SEARCH_KERNEL_LAUNCHES_TOTAL, "inc", inc)
    return mine


# --- (a) the mask-tier fill is a phase of the request that pays for it ------

def test_mask_fill_is_a_phase_and_a_mask_hit_has_none(service, storage,
                                                      launches):
    first = _leaf(service, storage, _newest(1, 4))
    fills = [p for p in first["phases"] if p["name"] == PHASE_MASK_FILL]
    assert len(fills) == 1 and fills[0]["duration_ms"] > 0
    assert first["counters"]["mask_fills"] == 1
    # the main program and the fill: both counted where they launch
    assert len(launches) == 2
    # the same filter at another page size: a leaf-cache miss, a mask hit
    second = _leaf(service, storage, _newest(1, 4, max_hits=7))
    assert second["phases"], "the second request was served from a cache"
    assert PHASE_MASK_FILL not in {p["name"] for p in second["phases"]}
    assert "mask_fills" not in second["counters"]
    assert len(launches) == 3


def test_the_leaf_path_is_covered_by_phases(service, storage):
    """What the benchmark's `request_uncovered_ms` reads: on the leaf, the
    phases' union leaves out only scraps between them."""
    profile = _leaf(service, storage, _newest(2, 5))
    names = {p["name"] for p in profile["phases"]}
    assert {"leaf_prepare", "split_open", "cache_lookup", "plan_build",
            "dispatch_prepare", "execute", "mask_fill", "cache_fill"} <= names
    assert {"compile", "execute"} & names


# --- (b) a rider's wait for its group's device run is on its own profile ----

def _form_group(batcher, prepped, k, profiles):
    """Hold the dispatch lock until every rider has queued, then let the
    leader dispatch: one stacked group, deterministically."""
    plans = [plan for plan, _ in prepped]
    key = batcher.planner.key_for(plans[0], k, "s", qbatch_enabled())
    entry = batcher._dispatch_locks.setdefault(key, [_PriorityLock(), 1])
    entry[0].acquire()
    results, spans = [None] * len(prepped), [None] * len(prepped)

    def rider(i):
        plan, arrays = prepped[i]
        with profile_scope(profiles[i]):
            began = time.monotonic()
            try:
                results[i] = batcher.execute(plan, k, arrays, split_key="s")
            except Exception as exc:  # noqa: BLE001 - asserted on below
                results[i] = exc
            spans[i] = (began, time.monotonic())

    threads = [threading.Thread(target=rider, args=(i,), daemon=True)
               for i in range(len(prepped))]
    for thread in threads:
        thread.start()
    deadline = time.monotonic() + 10.0
    while (len(batcher._queues.get(key, ())) < len(prepped)
           and time.monotonic() < deadline):
        time.sleep(0.005)
    assert len(batcher._queues.get(key, ())) == len(prepped)
    entry[0].release()
    for thread in threads:
        thread.join(timeout=30.0)
        assert not thread.is_alive()
    with batcher._lock:
        entry[1] -= 1
        if entry[1] <= 0:
            batcher._dispatch_locks.pop(key, None)
    return results, spans


def test_riders_hold_group_execute_wait_from_dispatch_to_result(reader):
    requests = [_newest(i / 4, 3 + i / 4) for i in range(3)]
    prepped = [prepare_single_split(r, HDFS_MAPPER, reader, "s")[:2]
               for r in requests]
    profiles = [QueryProfile(f"q{i}") for i in range(3)]
    results, spans = _form_group(QueryBatcher(), prepped, 10, profiles)
    assert not any(isinstance(r, Exception) for r in results), results
    leaders = 0
    for profile, (began, ended) in zip(profiles, spans):
        phases = {p["name"]: p for p in profile.phases()}
        assert PHASE_QBATCH_GROUP in phases
        assert PHASE_BATCHER_QUEUE not in phases
        if PHASE_GROUP_EXECUTE_WAIT not in phases:
            leaders += 1        # the leader ran the group: execute is its own
            assert "execute" in phases or "compile" in phases
            continue
        queued, waited = phases[PHASE_QBATCH_GROUP], \
            phases[PHASE_GROUP_EXECUTE_WAIT]
        assert waited["riders"] == 3 and waited["lane"] in (0, 1, 2)
        # the two phases meet at the leader's dispatch and cover the rider
        # from enqueue to result, to within the thread's wake-up
        assert abs(queued["start_ms"] + queued["duration_ms"]
                   - waited["start_ms"]) < 0.01
        inside_ms = (ended - began) * 1000.0
        covered = queued["duration_ms"] + waited["duration_ms"]
        assert covered <= inside_ms + 0.01
        assert inside_ms - covered < max(25.0, 0.25 * inside_ms)
    assert leaders == 1


# --- (c) phases land in the profiler's trace, and only with a profile -------

def _host_events(trace_dir) -> list:
    from jax.profiler import ProfileData
    path, = trace_dir.glob("plugins/profile/*/*.xplane.pb")
    return [(event.name, dict(event.stats))
            for plane in ProfileData.from_file(str(path)).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for event in line.events
            if event.name.startswith("qw.")]


def _trace(trace_dir, body) -> list:
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0     # as benchmark/node_main.py does
    options.host_tracer_level = 1
    jax.profiler.start_trace(str(trace_dir), profiler_options=options)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    return _host_events(trace_dir)


def test_a_profiled_phase_is_an_event_on_the_profilers_clock(tmp_path):
    profile = QueryProfile("query-25")

    def profiled():
        with profile_scope(profile):
            with profiled_phase("plan_build"):
                time.sleep(0.002)
            with profile.phase("execute", stage="readback"):
                time.sleep(0.002)

    events = _trace(tmp_path / "on", profiled)
    by_name = dict(events)
    assert set(by_name) == {"qw.plan_build", "qw.execute"}
    assert by_name["qw.plan_build"]["query_id"] == "query-25"
    assert by_name["qw.execute"]["stage"] == "readback"
    assert [p["name"] for p in profile.phases()] == ["plan_build", "execute"]

    def unprofiled():
        with profiled_phase("plan_build"):      # no profile bound: the shared
            time.sleep(0.002)                   # no-op, and no annotation

    assert _trace(tmp_path / "off", unprofiled) == []


# --- (d) static names and scopes, functions of the cache key alone ----------

REQUESTS = {
    "bool_range_top100": (100, SearchRequest(
        index_ids=["hdfs-logs"], max_hits=100,
        query_ast=Bool(must=(ERROR,),
                       should=(Term("body", body_term(3)),
                               Term("body", body_term(7))),
                       filter=(_window(1, 4),)))),
    "flagship_top10_aggs": (10, SearchRequest(
        index_ids=["hdfs-logs"], query_ast=ERROR, max_hits=10, aggs=AGGS)),
    "term_newest10": (10, _newest(1, 5)),
    "agg_only": (0, SearchRequest(
        index_ids=["hdfs-logs"], max_hits=0, aggs=AGGS,
        query_ast=Bool(must=(ERROR,), filter=(_window(1, 3),)))),
}
USES = {    # the vocabulary each plan's stages use
    "bool_range_top100": {"term_mask", "bm25_score", "range_filter",
                          "sort_key", "topk", "pack"},
    "flagship_top10_aggs": {"bm25_score", "sort_key", "topk", "aggs", "pack"},
    "term_newest10": {"term_mask", "range_filter", "sort_key", "topk",
                      "pack"},
    "agg_only": {"term_mask", "range_filter", "aggs", "pack"},
}


def _structs(values):
    return tuple(jax.ShapeDtypeStruct(np.shape(v), np.asarray(v).dtype)
                 for v in values)


def _plan_args(plan):
    return (_structs(plan.arrays), _structs(plan.scalars),
            jax.ShapeDtypeStruct((), np.int32))


def _scopes_of(path: str) -> list:
    """The vocabulary names in a framework-op path, outermost first; a
    vmapped program writes `vmap(term_mask)` for `term_mask`."""
    words = re.findall(r"[A-Za-z_][\w.]*", path)
    return [w for w in ("aggs" if word.startswith("aggs.") else word
                        for word in words) if w in VOCABULARY]


def _module_and_scopes(jitted, args) -> tuple:
    text = jitted.lower(*args).as_text(debug_info=True)
    module = re.search(r"module @(\S+)", text).group(1)
    scopes = set()
    for path in re.findall(r'"(jit\([^"]*)"', text):
        scopes.update(_scopes_of(path))
    return module, scopes


@pytest.mark.parametrize("name", sorted(REQUESTS))
def test_solo_program_name_and_scopes(name, reader):
    k, request = REQUESTS[name]
    plan = prepare_plan_only(request, HDFS_MAPPER, reader, "s0")
    jitted, _, _ = executor._get_packed_executor(plan, k, _plan_args(plan),
                                                 key=("coverage", name))
    module, scopes = _module_and_scopes(jitted, _plan_args(plan))
    assert module == f"jit_qw_solo_k{k}"
    assert scopes == USES[name]


def test_multi_and_stacked_program_names_and_scopes(reader):
    lanes = [prepare_plan_only(_newest(i / 8, 3 + i / 8), HDFS_MAPPER,
                               reader, "s0") for i in range(4)]
    plan = lanes[0]
    slots = _structs(plan.arrays)
    scalars_b = tuple(jax.ShapeDtypeStruct((4,), np.asarray(s).dtype)
                      for s in plan.scalars)
    nd_b = jax.ShapeDtypeStruct((4,), np.int32)
    jitted, _, _ = executor._get_packed_multi_executor(
        plan, 10, 4, slots, key=("coverage", "multi"))
    module, scopes = _module_and_scopes(jitted, (slots, scalars_b, nd_b))
    assert module == "jit_qw_multi_b4_k10"
    assert scopes == USES["term_newest10"]
    _shared, stacked_slots = executor.stacked_slot_split(lanes)
    jitted, _, _ = executor._get_packed_stacked_executor(
        plan, 10, 4, stacked_slots, slots, key=("coverage", "stacked"))
    shared = tuple(s for i, s in enumerate(slots) if i not in stacked_slots)
    stacks = tuple(tuple(slots[i] for _ in range(4)) for i in stacked_slots)
    module, scopes = _module_and_scopes(
        jitted, (shared, stacks, scalars_b, nd_b,
                 jax.ShapeDtypeStruct((4,), np.bool_)))
    assert module == "jit_qw_stacked_q4_k10"
    assert scopes == USES["term_newest10"]


def test_mask_fill_program_name_and_one_outer_scope(reader):
    plan = prepare_plan_only(_newest(1, 5), HDFS_MAPPER, reader, "s0")
    text = jax.jit(executor._mask_fill_fn(plan)).lower(
        *_plan_args(plan)).as_text(debug_info=True)
    assert re.search(r"module @(\S+)", text).group(1) == "jit_qw_mask_fill"
    scoped = [_scopes_of(p) for p in re.findall(r'"(jit\([^"]*)"', text)]
    scoped = [names for names in scoped if names]
    assert scoped
    # the fill's own predicate sits under mask_fill: outermost, so it does
    # not count as a second term_mask or range_filter
    assert all(names[0] == "mask_fill" for names in scoped), scoped
    assert any("term_mask" in names for names in scoped)


def test_mesh_batch_program_name(reader):
    batch = fanout.build_batch(
        SearchRequest(index_ids=["hdfs-logs"], max_hits=10, aggs=AGGS,
                      query_ast=ERROR),
        HDFS_MAPPER, [reader] * 2, ["s0", "s1"])
    args = (tuple(jax.ShapeDtypeStruct(a.shape, a.dtype)
                  for a in batch.arrays),
            tuple(jax.ShapeDtypeStruct(s.shape, s.dtype)
                  for s in batch.scalars),
            jax.ShapeDtypeStruct(batch.num_docs.shape, batch.num_docs.dtype))
    jitted, _, _, _ = fanout._batch_executor(batch, 10,
                                             fanout.make_mesh(2, 1), args)
    text = jitted.lower(*args).as_text(debug_info=True)
    assert re.search(r"module @(\S+)", text).group(1) == \
        "jit_qw_batch_s2_k10_mesh2"
    # inside shard_map the name stack starts anew: the per-split stages'
    # paths carry no `jit(...)` prefix there (file paths start with "/")
    scopes = set()
    for path in re.findall(r'loc\("([^"/][^"]*)"', text):
        scopes.update(_scopes_of(path))
    assert {"bm25_score", "sort_key", "topk", "aggs", "pack"} <= scopes


def test_scalars_change_neither_the_name_nor_the_number_of_programs(reader,
                                                                    launches):
    """Two requests that differ only in their range bounds: one cache
    entry, one name, one launch each."""
    plans, arrays = zip(*(prepare_single_split(
        _newest(lo, lo + 2), HDFS_MAPPER, reader, "s0")[:2]
        for lo in (0.5, 1.75)))
    assert plans[0].scalars != plans[1].scalars
    before = set(executor._PACKED_CACHE)
    counts = [executor.execute_plan(plan, 10, arrs)["count"]
              for plan, arrs in zip(plans, arrays)]
    assert counts[0] != counts[1]
    assert len(launches) == 2
    added = set(executor._PACKED_CACHE) - before
    assert len(added) <= 1      # 0 where an earlier test compiled the shape
    key = executor.program_cache_key(plans[0], 10)
    assert key == executor.program_cache_key(plans[1], 10)
    assert executor._PACKED_CACHE[key][0].__name__ == "qw_solo_k10"


def test_the_mesh_batch_family_counts_its_launches(reader, launches):
    batch = fanout.build_batch(
        SearchRequest(index_ids=["hdfs-logs"], max_hits=5, query_ast=ERROR),
        HDFS_MAPPER, [reader] * 2, ["s0", "s1"])
    response = fanout.readback_batch(fanout.dispatch_batch(
        batch, SearchRequest(index_ids=["hdfs-logs"], max_hits=5,
                             query_ast=ERROR), fanout.make_mesh(2, 1)))
    assert response.num_hits > 0
    assert len(launches) == 1


# --- (e) no device round trip outside a phase --------------------------------

def test_every_device_get_of_a_profiled_search_is_inside_a_phase(
        service, storage, monkeypatch):
    depth = threading.local()
    real_phase, real_get = QueryProfile.phase, jax.device_get
    gets, outside = [], []

    @contextmanager
    def counting_phase(self, name, **attrs):
        depth.open = getattr(depth, "open", 0) + 1
        try:
            with real_phase(self, name, **attrs) as record:
                yield record
        finally:
            depth.open -= 1

    def watched_get(tree):
        gets.append(1)
        if (profile_mod.current_profile() is not None
                and not getattr(depth, "open", 0)):
            outside.append("".join(traceback.format_stack(limit=6)))
        return real_get(tree)

    monkeypatch.setattr(QueryProfile, "phase", counting_phase)
    monkeypatch.setattr(jax, "device_get", watched_get)
    for request in (_newest(0.25, 3.5),                 # main + mask fill
                    REQUESTS["agg_only"][1],            # k = 0
                    REQUESTS["bool_range_top100"][1]):  # scoring, top-100
        profile = _leaf(service, storage,
                        dataclasses.replace(request, profile=True))
        assert profile["phases"]
    assert len(gets) >= 4       # three programs and one mask fill at least
    assert not outside, outside[0]
