"""Search service: the node-local search endpoints.

Role of the reference's `SearchService` trait + `SearchServiceImpl`
(`quickwit-search/src/service.rs:65`) and the leaf entry point
`multi_index_leaf_search`/`single_doc_mapping_leaf_search`
(`leaf.rs:1497,1887`):

- `leaf_search`: search a batch of splits of one index on this node — split
  reordering for pruning (`CanSplitDoBetter`), leaf cache, one fused
  collective program over a group of splits where the node's devices form a
  mesh and the plan is split-uniform, otherwise the group's per-split
  programs launched together, partial failure collection.
- `fetch_docs`: phase-2 doc fetch + snippet generation.

The SearcherContext owns the caches (reader/hotcache byte ranges + device
arrays per split, leaf results) and the admission budget — the roles of the
reference's SearcherContext (`service.rs:405`) and SearchPermitProvider.
"""

from __future__ import annotations

import dataclasses
import logging
import os
import re
from collections import OrderedDict
from typing import Any, Optional

from ..common.ctx import run_with_context
from ..common.deadline import (
    CancelledQuery, Deadline, current_cancel_token, current_deadline,
    deadline_scope,
)
from ..index.reader import SplitReader
from ..models.doc_mapper import DocMapper
from ..observability.metrics import (
    SEARCH_DEADLINE_REMAINING, SEARCH_SHED_TOTAL,
    SEARCH_SPLITS_DOWNGRADED_TOTAL, SEARCH_SPLITS_PRUNED_TOTAL,
    SPLIT_WAVE_WIDTH,
)
from ..observability.profile import (
    PHASE_CACHE_FILL, PHASE_CACHE_LOOKUP, PHASE_LEAF_PREPARE,
    PHASE_SPLIT_OPEN, QueryProfile, current_profile, profile_scope,
    profiled_phase,
)
from ..query.ast import MatchAll
from ..parallel.fanout import (
    build_batch, dispatch_batch, per_device_bytes, readback_batch,
    release_stack_pin, stage_device_inputs,
)
from ..storage.base import StorageResolver
from ..tenancy.context import (
    TenantContext, current_tenant, tenant_scope,
)
from ..tenancy.overload import OverloadShed
from ..tenancy.registry import TenantRateLimited
from .agg_cache import PartialAggCache, agg_shape_digest
from .cache import (LeafSearchCache, canonical_filter_digest,
                    canonical_request_key)
from .mask_cache import PredicateMaskCache, packed_mask_nbytes
from .predicate_cache import PredicateCache, required_terms
from .collector import IncrementalCollector
from .leaf import (execute_prepared_split, leaf_search_single_split,
                   prepare_plan_only)
from .models import (
    FetchDocsRequest, LeafSearchRequest, LeafSearchResponse, SearchRequest,
    SplitIdAndFooter, SplitSearchError, string_sort_of,
)
from .pruning import (
    PruningContext, ScoreBoundCache, ThresholdBox, downgrade_to_count,
    pruning_context, record_split_term_stats, split_best_internal_key,
)

logger = logging.getLogger(__name__)

# rate_limited_tracing.rs analogue: a bad query fanned over thousands of
# splits must not emit thousands of identical warnings
from ..observability.tracing import TRACER, RateLimitedLog  # noqa: E402
from ..common import sync

_SPLIT_WARN_LIMITER = RateLimitedLog(limit=5, period_secs=60.0)


def _warn_split_failure(kind: str, split_id: str, exc: object) -> None:
    emit, suppressed = _SPLIT_WARN_LIMITER.should_log(kind)
    if emit:
        extra = f" ({suppressed} similar suppressed)" if suppressed else ""
        logger.warning("split %s %s failed: %s%s", split_id, kind, exc,
                       extra)


class SearcherContext:
    def __init__(self, storage_resolver: Optional[StorageResolver] = None,
                 max_open_splits: int = 128,
                 leaf_cache_bytes: int = 64 << 20,
                 batch_size: int = 8,
                 prefetch: bool = True,
                 offload: Optional[dict] = None,
                 offload_endpoint: Optional[str] = None,
                 offload_max_local_splits: int = 16,
                 offload_client_factory=None,
                 split_cache=None,
                 enable_threshold_pruning: bool = True,
                 resident_columns: bool = True,
                 mask_cache_bytes: int = 32 << 20,
                 agg_cache_bytes: int = 32 << 20,
                 enable_mask_cache: bool = True,
                 enable_agg_cache: bool = True,
                 fault_injector=None):
        self.storage_resolver = storage_resolver or StorageResolver.default()
        # disk-resident split cache (reference SearchSplitCache,
        # split_cache/mod.rs:43): reader opens check it first; misses
        # report the split as a download candidate
        self.split_cache = split_cache
        self.leaf_cache = LeafSearchCache(leaf_cache_bytes)
        # hierarchical leaf caches (docs/hierarchical-cache.md). Tier A
        # memoizes evaluated filter bitmasks, Tier B memoizes per-split
        # count + intermediate agg states; both key on the canonical
        # FILTER digest so dashboard panels sharing one filter collapse.
        # Constructor flags serve equivalence tests; the QW_DISABLE_* env
        # kill switches serve operators (same pattern as QW_DISABLE_IMPACT).
        # `fault_injector` threads the chaos points (cache.mask_corrupt /
        # cache.evict) into both tiers and the residency store.
        self.fault_injector = fault_injector
        self.mask_cache = (
            PredicateMaskCache(mask_cache_bytes,
                               fault_injector=fault_injector)
            if enable_mask_cache
            and os.environ.get("QW_DISABLE_MASK_CACHE", "0") != "1"
            else None)
        self.agg_cache = (
            PartialAggCache(agg_cache_bytes, fault_injector=fault_injector)
            if enable_agg_cache
            and os.environ.get("QW_DISABLE_AGG_CACHE", "0") != "1"
            else None)
        self.batch_size = batch_size
        # warmup/compute pipelining (SURVEY hard-part #4): one prefetch
        # worker stages batch N+1's storage IO + H2D transfer while batch
        # N executes on device. Single worker = classic double buffering;
        # bounds both memory (at most one staged batch) and storage load.
        self.prefetch = prefetch
        self._prefetch_pool = None
        # predicate/negative cache: (split, term)-absence proofs prune
        # provably-empty splits before the reader is even constructed
        # (reference: leaf_cache.rs:197 + leaf.rs:758-841)
        self.predicate_cache = PredicateCache()
        # dynamic top-K pruning (reference CanSplitDoBetter, leaf.rs:1279):
        # once the collector holds K hits, splits whose sort bound cannot
        # beat the Kth value are skipped or downgraded to count-only.
        # The flag exists so equivalence tests can run an unpruned baseline.
        self.enable_threshold_pruning = enable_threshold_pruning
        # per-(split, field, term) df/max-tf for BM25 score upper bounds,
        # recorded at split open (search/pruning.py)
        self.score_bound_cache = ScoreBoundCache()
        # byte-accurate HBM admission (reference SearchPermitProvider):
        # the lowered plan knows every array's size, so over-budget work
        # queues instead of materializing
        from .admission import HbmBudget
        self.hbm_budget = HbmBudget()
        # device-resident column store (search/residency.py): a warm
        # split's packed columns stay in HBM across queries AND reader
        # reopens (residency keys on split id, not reader identity); the
        # budget sees resident bytes through its existing owner seam. The
        # flag exists so equivalence tests can run a cold-staging baseline.
        from .residency import ResidentColumnStore
        self.resident_store = (
            ResidentColumnStore(fault_injector=fault_injector)
            if resident_columns else None)
        # cross-query dispatch coalescing: concurrent same-structure
        # queries on one split ride a single vmapped dispatch
        # (search/batcher.py; reference analogue: per-node leaf request
        # batching, leaf.rs:81)
        from .batcher import QueryBatcher
        self.query_batcher = QueryBatcher()
        self._readers: OrderedDict[str, SplitReader] = OrderedDict()
        self._max_open_splits = max_open_splits
        self._lock = sync.lock("SearchService._lock")
        self._meshes: dict = {}
        # elastic leaf-search offload (reference: lambda leaf-search
        # offload, quickwit-lambda-client/src/invoker.rs:129 + the
        # scheduling split at leaf.rs:1658,1828): cold splits beyond
        # `max_local_splits` per leaf request fan out over an elastic
        # worker pool (quickwit_tpu/offload/) — any processes serving the
        # internal leaf-search protocol (peer nodes, a FaaS worker
        # fleet, ...). The legacy single-endpoint knobs migrate into a
        # pool-of-one; `offload=None` with no endpoint keeps the subsystem
        # unimported and the leaf path byte-identical to the pre-pool
        # behavior.
        if offload is None and offload_endpoint:
            offload = {"endpoints": [offload_endpoint]}
        self.offload = offload
        self.offload_endpoint = offload_endpoint
        self.offload_max_local_splits = (
            int(offload.get("max_local_splits", offload_max_local_splits))
            if offload is not None else offload_max_local_splits)
        self._offload_client_factory = offload_client_factory
        self._offload_pool = None
        self._offload_dispatcher = None

    def offload_dispatcher(self):
        """The pool dispatcher, built lazily on first offloading leaf
        request; None when no pool is configured."""
        if self.offload is None:
            return None
        with self._lock:
            if self._offload_dispatcher is None:
                from ..offload import (
                    Autoscaler, OffloadDispatcher, WorkerPool,
                )
                config = self.offload
                pool = WorkerPool(
                    suspect_after=int(config.get("suspect_after", 1)),
                    eject_after=int(config.get("eject_after", 3)),
                    readmit_backoff_secs=float(
                        config.get("readmit_backoff_secs", 0.5)),
                    readmit_backoff_max_secs=float(
                        config.get("readmit_backoff_max_secs", 30.0)))
                for endpoint in config.get("endpoints", ()):
                    if self._offload_client_factory is not None:
                        client = self._offload_client_factory(endpoint)
                    else:
                        from ..serve.http_client import HttpSearchClient
                        client = HttpSearchClient(endpoint)
                    pool.add_worker(endpoint, client)
                autoscaler = None
                launcher = config.get("launcher")
                if launcher is not None:
                    autoscale = config.get("autoscale") or {}
                    autoscaler = Autoscaler(
                        pool, launcher,
                        min_workers=int(autoscale.get("min_workers", 1)),
                        max_workers=int(autoscale.get("max_workers", 8)),
                        queue_per_worker=int(
                            autoscale.get("queue_per_worker", 16)),
                        scale_down_cooldown_secs=float(autoscale.get(
                            "scale_down_cooldown_secs", 10.0)))
                self._offload_pool = pool
                self._offload_dispatcher = OffloadDispatcher(
                    pool,
                    task_splits=int(config.get("task_splits", 8)),
                    max_inflight_per_worker=int(
                        config.get("max_inflight_per_worker", 1)),
                    hedge_min_delay_secs=float(
                        config.get("hedge_min_delay_secs", 0.05)),
                    hedge_max_delay_secs=float(
                        config.get("hedge_max_delay_secs", 5.0)),
                    injector=config.get("fault_injector"),
                    autoscaler=autoscaler)
            return self._offload_dispatcher

    def offload_pool(self):
        """The live WorkerPool (builds the dispatcher if needed); None
        when offload is unconfigured."""
        if self.offload_dispatcher() is None:
            return None
        return self._offload_pool

    def device_mesh(self, n_splits: int):
        """A 2D ("splits", "docs") mesh sized to shard `n_splits` across
        this host's accelerators, or None when the batch cannot shard —
        single device, single split, or no axis size >1 divides the batch.
        Without a mesh the service runs the group's per-split programs
        together and merges on the host (`_execute_per_split`).

        The splits axis takes the largest size ≤ ndev that divides the
        batch; leftover devices fold into the docs axis (largest power of
        two, so it always divides the DOC_PAD-aligned padded doc count) —
        dense column shards then spread over splits × docs while compute
        replicates along docs (parallel/fanout.mesh_batch_fn)."""
        import jax
        ndev = len(jax.devices())
        if ndev < 2 or n_splits < 2:
            return None
        axis = min(ndev, n_splits)
        while axis > 1 and n_splits % axis:
            axis -= 1
        if axis < 2:
            return None
        docs = 1
        while docs * 2 * axis <= ndev:
            docs *= 2
        with self._lock:
            mesh = self._meshes.get((axis, docs))
            if mesh is None:
                from ..parallel.fanout import make_mesh
                mesh = self._meshes[(axis, docs)] = make_mesh(axis, docs)
            return mesh

    def has_warm_reader(self, split: SplitIdAndFooter) -> bool:
        """True when this split's reader (and its byte-range/device
        caches) is already resident — the 'warm split' signal the offload
        scheduling uses (the reference offloads splits absent from the
        local split cache)."""
        with self._lock:
            return f"{split.storage_uri}/{split.split_id}" in self._readers

    def peek_reader(self, split: SplitIdAndFooter) -> Optional[SplitReader]:
        """Warm reader or None — NEVER opens a cold split. Threshold
        pruning consults footer metadata (field min/max, term max-tf)
        through this: paying a footer GET to maybe skip one kernel would
        often cost more than the kernel."""
        with self._lock:
            return self._readers.get(f"{split.storage_uri}/{split.split_id}")

    def prefetch_pool(self):
        from concurrent.futures import ThreadPoolExecutor
        with self._lock:
            if self._prefetch_pool is None:
                self._prefetch_pool = ThreadPoolExecutor(
                    max_workers=1, thread_name_prefix="leaf-prefetch")
            return self._prefetch_pool

    def reader(self, split: SplitIdAndFooter) -> SplitReader:
        """LRU-cached split readers: keeps footer, term dict, byte-range and
        device-array caches warm across queries (the warmup-amortization the
        reference's cache stack exists for)."""
        key = f"{split.storage_uri}/{split.split_id}"
        with self._lock:
            reader = self._readers.get(key)
            if reader is not None:
                self._readers.move_to_end(key)
                return reader
        storage = self.storage_resolver.resolve(split.storage_uri)
        if self.split_cache is not None:
            local = self.split_cache.local_path(split.split_id)
            if local is not None:
                from ..common.uri import Uri
                from ..storage.local import LocalFileStorage
                storage = LocalFileStorage(
                    Uri.parse(f"file://{self.split_cache.root_path}"))
            else:
                self.split_cache.report_split(
                    split.split_id, split.storage_uri,
                    num_bytes_hint=split.file_len or 0)
        reader = SplitReader(storage, f"{split.split_id}.split",
                             file_len=split.file_len)
        with self._lock:
            self._readers[key] = reader
            while len(self._readers) > self._max_open_splits:
                self._readers.popitem(last=False)
        return reader


class SearchService:
    """One node's search endpoints. Any node can act as root; leaf work runs
    where this service lives."""

    def __init__(self, context: Optional[SearcherContext] = None,
                 node_id: str = "node-0"):
        self.context = context or SearcherContext()
        self.node_id = node_id

    # ------------------------------------------------------------------
    def leaf_search(self, request: LeafSearchRequest) -> LeafSearchResponse:
        # A remote hop also drops the root's ambient tenant — rebuild it
        # from the wire field so leaf-side admission/batching enforce the
        # same class; embedded leaves (same process, fan-out thread)
        # already run under the root's binding.
        if request.tenant is not None and current_tenant() is None:
            with tenant_scope(TenantContext.from_wire(request.tenant)):
                return self._leaf_search_profiled(request)
        return self._leaf_search_profiled(request)

    def _leaf_search_profiled(self,
                              request: LeafSearchRequest) -> LeafSearchResponse:
        # A remote hop (REST/gRPC wire) drops the root's ambient profile
        # object — build a leaf-local one when profiling was requested and
        # ship it back on the response; embedded leaves (same process,
        # fan-out thread) write into the root's profile directly through
        # the ambient binding and must NOT double-profile.
        if (current_profile() is None
                and request.search_request.profile):
            local_profile = QueryProfile()
            with TRACER.span("leaf_search",
                             {"num_splits": len(request.splits)}):
                with profile_scope(local_profile):
                    response = self._leaf_search_traced(request)
            local_profile.finish()
            response.profile = local_profile.to_dict()
            return response
        with TRACER.span("leaf_search",
                         {"num_splits": len(request.splits)}):
            return self._leaf_search_traced(request)

    def _leaf_search_traced(self,
                            request: LeafSearchRequest) -> LeafSearchResponse:
        # The wire deadline (remaining budget serialized by the root) wins;
        # in-process callers inherit the ambient scope; otherwise unbounded.
        if request.deadline_millis is not None:
            deadline = Deadline.from_millis(request.deadline_millis)
        else:
            deadline = current_deadline() or Deadline.never()
        if deadline.bounded:
            SEARCH_DEADLINE_REMAINING.observe(deadline.remaining())
        with deadline_scope(deadline):
            return self._leaf_search_deadlined(request, deadline)

    def _leaf_search_deadlined(self, request: LeafSearchRequest,
                               deadline: Deadline) -> LeafSearchResponse:
        search_request = request.search_request
        with profiled_phase(PHASE_LEAF_PREPARE) as rec:
            (doc_mapper, splits, collector, prune_ctx, threshold, prune_stats,
             num_pruned_by_predicate, pending) = self._triage_splits(request)
            if rec is not None:
                rec["splits"] = len(splits)
                rec["pending"] = len(pending)
        return self._search_pending(request, deadline, doc_mapper, splits,
                                    collector, prune_ctx, threshold,
                                    prune_stats, num_pruned_by_predicate,
                                    pending)

    def _triage_splits(self, request: LeafSearchRequest) -> tuple:
        """Everything before the first split is prepared: the doc mapper,
        the split order, and each split answered without the device where
        it can be (metadata count, negative predicate cache, leaf cache,
        agg tier, wire-seeded threshold). Returns (doc_mapper, splits,
        collector, prune_ctx, threshold, prune_stats,
        num_pruned_by_predicate, pending)."""
        doc_mapper = DocMapper.from_dict(request.doc_mapping)
        search_request = request.search_request
        splits = self._optimize_split_order(search_request, request.splits)

        collector = IncrementalCollector(
            max_hits=search_request.max_hits,
            start_offset=search_request.start_offset,
            string_sort=string_sort_of(search_request, doc_mapper))
        # dynamic top-K pruning (reference CanSplitDoBetter): resolve the
        # sort kind once; the ThresholdBox carries the collector's Kth
        # value to the prefetch thread (monotone, so stale reads are sound)
        prune_ctx = (pruning_context(search_request, doc_mapper)
                     if self.context.enable_threshold_pruning
                     else PruningContext(None, None))
        threshold = ThresholdBox(
            seed=(request.sort_value_threshold
                  if prune_ctx.mode is not None else None))
        prune_stats = {"pruned": 0, "downgraded": 0}
        required = required_terms(search_request.query_ast, doc_mapper)
        num_pruned_by_predicate = 0
        pending: list[SplitIdAndFooter] = []
        for split in splits:
            if self._count_from_metadata(search_request, split):
                # pure count over the whole split: the metastore's doc count
                # IS the answer — never open or transfer the split
                # (reference: CanSplitDoBetter count path, leaf.rs:1361)
                collector.add_leaf_response(LeafSearchResponse(
                    num_hits=split.num_docs, num_attempted_splits=1,
                    num_successful_splits=1))
                continue
            if required and self.context.predicate_cache.known_empty(
                    split.split_id, required):
                # negative cache: a required term is proven absent from this
                # split — provably zero hits and identity agg states, so skip
                # the reader open, warmup, H2D, and kernel launch entirely
                num_pruned_by_predicate += 1
                collector.add_leaf_response(LeafSearchResponse(
                    num_hits=0, num_attempted_splits=1,
                    num_successful_splits=1))
                continue
            key = canonical_request_key(split.split_id, search_request,
                                        split.time_range)
            cached = self.context.leaf_cache.get(key)
            if cached is not None:
                collector.add_leaf_response(cached)
                continue
            agg_served = self._serve_from_agg_cache(search_request, split)
            if agg_served is not None:
                # Tier B full short-circuit: a count/agg-only request whose
                # count AND every agg state are cached never opens the
                # reader — the dashboard-fanout case collapses to a merge
                collector.add_leaf_response(agg_served)
                continue
            pending.append(split)

        if (prune_ctx.mode is not None and threshold.get() is not None
                and not search_request.count_hits_exact):
            # wire-seeded threshold (root retry round 2): drop provably
            # beaten splits BEFORE the offload cut, so pruned splits never
            # count against the local budget or ship to the endpoint
            still_pending: list[SplitIdAndFooter] = []
            for split in pending:
                best = self._split_bound(prune_ctx, split)
                if best is not None and best < threshold.get():
                    prune_stats["pruned"] += 1
                    SEARCH_SPLITS_PRUNED_TOTAL.inc()
                    collector.add_leaf_response(LeafSearchResponse(
                        num_hits=0, num_attempted_splits=1,
                        num_successful_splits=1))
                else:
                    still_pending.append(split)
            pending = still_pending
        return (doc_mapper, splits, collector, prune_ctx, threshold,
                prune_stats, num_pruned_by_predicate, pending)

    def _search_pending(self, request: LeafSearchRequest, deadline: Deadline,
                        doc_mapper, splits, collector, prune_ctx, threshold,
                        prune_stats, num_pruned_by_predicate,
                        pending) -> LeafSearchResponse:
        search_request = request.search_request
        offload_future = None
        offload_result: dict[str, Any] = {}
        offloaded: list[SplitIdAndFooter] = []
        offload_dispatcher = self.context.offload_dispatcher()
        if (offload_dispatcher is not None
                and len(pending) > self.context.offload_max_local_splits):
            # scheduling split (reference schedule_search_tasks,
            # leaf.rs:1828): warm splits stay local; the coldest tail
            # beyond the local budget fans out over the worker pool
            # CONCURRENTLY with the local loop
            warm = [s for s in pending if self.context.has_warm_reader(s)]
            cold = [s for s in pending
                    if not self.context.has_warm_reader(s)]
            budget = max(self.context.offload_max_local_splits, len(warm))
            local = (warm + cold)[:budget]
            offloaded = (warm + cold)[budget:]
            if offloaded:
                pending = local
                offload_tenant = current_tenant()
                remote_request = LeafSearchRequest(
                    search_request=search_request,
                    index_uid=request.index_uid,
                    doc_mapping=request.doc_mapping, splits=offloaded,
                    deadline_millis=deadline.timeout_millis(),
                    # the offload workers enforce the same tenant class
                    tenant=(offload_tenant.to_wire()
                            if offload_tenant is not None else None),
                    # seeded at dispatch time inside _invoke (below): the
                    # threshold is monotone, so the LATEST value prunes
                    # strictly more on the workers than a capture-time copy
                    sort_value_threshold=None)
                result_box: dict[str, Any] = {}
                # the dispatch thread has an empty thread-local span stack:
                # capture the traceparent HERE so each worker RPC's
                # injected header joins this query's trace (same capture
                # as root _fan_out)
                offload_tp = TRACER.current_traceparent()

                def _invoke(box=result_box, rr=remote_request,
                            tp=offload_tp):
                    try:
                        # read the shared ThresholdBox from the dispatch
                        # thread, NOT at capture time: the local execute
                        # loop keeps raising it concurrently
                        if prune_ctx.mode is not None:
                            rr = dataclasses.replace(
                                rr, sort_value_threshold=threshold.get())
                        with TRACER.span(
                                "leaf_offload",
                                {"num_splits": len(rr.splits)},
                                remote_parent=tp):
                            box["outcome"] = offload_dispatcher.dispatch(
                                rr, deadline=deadline, traceparent=tp)
                    except (OverloadShed, TenantRateLimited) as exc:
                        # typed backpressure from a worker: this query is
                        # rejected as a WHOLE (HTTP 429), NOT retried
                        # locally — a local retry would defeat the remote
                        # tenant limits
                        box["backpressure"] = exc
                    # qwlint: disable-next-line=QW004 - only generic pool
                    # failure lands here (typed backpressure is re-raised
                    # above); the offloaded splits fall back to LOCAL
                    # execution below, so nothing is swallowed
                    except Exception as exc:  # noqa: BLE001 - fallback below
                        box["error"] = exc

                # run_with_context: the dispatch thread (and the worker
                # attempt threads it spawns) must see the query's
                # deadline, tenant and profile
                offload_future = sync.thread(
                    target=run_with_context(_invoke),
                    name="leaf-offload", daemon=True)
                offload_future.start()
                offload_result = result_box

        batch_size = self.context.batch_size
        groups = [pending[b: b + batch_size]
                  for b in range(0, len(pending), batch_size)]
        # pipelined loop: group i executes while group i+1's storage IO and
        # H2D transfer run on the prefetch worker (double buffering —
        # reference rationale: the warmup/cache stack of leaf.rs:304).
        # The prefetch worker re-reads the ThresholdBox before staging, so
        # a split that just became prunable never burns storage IO or H2D;
        # the execute stage re-checks once more (the threshold is monotone,
        # so both reads are sound however stale).
        pipelined = self.context.prefetch and len(groups) > 1
        future = None
        if pipelined:
            # contextvars do not reach pool worker threads: one snapshot
            # carries deadline+tenant+profile (and any future binding)
            future = self.context.prefetch_pool().submit(
                run_with_context(self._prepare_group),
                groups[0], doc_mapper, search_request, prune_ctx, threshold)
        for i, group in enumerate(groups):
            begin = i * batch_size
            if deadline.expired:
                # out of budget mid-request: every remaining split surfaces
                # as a typed, retryable failure — partial and on time
                SEARCH_SHED_TOTAL.inc(stage="leaf_groups")
                shed_profile = current_profile()
                if shed_profile is not None:
                    shed_profile.mark_partial("shed: leaf group loop")
                for split in pending[begin:]:
                    collector.failed_splits.append(SplitSearchError(
                        split_id=split.split_id,
                        error="deadline exceeded before split executed at leaf",
                        retryable=True))
                if future is not None:
                    self._discard_prepared(future.result())
                    future = None
                break
            prepared = (future.result() if future is not None
                        else self._prepare_group(group, doc_mapper,
                                                 search_request, prune_ctx,
                                                 threshold))
            future = None
            if pipelined and i + 1 < len(groups):
                future = self.context.prefetch_pool().submit(
                    run_with_context(self._prepare_group),
                    groups[i + 1], doc_mapper, search_request, prune_ctx,
                    threshold)
            self._execute_group(prepared, doc_mapper, search_request,
                                collector, prune_ctx, threshold, prune_stats)
            # publish the (possibly higher) Kth value for the next groups
            threshold.update(collector.sort_value_threshold())

        num_offloaded = 0
        if offload_future is not None:
            offload_future.join(
                timeout=deadline.clamp(self._OFFLOAD_TIMEOUT_SECS))
            backpressure = offload_result.get("backpressure")
            if backpressure is not None:
                # a worker said 429 for this tenant/node: surface the SAME
                # typed error so serve/rest.py renders a real 429 instead
                # of silently re-running the splits locally (which would
                # bypass the remote admission decision)
                raise backpressure
            outcome = offload_result.get("outcome")
            leftovers: list[SplitIdAndFooter] = []
            if outcome is not None:
                for remote in outcome.responses:
                    collector.add_leaf_response(remote)
                    if remote.profile is not None:
                        remote_profile = current_profile()
                        if remote_profile is not None:
                            remote_profile.add_child(remote.profile)
                leftovers = list(outcome.unserved)
                num_offloaded = len(offloaded) - len(leftovers)
                stats_profile = current_profile()
                if stats_profile is not None:
                    for stat_key, value in outcome.stats.items():
                        if value:
                            stats_profile.add(f"offload_{stat_key}", value)
            else:
                leftovers = list(offloaded)
            if leftovers:
                # pool failed / timed out / left splits unserved: the
                # splits still belong to this request — run them locally
                # (reference invoker falls back the same way)
                _warn_split_failure(
                    "offload", leftovers[0].split_id,
                    offload_result.get(
                        "error",
                        "unserved" if outcome is not None else "timeout"))
                for group in [leftovers[b: b + batch_size]
                              for b in range(0, len(leftovers), batch_size)]:
                    if deadline.expired:
                        SEARCH_SHED_TOTAL.inc(stage="offload_fallback")
                        shed_profile = current_profile()
                        if shed_profile is not None:
                            shed_profile.mark_partial(
                                "shed: offload fallback")
                        for split in group:
                            collector.failed_splits.append(SplitSearchError(
                                split_id=split.split_id,
                                error="deadline exceeded before offloaded "
                                      "split ran locally",
                                retryable=True))
                        continue
                    prepared = self._prepare_group(group, doc_mapper,
                                                   search_request, prune_ctx,
                                                   threshold)
                    self._execute_group(prepared, doc_mapper, search_request,
                                        collector, prune_ctx, threshold,
                                        prune_stats)
                    threshold.update(collector.sort_value_threshold())

        response = collector.to_leaf_response()
        response.num_attempted_splits = len(splits)
        # num_splits_skipped predates the threshold subsystem and stays as
        # an alias of the threshold-pruned count (dashboards key on it)
        response.resource_stats["num_splits_skipped"] = prune_stats["pruned"]
        response.resource_stats["num_splits_pruned_by_threshold"] = \
            prune_stats["pruned"]
        response.resource_stats["num_splits_downgraded_to_count"] = \
            prune_stats["downgraded"]
        response.resource_stats["num_splits_pruned_by_predicate_cache"] = \
            num_pruned_by_predicate
        if num_offloaded:
            response.resource_stats["num_splits_offloaded"] = num_offloaded
        profile = current_profile()
        if profile is not None:
            # pruning decisions land in the waterfall's counters; the
            # threshold that killed the pruned splits rides along so the
            # profile can answer "skipped — against WHAT bound?"
            for stat_key, value in response.resource_stats.items():
                profile.add(stat_key, value)
            final_threshold = threshold.get()
            if final_threshold is not None and (
                    prune_stats["pruned"] or prune_stats["downgraded"]):
                profile.set_counter("topk_prune_threshold",
                                    float(final_threshold))
        return response

    _OFFLOAD_TIMEOUT_SECS = 30.0

    @staticmethod
    def _count_from_metadata(request: SearchRequest,
                             split: SplitIdAndFooter) -> bool:
        """True when this split's contribution is exactly its doc count:
        match-all query, no hits wanted, no aggregations, and any request
        time filter fully covers the split's own time range (sound because
        the doc mapper requires the timestamp field on every doc, so the
        split range bounds all of them)."""
        if (request.max_hits != 0 or request.start_offset != 0
                or request.aggs or not isinstance(request.query_ast, MatchAll)):
            return False
        if request.start_timestamp is None and request.end_timestamp is None:
            return True
        if split.time_range is None:
            return False  # no bounds recorded: must evaluate
        lo, hi = split.time_range
        if request.start_timestamp is not None and request.start_timestamp > lo:
            return False
        # end_timestamp is exclusive; split ranges are inclusive
        if request.end_timestamp is not None and request.end_timestamp <= hi:
            return False
        return True

    def _split_bound(self, prune_ctx: PruningContext,
                     split: SplitIdAndFooter) -> Optional[float]:
        """Best internal sort key any doc of `split` can reach, or None
        (must run). Consults only metadata already in hand: split
        time_range, a WARM reader's footer field min/max, or the score
        bound cache (falling back to a warm reader's term stats)."""
        def field_meta():
            reader = self.context.peek_reader(split)
            return (reader.field_meta(prune_ctx.sort.field)
                    if reader is not None else None)

        def score_stats(field, term):
            stats = self.context.score_bound_cache.get(
                split.split_id, field, term)
            if stats is None:
                reader = self.context.peek_reader(split)
                if reader is None:
                    return None
                df, max_tf = reader.term_stats(field, term)
                cap = reader.term_score_cap(field, term)
                stats = (df, max_tf, cap)
                self.context.score_bound_cache.record(
                    split.split_id, field, term, df, max_tf, cap)
            return stats

        return split_best_internal_key(prune_ctx, split,
                                       field_meta_fn=field_meta,
                                       score_stats_fn=score_stats)

    def _classify_group(self, group, search_request, prune_ctx, threshold):
        """(run, skipped, to_count): splits whose bound cannot beat the
        current threshold are skipped (inexact counting) or downgraded to
        count-only requests (exact counting); ties always run."""
        thr = threshold.get() if prune_ctx.mode is not None else None
        if thr is None:
            return list(group), [], []
        run, skipped, to_count = [], [], []
        for split in group:
            best = self._split_bound(prune_ctx, split)
            if best is not None and best < thr:
                (to_count if search_request.count_hits_exact
                 else skipped).append(split)
            else:
                run.append(split)
        return run, skipped, to_count

    def _prepare_group(self, group, doc_mapper, search_request, prune_ctx,
                       threshold):
        """Stage 1 (prefetch-thread-safe): threshold re-check + storage IO,
        plan lowering, and the async H2D transfer for one split group.
        Returns an opaque prepared unit for `_execute_group`:
        (kind, run_group, data, extras) where extras carries the
        threshold-pruned splits (skipped / count-ready / count-prepared)."""
        run_group, skipped, to_count = self._classify_group(
            group, search_request, prune_ctx, threshold)
        count_ready: list[tuple] = []
        count_prepared: list[tuple] = []
        count_request = None
        if to_count:
            # exact counting: the split still owes its hit count — re-issue
            # as a count-only request (max_hits=0) riding the metadata
            # count, the leaf cache, or the k==0 no-sort/no-top-k kernel
            count_request = downgrade_to_count(search_request)
            for split in to_count:
                if self._count_from_metadata(count_request, split):
                    count_ready.append((split, LeafSearchResponse(
                        num_hits=split.num_docs, num_attempted_splits=1,
                        num_successful_splits=1)))
                    continue
                key = canonical_request_key(split.split_id, count_request,
                                            split.time_range)
                cached = self.context.leaf_cache.get(key)
                if cached is not None:
                    count_ready.append((split, cached))
                    continue
                if self.context.agg_cache is not None:
                    # Tier B: the count entry shares the filter digest with
                    # the full request, so a downgraded split whose count
                    # was ever computed (any top-K, sort, or agg variant)
                    # resolves without opening the reader
                    cached_count = self.context.agg_cache.get_count(
                        split.split_id,
                        canonical_filter_digest(count_request,
                                                split.time_range))
                    if cached_count is not None:
                        count_ready.append((split, LeafSearchResponse(
                            num_hits=cached_count, num_attempted_splits=1,
                            num_successful_splits=1)))
                        continue
                count_prepared.extend(self._prepare_per_split(
                    [split], doc_mapper, count_request, prune_ctx=None))
        extras = {"skipped": skipped, "count_ready": count_ready,
                  "count_prepared": count_prepared,
                  "count_request": count_request}
        push_thr = (threshold.get() if prune_ctx.mode is not None else None)
        # the batch path has no search_after pushdown or per-split terms
        # truncation; the per-split path handles those (2-key sorts ride
        # the batch via the lexicographic cross-split re-top-k)
        import json as _json
        # the fused program is the mesh's: its cross-split merge runs as
        # collectives over the devices. One device has no collective to
        # amortise, and stacking the splits there costs more than their
        # programs launched together (_execute_per_split) — so without a
        # mesh the group goes per split and no host stack is ever made
        mesh = self.context.device_mesh(len(run_group))
        if (mesh is not None and not search_request.search_after
                and string_sort_of(search_request, doc_mapper) is None
                and not self._split_caches_route_per_split(search_request)
                and not any(key in _json.dumps(search_request.aggs or {})
                            for key in ("split_size", "shard_size",
                                        "segment_size"))):
            # Batch lanes must be in split_id order: the kernel's
            # cross-split merge breaks sort-value ties by flattened lane
            # index (fanout.mesh_batch_fn / ops.topk.exact_topk_2key), and the
            # collector's total order is (key desc, split_id asc, doc asc).
            # _optimize_split_order and the offload cut reorder/recompose
            # run_group between passes, so an all-ties search would
            # otherwise keep a DIFFERENT tie subset under truncation cold
            # vs warm, breaking cache_cold_equivalence.
            run_group = sorted(run_group, key=lambda s: s.split_id)
            admitted = None
            batch = None
            try:
                readers = [self.context.reader(s) for s in run_group]
                if prune_ctx.mode == "score":
                    for reader, split in zip(readers, run_group):
                        record_split_term_stats(
                            self.context.score_bound_cache, split.split_id,
                            reader, prune_ctx.terms)
                batch = build_batch(
                    search_request, doc_mapper, readers,
                    [s.split_id for s in run_group],
                    absence_sink=self.context.predicate_cache
                    .record_term_absent,
                    sort_value_threshold=push_thr)
                # the mesh was fixed above, before staging: arrays committed
                # for one sharding must not feed an executor traced for
                # another. per-DEVICE admission: each chip pins only its
                # shard of the stacks; column-family bytes are admitted
                # under the mesh-resident stack owner inside
                # stage_device_inputs (and stay warm), so exclude them here
                # when that store will take them
                stack_store = self.context.resident_store
                admitted = self.context.hbm_budget.admit(
                    batch, per_device_bytes(
                        batch, mesh,
                        exclude_stack_resident=(
                            stack_store is not None
                            and stack_store.enabled)))
                stage_device_inputs(  # async transfer starts now
                    batch, mesh, resident_store=stack_store,
                    budget=self.context.hbm_budget)
                return ("batch", run_group, (batch, admitted, mesh), extras)
            except (OverloadShed, TenantRateLimited):
                # whole-query backpressure, not a split failure: falling
                # back per split would just re-admit and shed again
                if admitted is not None and batch is not None:
                    self.context.hbm_budget.release(batch, admitted)
                if batch is not None:
                    release_stack_pin(batch, self.context.hbm_budget)
                raise
            except Exception as exc:  # noqa: BLE001 - fall back per split
                if admitted is not None and batch is not None:
                    self.context.hbm_budget.release(batch, admitted)
                if batch is not None:
                    release_stack_pin(batch, self.context.hbm_budget)
                logger.debug("batch path failed (%s); searching per split", exc)
        return ("per_split", run_group,
                self._prepare_per_split(run_group, doc_mapper, search_request,
                                        prune_ctx=prune_ctx,
                                        sort_value_threshold=push_thr),
                extras)

    def _discard_prepared(self, prepared) -> None:
        """A prefetched group dropped by the deadline must return its
        admitted HBM pins (the per-split path takes none at prepare time —
        only the batch path pre-admits)."""
        kind, _group, data, _extras = prepared
        if kind == "batch":
            batch, admitted, _mesh = data
            self.context.hbm_budget.release(batch, admitted)
            release_stack_pin(batch, self.context.hbm_budget)

    def _prepare_per_split(self, group, doc_mapper, search_request,
                           prune_ctx=None, sort_value_threshold=None):
        prepared = []
        for split in group:
            try:
                with profiled_phase(PHASE_SPLIT_OPEN):
                    reader = self.context.reader(split)
                cache = self.context.predicate_cache
                if prune_ctx is not None and prune_ctx.mode == "score":
                    # remember df/max-tf at split open so future queries
                    # can bound this split before (re)opening it
                    record_split_term_stats(
                        self.context.score_bound_cache, split.split_id,
                        reader, prune_ctx.terms)
                # plan-only (storage IO + lowering): the H2D transfer is
                # deferred to the execute stage so each split's
                # admit→transfer→execute→release cycle runs alone — a whole
                # group admitted up front could exceed the budget and
                # starve itself
                with profiled_phase(PHASE_CACHE_LOOKUP):
                    cache_ctx = self._consult_split_caches(search_request,
                                                           split, reader)
                plan = prepare_plan_only(
                    search_request, doc_mapper, reader, split.split_id,
                    absence_sink=lambda f, t, s=split.split_id:
                        cache.record_term_absent(s, f, t),
                    sort_value_threshold=sort_value_threshold,
                    aggs_override=(cache_ctx or {}).get("aggs_override"),
                    mask_override=(cache_ctx or {}).get("mask"),
                    mask_key=(cache_ctx or {}).get("mask_key"))
                prepared.append((split, reader, plan, None, cache_ctx))
            except (OverloadShed, TenantRateLimited):
                # whole-query backpressure: demoting it to a per-split
                # failure here would turn a typed 429 into a generic 400
                # (same contract as _prepare_group/_execute_per_split)
                raise
            except Exception as exc:  # noqa: BLE001 - partial failure
                prepared.append((split, None, None, exc, None))
        return prepared

    # --- hierarchical leaf caches (Tier A/B, docs/hierarchical-cache.md) --

    def _serve_from_agg_cache(self, request, split):
        """Full Tier B short-circuit: a count/agg-only request (max_hits=0,
        no offset) whose count AND every agg state are cached builds its
        LeafSearchResponse from partials alone — no reader open, no
        staging, no kernel. Any missing piece returns None (the split runs
        normally and refills)."""
        agg_cache = self.context.agg_cache
        if (agg_cache is None or request.max_hits != 0
                or request.start_offset != 0):
            return None
        digest = canonical_filter_digest(request, split.time_range)
        count = agg_cache.get_count(split.split_id, digest)
        if count is None:
            return None
        states: dict[str, Any] = {}
        for name, spec in (request.aggs or {}).items():
            state = agg_cache.get_agg(split.split_id, digest,
                                      agg_shape_digest(spec))
            if state is None:
                return None
            states[name] = state
        return LeafSearchResponse(
            num_hits=count, num_attempted_splits=1, num_successful_splits=1,
            intermediate_aggs=states)

    def _split_caches_route_per_split(self, request) -> bool:
        """True when the Tier A/B caches could serve or warm this request.
        Consults and fills are per-split operations; the fused batch path
        merges its results on-mesh, so a batched group can neither use a
        cached mask nor attribute partials back to one split. Such groups
        route per-split instead — cheap since the resident column store
        keeps warm splits on device either way. Scoring sorts stay fused
        (mask-ineligible: the default sort IS _score and the mask carries
        no BM25 scores) except agg-only requests, where Tier B applies
        regardless of sort. Both kill switches off restores the fused
        routing bit-identically."""
        sort_fields = [s.field for s in request.sort_fields] or ["_score"]
        if self.context.mask_cache is not None and "_score" not in sort_fields:
            return True
        return (self.context.agg_cache is not None and bool(request.aggs)
                and request.max_hits == 0 and request.start_offset == 0)

    def _consult_split_caches(self, request, split, reader):
        """Tier A/B lookups for one split, before lowering. Returns None
        (both tiers off) or a cache_ctx dict driving `prepare_plan_only`
        and the post-execute fill:

        - mask / mask_key: a cached packed predicate mask replaces the
          whole query root (zero predicate columns fetched or staged);
          mask_fill marks a miss to backfill. Scoring requests are
          ineligible — the mask carries no BM25 scores, and the default
          sort IS _score.
        - agg_hits: cached intermediate states attached post-execute;
          aggs_override: the missed subset actually lowered ({} lowers
          none); agg_fill: names to backfill from the response."""
        mask_cache = self.context.mask_cache
        agg_cache = self.context.agg_cache
        if mask_cache is None and agg_cache is None:
            return None
        digest = canonical_filter_digest(request, split.time_range)
        ctx: dict[str, Any] = {
            "digest": digest, "mask": None, "mask_key": None,
            "mask_fill": False, "agg_hits": {}, "aggs_override": None,
            "agg_fill": []}
        sort_fields = [s.field for s in request.sort_fields] or ["_score"]
        if mask_cache is not None and "_score" not in sort_fields:
            packed = mask_cache.get(split.split_id, digest,
                                    packed_mask_nbytes(reader.num_docs_padded))
            if packed is not None:
                ctx["mask"] = packed
                ctx["mask_key"] = f"mask.{digest}"
            else:
                ctx["mask_fill"] = True
        if agg_cache is not None and request.aggs:
            missing: dict[str, Any] = {}
            for name, spec in request.aggs.items():
                state = agg_cache.get_agg(split.split_id, digest,
                                          agg_shape_digest(spec))
                if state is not None:
                    ctx["agg_hits"][name] = state
                else:
                    missing[name] = spec
            if ctx["agg_hits"]:
                ctx["aggs_override"] = missing
            ctx["agg_fill"] = list(missing)
        return ctx

    def _fill_split_caches(self, request, split, plan, device_arrays,
                           response, cache_ctx, owner=None) -> None:
        """Post-execute backfill, while the split's device arrays are still
        pinned. Fills are best-effort: a failure (including injected cache
        faults) degrades to an uncached split, never fails the query."""
        if cache_ctx is None:
            return
        digest = cache_ctx["digest"]
        mask_cache = self.context.mask_cache
        if (mask_cache is not None and cache_ctx.get("mask_fill")
                and plan.count_override is None):
            # count_override marks an impact-prefix-truncated plan (format
            # v3): the kernel never saw the posting tail, so its mask is
            # incomplete — skip the fill, never cache a partial mask
            from .executor import compute_packed_mask
            try:
                # the fill program and its readback are the `mask_fill`
                # phase (inside compute_packed_mask); the puts are below
                host_packed, dev_packed = compute_packed_mask(
                    plan, device_arrays)
                with profiled_phase(PHASE_CACHE_FILL) as rec:
                    if rec is not None:
                        rec["tier"] = "mask"
                    mask_cache.put(split.split_id, digest, host_packed)
                    store = self.context.resident_store
                    if (store is not None and owner is not None
                            and getattr(owner, "_device_array_cache",
                                        None) is not None):
                        # seed the device copy under the SAME key a
                        # mask-hit plan will stage (`mask.<digest>`): the
                        # next warm run finds every array resident and
                        # uploads nothing. Accounted in the store's byte
                        # stats (columns=0: the mask is not a column miss);
                        # the padded/8 bytes ride outside HbmBudget
                        # admission by design — they are noise next to any
                        # column and admission could shed a best-effort
                        # fill
                        owner._device_array_cache[f"mask.{digest}"] = \
                            dev_packed
                        store.note_upload(split.split_id,
                                          int(dev_packed.nbytes), 0)
            except (OverloadShed, TenantRateLimited):
                raise
            except Exception as exc:  # noqa: BLE001 - fill is best-effort
                logger.debug("mask-cache fill failed for %s: %s",
                             split.split_id, exc)
        agg_cache = self.context.agg_cache
        if agg_cache is None:
            return
        try:
            # sound under threshold pushdown and search_after: the kernel
            # computes count/aggs from the FULL filter mask (executor.py);
            # only the hit list is eligibility-restricted
            with profiled_phase(PHASE_CACHE_FILL) as rec:
                if rec is not None:
                    rec["tier"] = "agg"
                agg_cache.put_count(split.split_id, digest,
                                    response.num_hits)
                for name in cache_ctx.get("agg_fill", ()):
                    state = response.intermediate_aggs.get(name)
                    spec = (request.aggs or {}).get(name)
                    if state is not None and spec is not None:
                        agg_cache.put_agg(split.split_id, digest,
                                          agg_shape_digest(spec), state)
        except (OverloadShed, TenantRateLimited):
            raise
        except Exception as exc:  # noqa: BLE001 - fill is best-effort
            logger.debug("agg-cache fill failed for %s: %s",
                         split.split_id, exc)

    def _execute_group(self, prepared, doc_mapper, search_request,
                       collector, prune_ctx, threshold, prune_stats) -> None:
        """Stage 2 (main thread): kernel execution + readback + merge."""
        kind, group, data, extras = prepared
        for split in extras["skipped"]:
            # conclusively handled without execution: zero hits here can
            # reach the top-K (num_hits is a lower bound when
            # count_hits_exact=False, same contract as before)
            prune_stats["pruned"] += 1
            SEARCH_SPLITS_PRUNED_TOTAL.inc()
            collector.add_leaf_response(LeafSearchResponse(
                num_hits=0, num_attempted_splits=1, num_successful_splits=1))
        for _split, response in extras["count_ready"]:
            prune_stats["downgraded"] += 1
            SEARCH_SPLITS_DOWNGRADED_TOTAL.inc()
            collector.add_leaf_response(response)
        if extras["count_prepared"]:
            prune_stats["downgraded"] += len(extras["count_prepared"])
            SEARCH_SPLITS_DOWNGRADED_TOTAL.inc(
                len(extras["count_prepared"]))
            self._execute_per_split(
                extras["count_prepared"], doc_mapper,
                extras["count_request"], collector,
                prune_ctx=None, threshold=None, prune_stats=None)
        if kind == "batch":
            batch, admitted, mesh = data
            try:
                # dispatch and readback are split so the deadline can shed
                # BETWEEN them: the fused kernel may run to completion on
                # device, but a query nobody is waiting for never pays the
                # device->host transfer (scalars die with their buffers)
                dispatched = dispatch_batch(batch, search_request, mesh)
                deadline = current_deadline()
                if deadline is not None and deadline.expired:
                    from ..parallel.fanout import abandon_dispatch
                    from .residency import RESIDENT_READBACKS_SHED
                    # the mesh-dispatch guard (CPU host platform) must
                    # still observe program completion before the next
                    # collective program may enqueue
                    abandon_dispatch(dispatched)
                    RESIDENT_READBACKS_SHED.inc()
                    profile = current_profile()
                    if profile is not None:
                        profile.mark_partial("shed: batch readback")
                    for split_id in batch.split_ids:
                        if split_id:
                            collector.failed_splits.append(SplitSearchError(
                                split_id=split_id,
                                error="deadline exceeded before readback "
                                      "was awaited",
                                retryable=True))
                    return
                merged = readback_batch(dispatched)
                # batch responses cover several splits; cache only the merged
                # unit is wrong per-split, so cache skipped on the batch path
                collector.add_leaf_response(merged)
                return
            except (OverloadShed, TenantRateLimited):
                self.context.hbm_budget.release(batch, admitted)
                admitted = None  # the finally below must not release twice
                raise
            except Exception as exc:  # noqa: BLE001 - fall back per split
                logger.debug("batch execute failed (%s); per split", exc)
                # release BEFORE the per-split prepares re-admit: under a
                # tight budget the fallback would otherwise wait on its own
                # still-pinned batch bytes
                self.context.hbm_budget.release(batch, admitted)
                admitted = None
                release_stack_pin(batch, self.context.hbm_budget)
                data = self._prepare_per_split(
                    group, doc_mapper, search_request, prune_ctx=prune_ctx,
                    sort_value_threshold=(threshold.get()
                                          if prune_ctx.mode is not None
                                          else None))
            finally:
                if admitted is not None:
                    self.context.hbm_budget.release(batch, admitted)
                # idempotent: converts the stack pin to resident exactly
                # once, whichever exit path ran first
                release_stack_pin(batch, self.context.hbm_budget)
        self._execute_per_split(data, doc_mapper, search_request, collector,
                                prune_ctx=prune_ctx, threshold=threshold,
                                prune_stats=prune_stats)

    def _execute_per_split(self, data, doc_mapper, search_request, collector,
                           prune_ctx=None, threshold=None,
                           prune_stats=None) -> None:
        """The group's prepared splits as one wave: every split that is
        still to run launches at once, each on a worker of its own, so no
        program waits for another split's readback before it is enqueued
        on the device. The calling thread then merges the outcomes in
        split-id order and publishes the threshold once for the group, as
        the fused route does. A group of one runs here, on the calling
        thread."""
        deadline = current_deadline()
        cancel = current_cancel_token()
        profile = current_profile()
        # one outcome a split: a LeafSearchResponse or a SplitSearchError
        outcomes: list = []
        wave: list[int] = []
        for item in data:
            split, _reader, _plan, prep_error, _cache_ctx = item
            outcome = None
            if cancel is not None and cancel.cancelled:
                # cancelled before the wave: unexecuted splits are reported
                # as non-retryable cancel failures (the root must not spend
                # its retry pool re-running work the caller abandoned)
                outcome = SplitSearchError(
                    split_id=split.split_id,
                    error=f"query cancelled before split executed"
                          f"{': ' + cancel.reason if cancel.reason else ''}",
                    retryable=False)
            elif deadline is not None and deadline.expired:
                if profile is not None:
                    profile.mark_partial("shed: split execute")
                outcome = SplitSearchError(
                    split_id=split.split_id,
                    error="deadline exceeded before split executed at leaf",
                    retryable=True)
            elif prep_error is not None:
                _warn_split_failure("prepare", split.split_id, prep_error)
                outcome = SplitSearchError(
                    split_id=split.split_id, error=str(prep_error),
                    retryable=True)
            elif (prune_ctx is not None and prune_ctx.mode is not None
                    and threshold is not None
                    and not search_request.count_hits_exact):
                # execute-time re-check: the threshold may have risen past
                # this split's bound since the prefetch thread prepared it
                # (wasted prepare IO is the price of overlap, never wrong
                # results)
                thr = threshold.get()
                if thr is not None:
                    best = self._split_bound(prune_ctx, split)
                    if best is not None and best < thr:
                        if prune_stats is not None:
                            prune_stats["pruned"] += 1
                        SEARCH_SPLITS_PRUNED_TOTAL.inc()
                        outcome = LeafSearchResponse(
                            num_hits=0, num_attempted_splits=1,
                            num_successful_splits=1)
            if outcome is None:
                wave.append(len(outcomes))
            outcomes.append(outcome)

        def run(slot: int) -> None:
            try:
                outcomes[slot] = self._search_prepared_split(
                    data[slot], doc_mapper, search_request, threshold)
            # qwlint: disable-next-line=QW004 - whole-query backpressure
            # crosses the thread hop as a value and is re-raised below
            except (OverloadShed, TenantRateLimited) as exc:
                outcomes[slot] = exc

        if len(wave) == 1:
            run(wave[0])
        elif wave:
            SPLIT_WAVE_WIDTH.observe(len(wave))
            if profile is not None:
                profile.set_counter("split_wave_width", float(len(wave)))
            # a worker's span stack is empty: capture the traceparent HERE
            # so each split's spans join this query's trace (same capture
            # as the offload dispatch and root _fan_out)
            wave_tp = TRACER.current_traceparent()

            def run_traced(slot: int) -> None:
                with TRACER.span("leaf_split",
                                 {"split_id": data[slot][0].split_id},
                                 remote_parent=wave_tp):
                    run(slot)

            # run_with_context: the workers must see the query's deadline,
            # tenant, profile and cancel token (one snapshot, replayed into
            # a fresh context by every worker)
            target = run_with_context(run_traced)
            workers = [sync.thread(target=target, args=(slot,),
                                   name="leaf-split-wave", daemon=True)
                       for slot in wave]
            for worker in workers:
                worker.start()
            for worker in workers:
                worker.join()
        for _item, outcome in sorted(zip(data, outcomes),
                                     key=lambda pair: pair[0][0].split_id):
            if isinstance(outcome, (OverloadShed, TenantRateLimited)):
                # a shed/rate-limited tenant is rejected as a WHOLE query
                # (429 + Retry-After at the API layer) — recording it as a
                # retryable split failure would make the root burn retries
                # on work the controller just refused. Every worker has
                # returned its pins by now.
                raise outcome
            if isinstance(outcome, SplitSearchError):
                collector.failed_splits.append(outcome)
            else:
                collector.add_leaf_response(outcome)
        if threshold is not None:
            # monotone: a group that merged nothing publishes what stood
            threshold.update(collector.sort_value_threshold())

    def _search_prepared_split(self, item, doc_mapper, search_request,
                               threshold):
        """One split's unit of the wave, on whichever thread runs it:
        warm-up → execute (through the query batcher, so same-split queries
        of other requests still stack) → Tier A/B fills → leaf-cache put,
        with the pins returned before it ends. Gives the split's response,
        or its failure as a SplitSearchError; whole-query backpressure
        (OverloadShed, TenantRateLimited) is raised."""
        from .leaf import warmup_device_arrays
        split, reader, plan, _prep_error, cache_ctx = item
        admitted = 0
        warmed = False
        owner = reader
        try:
            device_arrays, admitted, owner = warmup_device_arrays(
                reader, plan, self.context.hbm_budget,
                store=self.context.resident_store,
                split_id=split.split_id)
            warmed = True
            response = execute_prepared_split(
                search_request, doc_mapper, reader, split.split_id,
                plan, device_arrays,
                batcher=self.context.query_batcher,
                threshold_box=threshold,
                fault_injector=self.context.fault_injector)
            if cache_ctx is not None and cache_ctx["agg_hits"]:
                # Tier B hits join the response BEFORE the leaf-cache
                # put and the merge — the cached LeafSearchResponse
                # must be complete, and the collector merges by name
                response.intermediate_aggs.update(cache_ctx["agg_hits"])
            self._fill_split_caches(search_request, split, plan,
                                    device_arrays, response, cache_ctx,
                                    owner=owner)
            if plan.threshold_slot < 0:
                # a threshold-pushdown response may have its hit list
                # truncated below k — correct for THIS query's merge,
                # poison for a future query with a lower threshold
                with profiled_phase(PHASE_CACHE_FILL) as rec:
                    if rec is not None:
                        rec["tier"] = "leaf"
                    key = canonical_request_key(
                        split.split_id, search_request,
                        split.time_range)
                    self.context.leaf_cache.put(key, response)
            return response
        except (OverloadShed, TenantRateLimited):
            raise
        except CancelledQuery as exc:
            # NEVER retryable: the caller asked for the query to stop
            return SplitSearchError(
                split_id=split.split_id, error=str(exc), retryable=False)
        except Exception as exc:  # noqa: BLE001 - partial failure semantics
            _warn_split_failure("search", split.split_id, exc)
            return SplitSearchError(
                split_id=split.split_id, error=str(exc), retryable=True)
        finally:
            if warmed:  # failed warmups release their own pins
                # releasing against the residency OWNER (not the reader)
                # is what moves the pins to resident instead of freeing
                # them: the owner carries `_device_array_cache`
                self.context.hbm_budget.release(owner, admitted)

    @staticmethod
    def _optimize_split_order(request: SearchRequest,
                              splits: list[SplitIdAndFooter]) -> list[SplitIdAndFooter]:
        """Reference `CanSplitDoBetter::optimize_split_order` (leaf.rs:1279):
        timestamp sorts visit the splits most likely to own the top hits
        first (enables pruning + better partial results under timeouts)."""
        sort = request.sort_fields[0] if request.sort_fields else None
        if sort is None or not splits:
            return list(splits)
        if sort.field == "_score":
            return sorted(splits, key=lambda s: -s.num_docs)
        def end_key(s: SplitIdAndFooter):
            return s.time_range[1] if s.time_range else 0
        def start_key(s: SplitIdAndFooter):
            return s.time_range[0] if s.time_range else 0
        if sort.order == "desc":
            return sorted(splits, key=end_key, reverse=True)
        return sorted(splits, key=start_key)

    # ------------------------------------------------------------------
    def fetch_docs(self, request: FetchDocsRequest) -> list[dict[str, Any]]:
        reader = self.context.reader(request.split)
        docs = reader.fetch_docs(request.doc_ids)
        if request.snippet_fields and request.query_ast is not None:
            from .snippets import generate_snippets
            for doc in docs:
                doc["_snippets"] = generate_snippets(
                    doc, request.snippet_fields, request.query_ast)
        return docs


class LocalSearchClient:
    """In-process transport to a SearchService (the tests' and single-node
    deployments' client; the HTTP client in serve/ has the same surface)."""

    def __init__(self, service: SearchService):
        self.service = service

    def leaf_search(self, request: LeafSearchRequest) -> LeafSearchResponse:
        return self.service.leaf_search(request)

    def fetch_docs(self, request: FetchDocsRequest) -> list[dict[str, Any]]:
        return self.service.fetch_docs(request)
