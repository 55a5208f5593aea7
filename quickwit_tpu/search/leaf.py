"""Leaf search: one split, end to end on device.

Role of the reference's `leaf_search_single_split` (`quickwit-search/src/
leaf.rs:657`): open the split (footer GET → reader), lower the query
(`doc_mapper.query` analogue), warm up (fetch + device-transfer exactly the
arrays the plan needs), execute the jitted kernel, and emit a mergeable
`LeafSearchResponse`.

Device-array residency is cached per split reader (the role of the
fast-field/hotcache byte caches): repeated queries touching the same
postings/columns skip both storage IO (ByteRangeCache) and host→HBM copies.
"""

from __future__ import annotations

import time
from typing import Any, Optional

import jax
import numpy as np

from ..models.doc_mapper import DocMapper, FieldType
from ..index.reader import SplitReader
from ..observability.profile import (
    PHASE_PLAN_BUILD, PHASE_STAGING_CACHE_HIT, PHASE_STAGING_UPLOAD,
    PHASE_TOPK_MERGE, current_profile, profile_add, profiled_phase,
)
from ..observability.metrics import (
    PREDICATE_STAGED_BYTES_TOTAL, STAGING_BYTES_TOTAL,
)
from ..ops.aggs import PCTL_NUM_BUCKETS
from ..query.aggregations import parse_aggs
from .executor import execute_plan
from .models import LeafSearchResponse, PartialHit, SearchRequest, SplitSearchError
from .plan import (BucketAggExec, CompositeAggExec, MetricAggExec,
                   lower_request, predicate_only_slots)


from ..ops.topk import MISSING_VALUE_SENTINEL
from .hostdecode import host_array, host_float, host_int, host_list


def decode_raw_sort_value(internal: float, sort_field: str, sort_order: str,
                          sort_is_int: bool, score: float, doc_id: int):
    """Internal higher-is-better key → displayed raw sort value.

    Shared by the single-split and batched decode paths so the sort-key
    encoding lives in exactly one place."""
    if sort_field == "_score":
        return host_float(score)
    if sort_field == "_doc":
        return doc_id
    if internal <= MISSING_VALUE_SENTINEL:
        return None
    raw = internal if sort_order == "desc" else -internal
    return host_int(raw) if sort_is_int else raw


def decode_sort_value_exact(internal: float, sort_field: str,
                            sort_order: str, sort_is_int: bool,
                            score: float, doc_id: int, exact_col):
    """`decode_raw_sort_value` + the exact 64-bit column re-read for int
    sorts (internal f64 keys round at 2^53) — the one decode used for
    primary AND secondary keys on both the per-split and batched paths."""
    raw = decode_raw_sort_value(internal, sort_field, sort_order,
                                sort_is_int, score, doc_id)
    if raw is not None and sort_is_int and exact_col is not None:
        # exact_col is the reader's mmap'd host column, never device data
        return host_int(exact_col[doc_id])
    return raw


def _device_cache(reader: SplitReader) -> dict[str, Any]:
    cache = getattr(reader, "_device_array_cache", None)
    if cache is None:
        cache = reader._device_array_cache = {}
    return cache


def warmup_device_arrays(reader: SplitReader, plan, budget=None,
                         store=None, split_id: Optional[str] = None
                         ) -> tuple[list, int, Any]:
    """Host→device transfer of the plan's arrays, with cross-query reuse
    (role of `warmup`, `leaf.rs:304`). With an `HbmBudget`, the exact NEW
    transfer bytes are admitted (blocking while over budget) BEFORE any
    device_put — the byte-accurate SearchPermitProvider role. FOR-packed
    columns (format v2) reach this point as their narrow u8/u16/u32 delta
    lanes, so `arr.nbytes` admits the COMPACT device footprint — the
    packing's HBM win flows through admission with no special casing.

    With a `ResidentColumnStore` (`store` + `split_id`), residency keys on
    the split id — the `SplitColumns` owner survives reader reopens, warm
    repeat queries perform ZERO column device_put (profiled as the
    `staging_cache_hit` phase), and only cold columns ride one batched
    `device_put` (`staging_upload`). Without a store, the legacy
    per-reader cache applies and residency dies with the reader.

    Returns (device_arrays, admitted_bytes, owner); the caller releases
    `owner` (NOT necessarily the reader) after execution. The returned
    list holds plain references, so a concurrent LRU eviction clearing the
    cache cannot corrupt this query's execution."""
    if store is not None and split_id is not None:
        owner = store.columns_for(split_id)
        cache = owner._device_array_cache
    else:
        owner = reader
        cache = _device_cache(reader)
    missing = [(slot, key, arr)
               for slot, (key, arr) in enumerate(zip(plan.array_keys,
                                                     plan.arrays))
               if key not in cache]
    staging_bytes = sum(arr.nbytes for _, _, arr in missing)
    if missing:
        STAGING_BYTES_TOTAL.inc(staging_bytes)
        # predicate-only attribution: the bytes a mask-cache hit avoids.
        # tests/test_hierarchical_cache.py asserts "zero predicate staging
        # when warm" on exactly this counter.
        pred_slots = predicate_only_slots(plan)
        predicate_bytes = sum(arr.nbytes for slot, _, arr in missing
                              if slot in pred_slots)
        if predicate_bytes:
            PREDICATE_STAGED_BYTES_TOTAL.inc(predicate_bytes)
            profile_add("predicate_staging_bytes", predicate_bytes)
    admitted = 0
    if budget is not None:
        # pins the owner even when nothing is missing (zero-byte
        # admission): its cached device arrays are in use and must not be
        # evicted mid-query
        admitted = budget.admit(owner, staging_bytes)
    try:
        if missing:
            # one batched host→device transfer (each separate device_put
            # pays its own dispatch overhead). The staging phase
            # times the transfer DISPATCH (device_put is async; completion
            # overlaps into the execute phase by design).
            with profiled_phase(PHASE_STAGING_UPLOAD) as rec:
                if rec is not None:
                    rec["bytes"] = staging_bytes
                    rec["arrays"] = len(missing)
                transferred = jax.device_put([arr for _, _, arr in missing])
            profile_add("staging_bytes", staging_bytes)
            for (_, key, _), dev in zip(missing, transferred):
                cache[key] = dev
            if store is not None and split_id is not None:
                store.note_upload(split_id, staging_bytes, len(missing))
                store.note_hits(len(plan.array_keys) - len(missing),
                                full=False)
        else:
            # the whole plan is device-resident: no transfer, no staging —
            # the phase records the skip (bytes served, none moved)
            with profiled_phase(PHASE_STAGING_CACHE_HIT) as rec:
                if rec is not None:
                    rec["bytes"] = 0
                    rec["bytes_resident"] = sum(a.nbytes
                                                for a in plan.arrays)
                    rec["arrays"] = len(plan.array_keys)
            if store is not None and split_id is not None:
                store.note_hits(len(plan.array_keys), full=True)
        return [cache[key] for key in plan.array_keys], admitted, owner
    except BaseException:
        if budget is not None:
            budget.release(owner, admitted, to_resident=False)
        raise


def prepare_plan_only(
    request: SearchRequest,
    doc_mapper: DocMapper,
    reader: SplitReader,
    split_id: str,
    absence_sink=None,
    sort_value_threshold: Optional[float] = None,
    aggs_override: Optional[dict] = None,
    mask_override=None,
    mask_key: Optional[str] = None,
):
    """Stage 1a: storage byte-range IO + plan lowering WITHOUT the device
    transfer. The service's per-split path defers H2D to the execute
    stage so each split's admit→transfer→execute→release cycle runs
    alone — a whole group admitted up front could exceed the budget and
    starve itself.

    `sort_value_threshold` (internal higher-is-better key) is pushed into
    the plan as a traced scalar masking sub-threshold docs before top_k
    (search/pruning.py); the plan signature only encodes its PRESENCE, so
    compiled executables are reused across threshold values.

    Hierarchical-cache hooks (search/service.py::_consult_split_caches):
    `aggs_override` replaces the request's agg dict — the partial-agg tier
    passes only the aggs it MISSED, so cached ones are neither lowered nor
    staged nor computed ({} lowers none at all). `mask_override`/`mask_key`
    forward a cached packed predicate mask to `lower_request`, which then
    skips query lowering and every predicate column."""
    aggs_dict = request.aggs if aggs_override is None else aggs_override
    agg_specs = parse_aggs(aggs_dict) if aggs_dict else []
    sort = request.sort_fields[0] if request.sort_fields else None
    sort_field = sort.field if sort else "_score"
    sort_order = sort.order if sort else "desc"
    sort2 = request.sort_fields[1] if len(request.sort_fields) > 1 else None
    # plan_build covers storage byte-range IO (footer/postings/column
    # reads surface as storage_read_* counters) plus the lowering itself
    with profiled_phase(PHASE_PLAN_BUILD) as rec:
        if rec is not None:
            rec["split_id"] = split_id
        return lower_request(
            request.query_ast, doc_mapper, reader, agg_specs,
            sort_field=sort_field, sort_order=sort_order,
            sort2_field=sort2.field if sort2 else None,
            sort2_order=sort2.order if sort2 else "desc",
            start_timestamp=request.start_timestamp,
            end_timestamp=request.end_timestamp,
            search_after=search_after_marker(request, split_id, sort_field,
                                             sort_order, sort2,
                                             doc_mapper=doc_mapper,
                                             reader=reader),
            absence_sink=absence_sink,
            sort_value_threshold=sort_value_threshold,
            mask_override=mask_override,
            mask_key=mask_key,
        )


def prepare_single_split(
    request: SearchRequest,
    doc_mapper: DocMapper,
    reader: SplitReader,
    split_id: str,
    absence_sink=None,
    budget=None,
    store=None,
) -> tuple[Any, list, int]:
    """Stage 1 of leaf search — everything up to (and including) starting
    the host→device transfer: storage byte-range IO via the reader, plan
    lowering, and the async `device_put`. Runs on a prefetch thread so the
    next split batch's IO overlaps the current batch's kernel execution
    (SURVEY hard-part #4: warmup/compute pipelining)."""
    plan = prepare_plan_only(request, doc_mapper, reader, split_id,
                             absence_sink)
    # device_put is async: the transfer proceeds while the caller executes
    # the previous batch's kernel
    device_arrays, admitted, _owner = warmup_device_arrays(
        reader, plan, budget, store=store, split_id=split_id)
    return plan, device_arrays, admitted


def leaf_search_single_split(
    request: SearchRequest,
    doc_mapper: DocMapper,
    reader: SplitReader,
    split_id: str,
) -> LeafSearchResponse:
    plan, device_arrays, _ = prepare_single_split(request, doc_mapper,
                                                  reader, split_id)
    return execute_prepared_split(request, doc_mapper, reader, split_id,
                                  plan, device_arrays)


def execute_prepared_split(
    request: SearchRequest,
    doc_mapper: DocMapper,
    reader: SplitReader,
    split_id: str,
    plan: Any,
    device_arrays: list,
    batcher=None,
    threshold_box=None,
    fault_injector=None,
) -> LeafSearchResponse:
    """Stage 2: jitted kernel execution + the single batched readback.
    With a `QueryBatcher`, concurrent same-structure queries on this split
    share one vmapped dispatch (see search/batcher.py). Work that profiles
    past the chunk-sizer target runs as a resumable chunked scan instead
    (search/chunkexec.py): cancellable/preemptable at every chunk boundary,
    with cross-chunk early termination fed by `threshold_box`."""
    from ..common.deadline import current_deadline
    from ..tenancy.context import effective_tenant
    from .chunkexec import PREEMPT_GATE, maybe_execute_chunked
    ambient = current_deadline()
    if ambient is not None:
        # shed before launching a kernel whose result nobody can use; the
        # service turns this into a typed, retryable SplitSearchError
        ambient.check(f"leaf split {split_id} execute")
    t0 = time.monotonic()
    sort = request.sort_fields[0] if request.sort_fields else None
    sort_field = sort.field if sort else "_score"
    sort_order = sort.order if sort else "desc"
    sort2 = request.sort_fields[1] if len(request.sort_fields) > 1 else None
    # k=0 (count/agg-only): the executor skips keying and top-k entirely
    k = request.start_offset + request.max_hits
    if plan.threshold_slot >= 0:
        from ..observability.metrics import SEARCH_KERNEL_THRESHOLD_TOTAL
        SEARCH_KERNEL_THRESHOLD_TOTAL.inc()
        profile_add("kernel_threshold_pushdowns")
    # fused splits register with the preempt gate too: their presence is
    # what tells a running chunked scan that interactive work is waiting
    with PREEMPT_GATE.running(effective_tenant().priority):
        from .batcher import qbatch_enabled
        if batcher is not None and qbatch_enabled():
            # query-axis stacking: the batcher must see the query BEFORE
            # the chunked check so distinct shape-compatible queries can
            # group; solo riders and formed groups both keep resumable
            # chunked semantics inside the batcher (execute_group_chunked
            # / maybe_execute_chunked)
            result = batcher.execute(plan, k, device_arrays,
                                     split_key=id(reader),
                                     threshold_box=threshold_box,
                                     fault_injector=fault_injector)
        else:
            result = maybe_execute_chunked(plan, k, device_arrays,
                                           threshold_box=threshold_box,
                                           fault_injector=fault_injector)
            if result is None:
                if batcher is not None:
                    result = batcher.execute(plan, k, device_arrays,
                                             split_key=id(reader))
                else:
                    result = execute_plan(plan, k, device_arrays)
    # cancelled mid-scan with partial_on_cancel: keep the chunks already
    # merged, flag the split so the root's response carries cancelled=true
    # qwlint: disable-next-line=QW001 - "partial" is a host bool stamped by
    # the chunked scan's boundary loop, never a device value
    partial_cancel = bool(result.get("partial"))

    count = result["count"]
    if getattr(plan, "count_override", None) is not None:
        # impact prefix cutoff (plan.py): the kernel only saw the live
        # prefix of a single bare term's postings, so its count is a
        # truncation artifact — the exact match count is the term's df
        count = plan.count_override
    profile = current_profile()
    t_merge = time.monotonic()
    num_hits_returned = min(k, count)
    partial_hits = []
    # text-field sort: internal keys are split-local dictionary ordinals —
    # decode to term strings here (the reference's leaf likewise returns
    # term bytes); collector merges on the strings
    text_dict = (reader.column_dict(plan.sort_text_field)
                 if plan.sort_text_field else None)
    sort_is_int = _sort_values_are_int(doc_mapper, sort_field)
    sort2_is_int = (_sort_values_are_int(doc_mapper, sort2.field)
                    if sort2 else False)
    # exact 64-bit display values: internal keys are f64 (2^53 mantissa),
    # so i64/u64 values near ±2^63 round — re-read the exact column value
    # host-side for the k returned hits (the reference returns exact
    # tantivy column values in hits[].sort)
    exact_col = (reader.column_values(sort_field)[0]
                 if sort_is_int and text_dict is None else None)
    exact_col2 = (reader.column_values(sort2.field)[0]
                  if sort2 is not None and sort2_is_int else None)
    # bulk .tolist() pre-decode: the packed readback already pulled these
    # to host, so ONE conversion per array replaces a per-hit int()/float()
    # in the loop below (everything past here touches Python scalars only)
    sort_values = host_list(result["sort_values"][:num_hits_returned])
    doc_ids = host_list(result["doc_ids"][:num_hits_returned])
    scores = host_list(result["scores"][:num_hits_returned])
    values2 = result.get("sort_values2")
    if values2 is not None:
        values2 = host_list(values2[:num_hits_returned])
    for i in range(num_hits_returned):
        internal = sort_values[i]
        if internal == float("-inf"):
            break  # fewer eligible hits than k (search_after pushdown)
        doc_id = doc_ids[i]
        if text_dict is not None:
            if internal == MISSING_VALUE_SENTINEL:
                raw = None
            else:
                ordinal = host_int(internal if sort_order == "desc"
                                   else -internal)
                raw = text_dict[ordinal]
        else:
            raw = decode_sort_value_exact(
                internal, sort_field, sort_order, sort_is_int,
                scores[i], doc_id, exact_col)
        internal2, raw2 = 0.0, None
        if sort2 is not None and values2 is not None:
            internal2 = values2[i]
            raw2 = decode_sort_value_exact(
                internal2, sort2.field, sort2.order, sort2_is_int,
                scores[i], doc_id, exact_col2)
        partial_hits.append(PartialHit(
            sort_value=internal, split_id=split_id, doc_id=doc_id,
            raw_sort_value=raw, sort_value2=internal2, raw_sort_value2=raw2))

    intermediate_aggs = _intermediate_aggs(plan, result["aggs"])
    if profile is not None:
        # host-side top-K decode + agg-state extraction for this split
        profile.record_phase(PHASE_TOPK_MERGE,
                             time.monotonic() - t_merge, start=t_merge,
                             split_id=split_id, hits=len(partial_hits))
    # qwlint: disable-next-line=QW001 - time.monotonic() arithmetic, host
    elapsed = int((time.monotonic() - t0) * 1e6)
    return LeafSearchResponse(
        num_hits=count,
        partial_hits=partial_hits,
        # a partial-on-cancel split still counts as successful (its hits are
        # real and mergeable); the cancel marker below is what flips the
        # root response to cancelled=true without tripping the
        # every-split-failed guard
        num_attempted_splits=1,
        num_successful_splits=1,
        failed_splits=([SplitSearchError(
            split_id=split_id,
            error="query cancelled: progressive partial results up to the "
                  "last completed chunk boundary",
            retryable=False)] if partial_cancel else []),
        intermediate_aggs=intermediate_aggs,
        resource_stats={"cpu_micros": elapsed},
    )


def search_after_marker(request: SearchRequest, split_id: str,
                        sort_field: str, sort_order: str, sort2=None,
                        doc_mapper=None, reader=None):
    """(internal_value, internal_value2|None, relation, marker_doc) for this
    split, or None.

    A hit qualifies iff key < m, or key == m and (split, doc) > (m_split,
    m_doc); the split relation is static per split:
      split < m_split  → strictly-less ("lt")
      split == m_split → less-or-doc-tie ("lt_tie")
      split > m_split  → less-or-equal ("le")

    String markers (text-field sorts): internal keys are SPLIT-LOCAL
    dictionary ordinals, so the raw term string translates per split via
    binary search in the column dict; a term absent from this split maps
    to the half-ordinal between its neighbors (f64 keys compare exactly),
    with tie relations impossible by construction.
    """
    if not request.search_after:
        return None
    sa = list(request.search_after)
    # search_after markers are request-JSON scalars (wire data, never
    # device arrays) — decode through the audited host seam
    if sort2 is not None and len(sa) == 4:
        raw, raw2, m_split, m_doc = sa[0], sa[1], sa[2], host_int(sa[3])
    else:
        raw, raw2, m_split, m_doc = sa[0], None, sa[1], host_int(sa[2])
    if m_split is not None:
        m_split = str(m_split)

    string_sort = None
    if doc_mapper is not None:
        from .models import string_sort_of
        string_sort = string_sort_of(request, doc_mapper)

    def encode_string(value: str, order: str) -> float:
        import bisect
        terms = reader.column_dict(sort_field)
        index = bisect.bisect_left(terms, value)
        if index < len(terms) and terms[index] == value:
            ordinal = host_float(index)     # exact: tie relations apply
        else:
            ordinal = index - 0.5           # between neighbors: no ties
        return ordinal if order == "desc" else -ordinal

    def encode(value, field, order):
        if value is None:
            return MISSING_VALUE_SENTINEL
        if string_sort is not None and field == sort_field \
                and isinstance(value, str):
            return encode_string(value, order)
        return (host_float(value) if order == "desc"
                else -host_float(value))

    internal = encode(raw, sort_field, sort_order)
    internal2 = (encode(raw2, sort2.field, sort2.order)
                 if sort2 is not None else None)
    if m_split is None:
        # value-only ES marker: strictly after the value in every split
        relation = "lt"
    elif split_id < m_split:
        relation = "lt"
    elif split_id == m_split:
        relation = "lt_tie"
    else:
        relation = "le"
    return (internal, internal2, relation, m_doc)


def _sort_values_are_int(doc_mapper: DocMapper, sort_field: str) -> bool:
    fm = doc_mapper.field(sort_field)
    return fm is not None and fm.type in (
        FieldType.I64, FieldType.U64, FieldType.DATETIME, FieldType.BOOL, FieldType.IP)


def _truncate_terms_state(state: dict[str, Any]) -> None:
    """Per-split `split_size` truncation (reference/tantivy shard_size
    semantics): forward only the top-N buckets by count; the largest
    dropped count becomes this split's doc_count_error_upper_bound
    contribution (error bounds sum at merge)."""
    counts = host_array(state["counts"])
    split_size = host_int(state["split_size"])
    nonzero = host_int((counts > 0).sum())
    if nonzero <= split_size:
        state["error_bound"] = 0
        return
    order = np.argsort(-counts, kind="stable")
    dropped_max = host_int(counts[order[split_size]])
    kept = np.zeros_like(counts)
    kept_idx = order[:split_size]
    kept[kept_idx] = counts[kept_idx]
    state["error_bound"] = dropped_max
    # ES/tantivy compute sum_other_doc_count from the FULL per-split doc
    # total, not just forwarded buckets — carry the dropped mass
    state["other_docs"] = host_int(counts.sum() - kept.sum())
    state["counts"] = kept


def _sub_state(child, res) -> dict[str, Any]:
    """Mergeable state of one nested bucket child: counts/metrics over
    the FLATTENED (ancestor-radix) space, plus its own children."""
    state = {
        "name": child.name,
        "kind": "terms" if child.kind == "terms_mv" else child.kind,
        "nb": child.num_buckets,
        "counts": host_array(res["counts"]),
        "metrics": {name: {k: host_array(v) for k, v in m.items()}
                    for name, m in res["metrics"].items()},
        "metric_kinds": {m.name: m.kind for m in child.metrics},
        "metric_percents": {m.name: list(m.percents) for m in child.metrics
                            if m.kind == "percentiles"},
        "metric_keyed": {m.name: m.keyed for m in child.metrics},
        **child.host_info,
    }
    if child.subs and "subs" in res:
        state["subs"] = [_sub_state(grandchild, grand_res)
                        for grandchild, grand_res
                        in zip(child.subs, res["subs"])]
    return state


def _intermediate_aggs(plan, agg_results: list) -> dict[str, Any]:
    """Device outputs + host_info → the mergeable intermediate agg states
    (role of the reference's serialized intermediate aggregation results)."""
    out: dict[str, Any] = {}
    for a, res in zip(plan.aggs, agg_results):
        if isinstance(a, BucketAggExec):
            state: dict[str, Any] = {
                # terms_mv is an execution detail; the mergeable state is a
                # plain terms state (counts over the ordinal space)
                "kind": "terms" if a.kind == "terms_mv" else a.kind,
                "counts": host_array(res["counts"]),
                "metrics": {name: {k: host_array(v) for k, v in m.items()}
                            for name, m in res["metrics"].items()},
                "metric_kinds": {m.name: m.kind for m in a.metrics},
                "metric_percents": {m.name: list(m.percents) for m in a.metrics
                                    if m.kind == "percentiles"},
                "metric_keyed": {m.name: m.keyed for m in a.metrics},
                **a.host_info,
            }
            if (a.kind == "terms" and state.get("split_size")
                    and state.get("order_target", "_count") == "_count"):
                # split_size truncation keeps top-N by count — unsound
                # under _key/metric ordering (the globally-first bucket
                # could rank low by count in every split), so those
                # orders forward exact per-split states instead
                _truncate_terms_state(state)
            if a.subs and "subs" in res:
                state["subs"] = [_sub_state(child, child_res)
                                 for child, child_res
                                 in zip(a.subs, res["subs"])]
            out[a.name] = state
        elif isinstance(a, CompositeAggExec):
            run_keys = host_array(res["run_keys"])       # [S, k_runs]
            counts = host_array(res["counts"])
            src_infos = a.host_info["sources"]
            metric_kinds = a.host_info.get("metric_kinds", {})
            res_metrics = {name: {k: host_array(v) for k, v in m.items()}
                           for name, m in res.get("metrics", {}).items()}
            buckets = []
            for j in range(run_keys.shape[1]):
                if counts[j] <= 0:
                    continue
                values = []
                for si, info in enumerate(src_infos):
                    enc = host_int(run_keys[si, j])
                    if enc == 0:
                        values.append(None)
                        continue
                    idx = enc // 2 - 1
                    if info["kind"] == "terms":
                        values.append(info["keys"][idx])
                    else:  # histogram kinds decode to absolute keys
                        values.append(info["origin"] + idx * info["interval"])
                entry = [values, host_int(counts[j])]
                if res_metrics or a.subs:
                    entry.append({
                        name: {k: (host_float(v[j]) if k != "count"
                                   else host_int(v[j]))
                               for k, v in state.items()}
                        for name, state in res_metrics.items()})
                if a.subs:
                    # run index: the collector decodes this bucket's
                    # children out of the flattened child states below
                    entry.append(j)
                buckets.append(entry)
            state_out = {
                "kind": "composite", "buckets": buckets,
                "size": a.host_info["size"],
                "metric_kinds": dict(metric_kinds),
                "sources": [{"name": i["name"], "kind": i["kind"]}
                            for i in src_infos],
            }
            if a.subs and "subs" in res:
                state_out["subs"] = [
                    _sub_state(child, child_res)
                    for child, child_res in zip(a.subs, res["subs"])]
            out[a.name] = state_out
        elif isinstance(a, MetricAggExec):
            met = a.metric
            if met.kind == "percentiles":
                out[a.name] = {"kind": "percentiles",
                               "sketch": host_array(res["sketch"]),
                               "percents": list(met.percents),
                               "keyed": met.keyed}
            elif met.kind == "cardinality":
                out[a.name] = {"kind": "cardinality",
                               "hll": host_array(res["hll"])}
            else:
                out[a.name] = {"kind": met.kind, "state": host_array(res["stats"])}
    return out
