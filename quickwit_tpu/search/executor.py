"""Jitted plan execution — the TPU leaf-search hot loop.

Role of the reference's `searcher.search(&query, &collector)` box
(`leaf.rs:853-875`: posting decode → boolean combine → BM25 → top-K +
aggregations on a rayon pool): here the whole box is **one XLA program**
assembled from the LoweredPlan:

    masks = scatter(postings)         # ops/masks.py; a dense term's
    scores = scatter-add(bm25(tfs))   # ops/bm25.py;  tf lane is read in place
    bool combine = elementwise VPU ops
    top-k = lax.top_k over dense keys # ops/topk.py
    aggs = scatter-add bucket states  # ops/aggs.py

Compiled executables are cached by plan *structure* signature — the arrays,
idf/bound scalars, and doc counts are traced inputs, so two different term
queries with the same shape reuse one compilation.
"""

from __future__ import annotations

from collections import OrderedDict
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from ..common.clock import monotonic as _clock_monotonic
from ..index.format import ZONEMAP_BLOCK
from ..observability.profile import (
    PHASE_COMPILE, PHASE_DISPATCH_PREPARE, PHASE_EXECUTE, PHASE_MASK_FILL,
    SCOPE_AGGS, SCOPE_BM25_SCORE, SCOPE_MASK_FILL, SCOPE_PACK,
    SCOPE_RANGE_FILTER, SCOPE_SORT_KEY, SCOPE_TERM_MASK, SCOPE_TOPK,
    current_profile, profile_add, profiled_phase,
)
from ..ops import aggs as agg_ops
from ..ops import masks as mask_ops
from ..ops import topk as topk_ops
from ..observability import flight
from ..observability.metrics import SEARCH_KERNEL_LAUNCHES_TOTAL
from ..ops.bm25 import dequantize_block_bounds, score_lanes, score_postings
from .plan import (
    PRESENT_FROM_VALUES, BucketAggExec, CompositeAggExec, LoweredPlan,
    MetricAggExec, PBool, PMaskRef, PMatchAll, PMatchNone, PNormPresence,
    PPostings, PPresence, PRange, PTermLane, SortExec,
)

_JIT_CACHE: dict[tuple, Callable] = {}

# device-resident scalar tuples keyed by (plan signature, values): repeated
# queries skip the host->device scalar upload entirely.
# LRU (move-to-end on hit), matching the other caches: a hot scalar tuple
# re-used every query must not be evicted just because it was inserted first.
_SCALAR_CACHE: "OrderedDict[tuple, Any]" = OrderedDict()
_SCALAR_CACHE_CAP = 512


def _named(fn: Callable, name: str) -> Callable:
    """Give the closure about to be jitted a static, readable name: XLA
    calls the program `jit_<name>`, which is what a profiler trace and the
    compile log show. `name` derives only from what is already in the
    program's cache key (family, lane bucket, k) — never from a request's
    scalars, terms or posting lengths, so it adds no compiled program."""
    fn.__name__ = fn.__qualname__ = name
    return fn


# qwlint: disable-next-line=QW001 - .item() on host numpy scalars builds
# the value-keyed upload-cache key; no device arrays are touched here
def _device_scalars(plan: LoweredPlan) -> tuple[Any, Any]:
    """(device_scalars, device_num_docs), one batched transfer on miss."""
    # value+dtype keyed: two plans with identical scalar tuples can share
    # the same device buffers — the content is the content
    key = (plan.num_docs,
           tuple((s.dtype.str, s.item()) for s in map(np.asarray, plan.scalars)))
    cached = _SCALAR_CACHE.get(key)
    if cached is None:
        moved = jax.device_put(list(plan.scalars) + [np.int32(plan.num_docs)])
        cached = (tuple(moved[:-1]), moved[-1])
        if len(_SCALAR_CACHE) >= _SCALAR_CACHE_CAP:
            _SCALAR_CACHE.popitem(last=False)
        _SCALAR_CACHE[key] = cached
    else:
        _SCALAR_CACHE.move_to_end(key)
    return cached


def _cardinality_hashes(met, arrays):
    """(hashes[uint64], present[bool]) per doc for a cardinality metric:
    text columns gather per-ordinal TERM hashes (cross-split identity),
    numeric columns mix the 64-bit value pattern. THE one derivation —
    the bucket, range, and top-level metric paths all call it."""
    if met.hash_slot >= 0:
        ordinals = arrays[met.values_slot]
        present = ordinals >= 0
        hashes = arrays[met.hash_slot][jnp.clip(ordinals, 0, None)]
    else:
        values = arrays[met.values_slot]
        present = arrays[met.present_slot].astype(jnp.bool_)
        bits = (agg_ops.jax_bitcast_f64(values)
                if values.dtype == jnp.float64
                else values.astype(jnp.int64).astype(jnp.uint64))
        hashes = agg_ops._hll_mix64(bits)
    return hashes, present


def _bucket_tree_blocks_posting_space(children) -> bool:
    """True when a nested-bucket subtree needs arrays the _GatherView
    cannot serve (range bounds, multivalued pair arrays, per-ordinal
    hash tables) — shared by the plain and composite eligibility
    checks."""
    stack = list(children)
    while stack:
        child = stack.pop()
        if (child.kind in ("range", "terms_mv")
                or any(m.kind == "cardinality" for m in child.metrics)):
            return True
        stack.extend(child.subs)
    return False


def _bucket_idx(a: BucketAggExec, arrays, scalars, mask):
    """(idx, in_bucket_mask): per-doc bucket index with the out-of-range
    sentinel `num_buckets` for dropped docs."""
    values = arrays[a.values_slot]
    nb = a.num_buckets
    if a.kind == "terms":
        ordinals = values
        m = mask & (ordinals >= 0)
        idx = jnp.where(m, ordinals, jnp.int32(nb))
        return idx, m
    if a.kind == "terms_mv":
        # multivalued: values are (doc, ordinal) PAIR arrays — gather the
        # doc-level mask at each pair's doc id; padding pairs carry
        # ordinal -1 (dropped here) with doc 0 (in-bounds gather)
        pair_docs = arrays[a.present_slot]
        m = mask[pair_docs] & (values >= 0)
        idx = jnp.where(m, values, jnp.int32(nb))
        return idx, m
    present = arrays[a.present_slot].astype(jnp.bool_)
    m = mask & present
    origin = scalars[a.origin_slot]
    interval = scalars[a.interval_slot]
    if a.kind == "date_histogram":
        raw = (values - origin) // interval          # exact integer math
    else:
        raw = jnp.floor((values.astype(jnp.float64) - origin) / interval)
    idx = raw.astype(jnp.int32)
    m = m & (idx >= 0) & (idx < nb)
    return jnp.where(m, idx, jnp.int32(nb)), m


def _bucket_metrics(metric_slots, arrays, idx, m, nb):
    metrics: dict[str, Any] = {}
    for met in metric_slots:
        if met.kind == "cardinality":
            # per-bucket HLL registers (scatter-max)
            hashes, present = _cardinality_hashes(met, arrays)
            ok = m & present
            metrics[met.name] = {"hll": agg_ops.bucket_hll_registers(
                jnp.where(ok, idx, jnp.int32(nb)), hashes, ok, nb)}
            continue
        mv = arrays[met.values_slot].astype(jnp.float64)
        mp = arrays[met.present_slot].astype(jnp.bool_)
        # docs with mm==False get the sentinel index; both bucket-kernel
        # paths neutralize them, so mv needs no extra masking passes
        mm = m & mp
        midx = jnp.where(mm, idx, jnp.int32(nb))
        state: dict[str, Any] = {}
        need = met.kind
        if need == "percentiles":
            state["sketch"] = agg_ops.bucket_percentile_sketch(midx, mv, nb)
            metrics[met.name] = state
            continue
        if need in ("sum", "avg", "stats", "extended_stats"):
            state["sum"] = agg_ops.bucket_sum(midx, mv, nb)
        if need in ("avg", "stats", "extended_stats", "value_count"):
            state["count"] = agg_ops.bucket_counts(midx, nb).astype(jnp.int64)
        if need in ("min", "stats", "extended_stats"):
            state["min"] = agg_ops.bucket_min(midx, mv, nb)
        if need in ("max", "stats", "extended_stats"):
            state["max"] = agg_ops.bucket_max(midx, mv, nb)
        if need in ("stats", "extended_stats"):
            state["sum_sq"] = agg_ops.bucket_sum(midx, mv * mv, nb)
        metrics[met.name] = state
    return metrics


def _eval_range_agg(a: BucketAggExec, arrays, mask):
    """Range buckets may OVERLAP (ES counts a doc in every range it falls
    in), so each range gets its own mask instead of one bucket index."""
    nb = a.num_buckets
    values = arrays[a.values_slot].astype(jnp.float64)
    present = arrays[a.present_slot].astype(jnp.bool_)
    froms = arrays[a.froms_slot]
    tos = arrays[a.tos_slot]
    in_range = ((mask & present)[:, None]
                & (values[:, None] >= froms[None, :])
                & (values[:, None] < tos[None, :]))          # [D, nb]
    counts = jnp.sum(in_range, axis=0, dtype=jnp.int32)
    metrics: dict[str, Any] = {}
    for met in a.metrics:
        if met.kind == "cardinality":
            # overlapping ranges: per-range HLL registers (small nb
            # loop, like the percentile sketches below). c_present, not
            # `present`: the enclosing scope's present masks the RANGE
            # field and must not be shadowed
            hashes, c_present = _cardinality_hashes(met, arrays)
            metrics[met.name] = {"hll": jnp.stack([
                agg_ops.hll_registers(hashes, in_range[:, i] & c_present)
                for i in range(nb)])}
            continue
        mv = arrays[met.values_slot].astype(jnp.float64)
        mp = arrays[met.present_slot].astype(jnp.bool_)
        mm = in_range & mp[:, None]                          # [D, nb]
        state: dict[str, Any] = {}
        need = met.kind
        mvb = mv[:, None]
        if need == "percentiles":
            state["sketch"] = jnp.stack([
                agg_ops.percentile_sketch(mv, mp, in_range[:, i] & mask)
                for i in range(nb)])
            metrics[met.name] = state
            continue
        if need in ("sum", "avg", "stats", "extended_stats"):
            state["sum"] = jnp.sum(jnp.where(mm, mvb, 0.0), axis=0)
        if need in ("avg", "stats", "extended_stats", "value_count"):
            state["count"] = jnp.sum(mm, axis=0, dtype=jnp.int64)
        if need in ("min", "stats", "extended_stats"):
            state["min"] = jnp.min(jnp.where(mm, mvb, jnp.inf), axis=0)
        if need in ("max", "stats", "extended_stats"):
            state["max"] = jnp.max(jnp.where(mm, mvb, -jnp.inf), axis=0)
        if need in ("stats", "extended_stats"):
            state["sum_sq"] = jnp.sum(jnp.where(mm, mvb * mvb, 0.0), axis=0)
        metrics[met.name] = state
    return {"counts": counts, "metrics": metrics}


def _eval_bucket_agg(a: BucketAggExec, arrays, scalars, mask):
    if a.kind == "range":
        return _eval_range_agg(a, arrays, mask)
    idx, m = _bucket_idx(a, arrays, scalars, mask)
    return _eval_bucket_level(a, arrays, scalars, mask, idx, m,
                              a.num_buckets)


def _eval_bucket_level(a: BucketAggExec, arrays, scalars, mask, idx, m,
                       space: int):
    """One level of a nested bucket tree. `idx`/`m` are the FLATTENED
    bucket index (mixed-radix over all ancestors) and its validity mask;
    `space` is the flattened bucket count. Children extend the radix:
    child_flat = parent_flat * child_nb + child_local."""
    out: dict[str, Any] = {
        "counts": agg_ops.bucket_counts(jnp.where(m, idx, jnp.int32(space)),
                                        space),
        "metrics": _bucket_metrics(a.metrics, arrays, idx, m, space),
    }
    subs = []
    for child in a.subs:
        nb2 = child.num_buckets
        idx2, m2 = _bucket_idx(child, arrays, scalars, mask)
        both = m & m2
        combined = jnp.where(both, idx * nb2 + idx2, jnp.int32(space * nb2))
        subs.append(_eval_bucket_level(child, arrays, scalars, mask,
                                       combined, both, space * nb2))
    if subs:
        out["subs"] = subs
    return out



def _keyed_for(by, descending, values_slot, present_slot, view, mask,
               scores, doc_key):
    """Higher-is-better f64 key for one sort part (missing column values get
    the finite bottom sentinel, non-matching docs -inf). `view` is either the
    arrays tuple (dense path) or a _GatherView (posting space); `doc_key` is
    the per-element doc id source for "doc" sorts."""
    if by == "score":
        key = scores.astype(jnp.float64)
        if not descending:
            key = -key
        return jnp.where(mask, key, -jnp.inf)
    if by == "column":
        key = view[values_slot].astype(jnp.float64)
        if not descending:
            key = -key
        if present_slot == PRESENT_FROM_VALUES:
            present = view[values_slot] >= 0  # ordinal columns: -1 = missing
        else:
            present = view[present_slot].astype(jnp.bool_)
        has_value = mask & present
        return jnp.where(
            has_value, key,
            jnp.where(mask, jnp.float64(topk_ops.MISSING_VALUE_SENTINEL),
                      -jnp.inf))
    # "doc"
    key = doc_key.astype(jnp.float64)
    return jnp.where(mask, key if descending else -key, -jnp.inf)


def _global_doc_ids(plan, scalars, padded):
    """Per-lane GLOBAL doc ids: the plain iota for whole-split plans; the
    chunk's traced doc offset shifts it for chunked dense sub-plans
    (search/chunkexec.py) so doc-keyed comparisons match the fused path."""
    docs = jnp.arange(padded, dtype=jnp.int32)
    if plan.doc_base_slot >= 0:
        docs = docs + scalars[plan.doc_base_slot].astype(jnp.int32)
    return docs


def _apply_search_after(plan, keyed, keyed2, scalars, padded):
    """Restrict top-k eligibility per the search_after marker (counts/aggs
    keep full-query semantics). With a secondary key the comparison is
    lexicographic."""
    relation = plan.search_after_relation
    marker = scalars[plan.sa_value_slot]
    if keyed2 is None:
        if relation == "lt":
            eligible = keyed < marker
        elif relation == "le":
            eligible = keyed <= marker
        else:  # "lt_tie"
            marker_doc = scalars[plan.sa_doc_slot]
            docs = _global_doc_ids(plan, scalars, padded)
            eligible = (keyed < marker) | ((keyed == marker) &
                                           (docs > marker_doc))
        return jnp.where(eligible, keyed, -jnp.inf), None
    marker2 = scalars[plan.sa_value2_slot]
    lt = (keyed < marker) | ((keyed == marker) & (keyed2 < marker2))
    tie = (keyed == marker) & (keyed2 == marker2)
    if relation == "lt":
        eligible = lt
    elif relation == "le":
        eligible = lt | tie
    else:  # "lt_tie"
        marker_doc = scalars[plan.sa_doc_slot]
        docs = _global_doc_ids(plan, scalars, padded)
        eligible = lt | (tie & (docs > marker_doc))
    return (jnp.where(eligible, keyed, -jnp.inf),
            jnp.where(eligible, keyed2, -jnp.inf))


def _posting_space_eligible(plan: LoweredPlan) -> bool:
    """Single-term queries (no boolean structure, no NOT semantics) can
    execute entirely over the [P] posting arrays instead of [N] dense docs —
    the role of the reference's specialized single-term scorer, with P often
    orders of magnitude below the doc count.

    Aggregations whose auxiliary arrays are NOT doc-space (range bounds,
    multivalued pair arrays, per-ordinal hash tables) cannot ride the
    _GatherView (it gathers every slot at per-posting doc ids) — those
    plans take the dense path."""
    if not (isinstance(plan.root, PPostings)
            and plan.search_after_relation == "none"):
        return False
    if plan.root.impact_ordered and plan.sort.by not in ("score", "doc"):
        # impact-ordered postings (format v3) break posting-index ==
        # doc-order; a field-primary key's lowest-index-wins ties would
        # diverge from the doc-ordered seed. Score keys are safe (equal-
        # score groups stay contiguous and doc-ascending by the writer's
        # sort contract) and "doc" keys are unique. The dense path below
        # scatters into doc space, which is order-independent.
        return False
    for a in plan.aggs:
        if isinstance(a, BucketAggExec):
            if _bucket_tree_blocks_posting_space([a]):
                return False
        elif isinstance(a, CompositeAggExec):
            # composite CHILDREN are normal nested buckets and carry the
            # same gather-view restrictions
            if _bucket_tree_blocks_posting_space(a.subs):
                return False
        elif isinstance(a, MetricAggExec):
            if a.metric.kind == "cardinality":
                return False
    return True


class _RebaseView:
    """arrays[slot] with FOR-packed slots reconstructed in-register:
    `delta * for_scale + for_min` in the column's integer domain (see
    LoweredPlan.rebase), so sort keys, metric inputs and cardinality
    hashes observe full-width values while HBM holds the narrow lanes.
    Absent lanes reconstruct to for_min rather than the raw layout's 0 —
    invisible downstream because every consumer masks by the present
    column."""

    def __init__(self, arrays, scalars, rebase):
        self.arrays = arrays
        self.scalars = scalars
        self.rebase = rebase

    def __getitem__(self, slot: int):
        arr = self.arrays[slot]
        rb = self.rebase.get(slot)
        if rb is None:
            return arr
        scale, fmin = self.scalars[rb[0]], self.scalars[rb[1]]
        return arr.astype(scale.dtype) * scale + fmin


class _GatherView:
    """arrays[slot] gathered at per-posting doc ids — lets the bucket-agg
    evaluator run unchanged in posting space. FOR-packed slots rebase
    AFTER the gather: the [P]-sized reconstruction is cheaper than
    materializing the full-width doc-space column first."""

    def __init__(self, arrays, safe_ids, scalars=None, rebase=None):
        self.arrays = arrays
        self.safe_ids = safe_ids
        self.scalars = scalars
        self.rebase = rebase or {}

    def __getitem__(self, slot: int):
        g = self.arrays[slot][self.safe_ids]
        rb = self.rebase.get(slot)
        if rb is None:
            return g
        scale, fmin = self.scalars[rb[0]], self.scalars[rb[1]]
        return g.astype(scale.dtype) * scale + fmin


def _build_posting_space(plan: LoweredPlan, k: int,
                         exact: bool = False) -> Callable:
    root, sort, aggs = plan.root, plan.sort, plan.aggs
    padded = plan.num_docs_padded

    def fn(arrays, scalars, num_docs):
        ids = arrays[root.ids_slot]
        tfs = arrays[root.tfs_slot]
        num_postings = ids.shape[0]
        valid = ids < num_docs
        count = jnp.sum(valid.astype(jnp.int32))
        safe_ids = jnp.clip(ids, 0, padded - 1)
        if k == 0:  # count/agg-only: no scoring, no top-k
            gathered = _GatherView(arrays, safe_ids, scalars, plan.rebase)
            agg_out = _eval_aggs(aggs, gathered, scalars, valid)
            return (jnp.zeros((0,), jnp.float64), None,
                    jnp.zeros((0,), jnp.int32), jnp.zeros((0,), jnp.float32),
                    count, jnp.float64(1.0), tuple(agg_out))
        if root.scoring:
            with jax.named_scope(SCOPE_BM25_SCORE):
                scores = score_postings(
                    tfs, ids, arrays[root.norm_slot],
                    scalars[root.avg_len_slot], scalars[root.idf_slot])
        else:
            scores = jnp.zeros(num_postings, dtype=jnp.float32)
        gathered = _GatherView(arrays, safe_ids, scalars, plan.rebase)
        # "doc" sorts key on the posting's doc id (ascending already)
        with jax.named_scope(SCOPE_SORT_KEY):
            keyed = _keyed_for(sort.by, sort.descending, sort.values_slot,
                               sort.present_slot, gathered, valid, scores,
                               ids)
        if plan.threshold_slot >= 0:
            # dynamic pruning pushdown: counts/aggs above keep full-query
            # semantics; only top-k eligibility is restricted
            keyed = topk_ops.apply_threshold_mask(
                keyed, scalars[plan.threshold_slot])
            if (root.impact_bmax_slot >= 0 and sort.by == "score"
                    and sort.descending):
                # impact block-max early exit (format v3): whole 128-posting
                # blocks whose quantized score bound cannot reach the
                # threshold mask without scoring — a no-op for results
                # (the bound is sound, so every masked lane was already
                # below the threshold mask above)
                bounds = dequantize_block_bounds(
                    arrays[root.impact_bmax_slot],
                    scalars[root.impact_scale_slot])
                keyed = topk_ops.block_max_threshold_mask(
                    keyed, bounds, scalars[plan.threshold_slot])
        kk = min(k, num_postings)
        topk_safe = jnp.float64(1.0)
        if sort.by2 == "none":
            with jax.named_scope(SCOPE_TOPK):
                if exact:
                    sort_vals, pos = topk_ops.exact_topk(keyed, kk)
                else:
                    sort_vals, pos, topk_safe = topk_ops.guided_topk(keyed,
                                                                     kk)
            sort_vals2 = None
        else:
            with jax.named_scope(SCOPE_SORT_KEY):
                keyed2 = _keyed_for(sort.by2, sort.descending2,
                                    sort.values2_slot, sort.present2_slot,
                                    gathered, valid, scores, ids)
                if plan.threshold_slot >= 0:
                    keyed2 = mask_ops.propagate_dead_lanes(keyed, keyed2)
            with jax.named_scope(SCOPE_TOPK):
                sort_vals, sort_vals2, pos = topk_ops.exact_topk_2key(
                    keyed, keyed2, kk)
        with jax.named_scope(SCOPE_TOPK):
            doc_ids = ids[pos]
            hit_scores = scores[pos]
        agg_out = _eval_aggs(aggs, gathered, scalars, valid)
        return sort_vals, sort_vals2, doc_ids.astype(jnp.int32), hit_scores, \
            count, topk_safe, tuple(agg_out)

    return fn


def _eval_composite_agg(a: CompositeAggExec, arrays, scalars, mask):
    """Composite buckets TPU-first: one multi-key lexicographic sort over
    the doc space, run-boundary detection, and the first `size` distinct
    key tuples read back with exact counts — no dynamic hash tables.

    Per-source i32 keys use the order-preserving encoding documented on
    CompositeSourceExec (missing=0, value=(idx+1)*2, after markers odd)."""
    num = mask.shape[0]
    m = mask
    keys = []
    for s in a.sources:
        if s.kind == "terms_ord":
            ordinals = arrays[s.values_slot]
            present = ordinals >= 0
            key = (ordinals.astype(jnp.int32) + 1) * 2
        else:
            values = arrays[s.values_slot]
            present = arrays[s.present_slot].astype(jnp.bool_)
            origin = scalars[s.origin_slot]
            interval = scalars[s.interval_slot]
            if s.kind == "date_histogram":
                idx = ((values - origin) // interval).astype(jnp.int32)
            else:
                idx = jnp.floor((values.astype(jnp.float64) - origin)
                                / interval).astype(jnp.int32)
            key = (idx + 1) * 2
        if s.missing_bucket:
            key = jnp.where(present, key, jnp.int32(0))
        else:
            m = m & present
        keys.append(key)
    if a.has_after:
        # strict lexicographic tuple > after, cascaded per source
        gt = jnp.zeros(num, dtype=jnp.bool_)
        eq = jnp.ones(num, dtype=jnp.bool_)
        for key, s in zip(keys, a.sources):
            marker = scalars[s.after_slot]
            gt = gt | (eq & (key > marker))
            eq = eq & (key == marker)
        m = m & gt
    sentinel = jnp.int32(2**31 - 1)
    keys = [jnp.where(m, key, sentinel) for key in keys]
    # metric operands ride the same sort so per-run (bucket) metric
    # states segment-reduce over contiguous ranges; the position index
    # rides along too, recovering the permutation that lets bucket
    # CHILDREN evaluate back in doc space
    metric_ops: list = []
    for met in a.metrics:
        mv = arrays[met.values_slot].astype(jnp.float64)
        mp = arrays[met.present_slot].astype(jnp.bool_)
        metric_ops.extend([mv, mp & m])
    positions = jnp.arange(num, dtype=jnp.int32)
    sorted_all = jax.lax.sort(tuple(keys) + (positions,) + tuple(metric_ops),
                              num_keys=len(keys))
    if not isinstance(sorted_all, (tuple, list)):
        sorted_all = (sorted_all,)
    sorted_keys = sorted_all[: len(keys)]
    perm = sorted_all[len(keys)]
    sorted_metrics = sorted_all[len(keys) + 1:]
    valid_total = jnp.sum(m.astype(jnp.int32))
    idxs = jnp.arange(num, dtype=jnp.int32)
    diff = jnp.zeros(max(num - 1, 0), dtype=jnp.bool_)
    for sk in sorted_keys:
        diff = diff | (sk[1:] != sk[:-1])
    is_start = jnp.concatenate(
        [jnp.ones(min(num, 1), dtype=jnp.bool_), diff])
    is_start = is_start & (idxs < valid_total)
    start_pos = jnp.where(is_start, idxs, jnp.int32(num))
    k_runs = min(a.size, num)
    neg_top, _ = jax.lax.top_k(-start_pos, min(k_runs + 1, num))
    starts = -neg_top                       # ascending run starts
    if starts.shape[0] < k_runs + 1:
        starts = jnp.concatenate(
            [starts, jnp.full(k_runs + 1 - starts.shape[0], num, jnp.int32)])
    safe = jnp.clip(starts[:k_runs], 0, num - 1)
    run_keys = jnp.stack([sk[safe] for sk in sorted_keys])   # [S, k_runs]
    ends = jnp.minimum(starts[1:], valid_total)
    counts = jnp.where(starts[:k_runs] < valid_total,
                       ends - starts[:k_runs], jnp.int32(0))
    out = {"run_keys": run_keys, "counts": counts}
    # per-position run id = rank of this position's run among the first
    # k_runs (positions past them segment-drop)
    run_id = jnp.cumsum(is_start.astype(jnp.int32)) - 1
    in_range = (idxs < valid_total) & (run_id >= 0) & (run_id < k_runs)
    if a.subs:
        # scatter each doc's run id back to its original position: bucket
        # children then evaluate with the normal nested machinery, the
        # composite acting as the outermost radix level
        run_id_doc = jnp.full(num, k_runs, jnp.int32).at[perm].set(
            jnp.where(in_range, run_id, jnp.int32(k_runs)))
        in_run = run_id_doc < k_runs
        subs = []
        for child in a.subs:
            nb2 = child.num_buckets
            idx2, m2 = _bucket_idx(child, arrays, scalars, mask)
            both = in_run & m2
            combined = jnp.where(both, run_id_doc * nb2 + idx2,
                                 jnp.int32(k_runs * nb2))
            subs.append(_eval_bucket_level(child, arrays, scalars, mask,
                                           combined, both, k_runs * nb2))
        out["subs"] = subs
    if a.metrics:
        metrics: dict[str, Any] = {}
        for mi, met in enumerate(a.metrics):
            mv = sorted_metrics[2 * mi]
            mp = sorted_metrics[2 * mi + 1].astype(jnp.bool_)
            seg = jnp.where(in_range & mp, run_id, jnp.int32(k_runs))
            state: dict[str, Any] = {}
            need = met.kind
            if need in ("sum", "avg", "stats", "extended_stats"):
                state["sum"] = jax.ops.segment_sum(
                    jnp.where(in_range & mp, mv, 0.0), seg,
                    num_segments=k_runs + 1)[:k_runs]
            if need in ("avg", "stats", "extended_stats", "value_count"):
                state["count"] = jax.ops.segment_sum(
                    (in_range & mp).astype(jnp.int64), seg,
                    num_segments=k_runs + 1)[:k_runs]
            if need in ("min", "stats", "extended_stats"):
                state["min"] = jax.ops.segment_min(
                    jnp.where(in_range & mp, mv, jnp.inf), seg,
                    num_segments=k_runs + 1)[:k_runs]
            if need in ("max", "stats", "extended_stats"):
                state["max"] = jax.ops.segment_max(
                    jnp.where(in_range & mp, mv, -jnp.inf), seg,
                    num_segments=k_runs + 1)[:k_runs]
            if need in ("stats", "extended_stats"):
                state["sum_sq"] = jax.ops.segment_sum(
                    jnp.where(in_range & mp, mv * mv, 0.0), seg,
                    num_segments=k_runs + 1)[:k_runs]
            metrics[met.name] = state
        out["metrics"] = metrics
    return out


def _agg_scope(a) -> str:
    """`aggs.<kind>`: the kind is part of the plan's structure signature."""
    if isinstance(a, CompositeAggExec):
        return f"{SCOPE_AGGS}.composite"
    if isinstance(a, BucketAggExec):
        return f"{SCOPE_AGGS}.{a.kind}"
    if isinstance(a, MetricAggExec):
        return f"{SCOPE_AGGS}.{a.metric.kind}"
    raise TypeError(f"unknown agg exec {type(a).__name__}")


def _eval_aggs(aggs, gathered, scalars, valid):
    agg_out = []
    for a in aggs:
        with jax.named_scope(_agg_scope(a)):
            agg_out.append(_eval_agg(a, gathered, scalars, valid))
    return agg_out


def _eval_agg(a, gathered, scalars, valid):
    if isinstance(a, CompositeAggExec):
        return _eval_composite_agg(a, gathered, scalars, valid)
    if isinstance(a, BucketAggExec):
        return _eval_bucket_agg(a, gathered, scalars, valid)
    met = a.metric
    if met.kind == "cardinality":
        hashes, present = _cardinality_hashes(met, gathered)
        return {"hll": agg_ops.hll_registers(hashes, valid & present)}
    mv = gathered[met.values_slot]
    mp = gathered[met.present_slot]
    if met.kind == "percentiles":
        return {"sketch": agg_ops.percentile_sketch(mv, mp, valid)}
    return {"stats": agg_ops.stats_state(mv, mp, valid)}


def _pack_mask(mask, padded: int):
    """Big-endian bit pack of a [padded] bool mask into [ceil(padded/8)]
    uint8 — np.packbits bit order, so a device-computed mask and a host
    np.packbits of the same booleans are byte-identical (the mask-cache
    equivalence tests lean on this)."""
    nbytes = (padded + 7) // 8
    bits = jnp.zeros((nbytes * 8,), dtype=jnp.uint32)
    bits = bits.at[:padded].set(mask.astype(jnp.uint32))
    weights = jnp.array([128, 64, 32, 16, 8, 4, 2, 1], dtype=jnp.uint32)
    return jnp.sum(bits.reshape(nbytes, 8) * weights, axis=1).astype(jnp.uint8)


def _unpack_mask(packed, padded: int):
    """Inverse of `_pack_mask`: [nbytes] uint8 -> [padded] bool."""
    shifts = jnp.arange(7, -1, -1, dtype=jnp.uint8)
    bits = (packed[:, None] >> shifts[None, :]) & jnp.uint8(1)
    return bits.reshape(-1)[:padded].astype(jnp.bool_)


def _node_evaluator(padded: int) -> Callable:
    """The predicate-tree evaluator, shared by the full search kernel
    (`_build`) and the mask-fill kernel (`compute_packed_mask`) — one
    implementation, so a cached mask is bit-identical to inline evaluation
    by construction (zonemaps, FOR-packed compares, msm semantics and
    all)."""

    def eval_node(node, arrays, scalars):
        """Returns (mask[padded] bool, scores[padded] f32 | None)."""
        if isinstance(node, PMatchAll):
            return jnp.ones(padded, dtype=jnp.bool_), None
        if isinstance(node, PMatchNone):
            return jnp.zeros(padded, dtype=jnp.bool_), None
        if isinstance(node, PMaskRef):
            # Tier A hit: the whole predicate is the cached packed bitmask
            return _unpack_mask(arrays[node.packed_slot], padded), None
        if isinstance(node, PPostings):
            ids = arrays[node.ids_slot]
            with jax.named_scope(SCOPE_TERM_MASK):
                mask = mask_ops.mask_from_postings(ids, padded)
            if not node.scoring:
                return mask, None
            with jax.named_scope(SCOPE_BM25_SCORE):
                partial = score_postings(
                    arrays[node.tfs_slot], ids, arrays[node.norm_slot],
                    scalars[node.avg_len_slot], scalars[node.idf_slot])
                scores = mask_ops.dense_from_postings(ids, partial, padded)
            return mask, scores
        if isinstance(node, PTermLane):
            # a dense term read in place, one lane element a doc: no scatter
            lane = arrays[node.lane_slot]
            with jax.named_scope(SCOPE_TERM_MASK):
                mask = lane > 0
            if not node.scoring:
                return mask, None
            with jax.named_scope(SCOPE_BM25_SCORE):
                scores = score_lanes(
                    lane, arrays[node.norm_slot],
                    scalars[node.avg_len_slot], scalars[node.idf_slot])
            return mask, scores
        if isinstance(node, PRange):
            with jax.named_scope(SCOPE_RANGE_FILTER):
                return _range_node_mask(node, arrays, scalars), None
        if isinstance(node, PPresence):
            col = arrays[node.present_slot]
            return (col >= 0) if node.is_ordinal else col.astype(jnp.bool_), None
        if isinstance(node, PNormPresence):
            return arrays[node.norm_slot] > 0, None
        if isinstance(node, PBool):
            return eval_bool(node, arrays, scalars)
        raise TypeError(f"unknown plan node {type(node).__name__}")

    def _range_node_mask(node: PRange, arrays, scalars):
        values = arrays[node.values_slot]
        if values.dtype.kind == "u" and values.dtype.itemsize <= 4:
            # FOR-packed lanes compare as scaled deltas in i32 — the
            # lowering caps the span so span + 1 (the never-matching
            # bound) stays representable
            values = values.astype(jnp.int32)
        return mask_ops.range_mask(
            values, arrays[node.present_slot],
            scalars[node.lo_slot] if node.lo_slot >= 0 else 0,
            scalars[node.hi_slot] if node.hi_slot >= 0 else 0,
            node.lo_incl, node.hi_incl,
            node.lo_slot >= 0, node.hi_slot >= 0,
            zmin=(arrays[node.zmin_slot]
                  if node.zmin_slot >= 0 else None),
            zmax=(arrays[node.zmax_slot]
                  if node.zmax_slot >= 0 else None),
            zonemap_block=ZONEMAP_BLOCK)

    def eval_bool(node: PBool, arrays, scalars):
        score_parts = []
        conj = None
        for child in list(node.must) + list(node.filter):
            m, s = eval_node(child, arrays, scalars)
            conj = m if conj is None else (conj & m)
            if s is not None:
                score_parts.append(s)
        should_masks = []
        for child in node.should:
            m, s = eval_node(child, arrays, scalars)
            should_masks.append(m)
            if s is not None:
                score_parts.append(s)
        mask = conj
        if should_masks:
            if node.minimum_should_match:
                msm = mask_ops.minimum_should_match_mask(
                    should_masks, node.minimum_should_match)
                mask = msm if mask is None else (mask & msm)
            elif mask is None:
                mask = mask_ops.or_masks(*should_masks)
            # should with must present: purely optional (scoring only)
        if mask is None:
            mask = jnp.ones(padded, dtype=jnp.bool_)
        for child in node.must_not:
            m, _ = eval_node(child, arrays, scalars)
            mask = mask & ~m
        scores = None
        if score_parts:
            # the sum of the clauses' scores is scoring too: XLA fuses a
            # clause's postings->score scatter into this add and names the
            # fusion after it
            with jax.named_scope(SCOPE_BM25_SCORE):
                scores = score_parts[0]
                for s in score_parts[1:]:
                    scores = scores + s
        return mask, scores

    return eval_node


def _build(plan: LoweredPlan, k: int, exact: bool = False) -> Callable:
    if _posting_space_eligible(plan):
        return _build_posting_space(plan, k, exact)
    padded = plan.num_docs_padded
    root, sort, aggs = plan.root, plan.sort, plan.aggs
    eval_node = _node_evaluator(padded)

    def fn(arrays, scalars, num_docs):
        # predicate evaluation reads the raw (possibly packed-delta) arrays;
        # value consumers go through the rebasing view
        view = _RebaseView(arrays, scalars, plan.rebase)
        mask, scores = eval_node(root, arrays, scalars)
        mask = mask & mask_ops.valid_docs_mask(num_docs, padded)
        if scores is None:
            scores = jnp.zeros(padded, dtype=jnp.float32)
        if k == 0:  # count/agg-only: no keying, no top-k
            count = jnp.sum(mask.astype(jnp.int32))
            agg_out = _eval_aggs(aggs, view, scalars, mask)
            return (jnp.zeros((0,), jnp.float64), None,
                    jnp.zeros((0,), jnp.int32), jnp.zeros((0,), jnp.float32),
                    count, jnp.float64(1.0), tuple(agg_out))
        with jax.named_scope(SCOPE_SORT_KEY):
            doc_key = _global_doc_ids(plan, scalars, padded)
            keyed = _keyed_for(sort.by, sort.descending, sort.values_slot,
                               sort.present_slot, view, mask, scores,
                               doc_key)
            keyed2 = None
            if sort.by2 != "none":
                keyed2 = _keyed_for(sort.by2, sort.descending2,
                                    sort.values2_slot, sort.present2_slot,
                                    view, mask, scores, doc_key)
            # search_after pushdown: restrict top-k eligibility, NOT
            # counts/aggs (ES semantics: totals and aggregations cover the
            # full query)
            if plan.search_after_relation != "none":
                keyed, keyed2 = _apply_search_after(plan, keyed, keyed2,
                                                    scalars, padded)
            if plan.threshold_slot >= 0:
                # dynamic-pruning threshold: same eligibility-only contract
                keyed = topk_ops.apply_threshold_mask(
                    keyed, scalars[plan.threshold_slot])
                if keyed2 is not None:
                    keyed2 = mask_ops.propagate_dead_lanes(keyed, keyed2)
        topk_safe = jnp.float64(1.0)
        with jax.named_scope(SCOPE_TOPK):
            if keyed2 is None:
                if exact:
                    sort_vals, doc_ids = topk_ops.exact_topk(keyed, k)
                else:
                    sort_vals, doc_ids, topk_safe = topk_ops.guided_topk(
                        keyed, k)
                sort_vals2 = None
            else:
                sort_vals, sort_vals2, doc_ids = topk_ops.exact_topk_2key(
                    keyed, keyed2, k)
            doc_ids = doc_ids.astype(jnp.int32)
        count = jnp.sum(mask.astype(jnp.int32))
        with jax.named_scope(SCOPE_TOPK):   # the winners' scores
            hit_scores = scores[jnp.clip(doc_ids, 0, padded - 1)]
        agg_out = _eval_aggs(aggs, view, scalars, mask)
        return sort_vals, sort_vals2, doc_ids, hit_scores, count, topk_safe, \
            tuple(agg_out)

    return fn


def get_executor(plan: LoweredPlan, k: int, exact: bool = False) -> Callable:
    key = (plan.signature(k), exact)
    cached = _JIT_CACHE.get(key)
    if cached is None:
        cached = jax.jit(_named(_build(plan, k, exact), f"qw_plain_k{k}"))
        _JIT_CACHE[key] = cached
    return cached


# --- packed readback ---------------------------------------------------------
#
# The result tree has O(10) leaves (hits, count, per-agg counts/metric
# states). Every separate leaf readback pays a per-transfer overhead, so
# the packed executor concatenates
# every leaf into ONE f64 device array — one transfer per query — and the
# host unpacks by the (treedef, shapes, dtypes) spec captured at trace
# time. f64 packing is exact for every output dtype in use: counts are
# doc-bounded (< 2^53), sums are f64 already, f32↔f64 is exact.

_PACKED_CACHE: dict[tuple, tuple] = {}


def _get_packed_executor(plan: LoweredPlan, k: int, example_args,
                         exact: bool = False, key: tuple = None):
    if key is None:
        key = (plan.signature(k), exact)
    cached = _PACKED_CACHE.get(key)
    if cached is None:
        fn = _build(plan, k, exact)
        shaped = jax.eval_shape(fn, *example_args)
        treedef = jax.tree_util.tree_structure(shaped)
        leaves = jax.tree_util.tree_leaves(shaped)
        spec = [(leaf.shape, leaf.dtype) for leaf in leaves]

        def packed(arrays, scalars, num_docs):
            out = fn(arrays, scalars, num_docs)
            with jax.named_scope(SCOPE_PACK):
                flat = [leaf.reshape(-1).astype(jnp.float64)
                        for leaf in jax.tree_util.tree_leaves(out)]
                return jnp.concatenate(flat) if flat else jnp.zeros((0,))

        cached = (jax.jit(_named(packed, f"qw_solo_k{k}")), treedef, spec)
        _PACKED_CACHE[key] = cached
    return cached


# qwlint: disable-next-line=QW001 - operates on the ALREADY-transferred
# host buffer from the packed seam; np.prod here is shape math, not I/O
def _unpack_result(packed: np.ndarray, treedef, spec):
    leaves = []
    offset = 0
    for shape, dtype in spec:
        size = int(np.prod(shape)) if shape else 1
        chunk = packed[offset: offset + size]
        offset += size
        leaf = chunk.astype(dtype).reshape(shape)
        leaves.append(leaf)
    return jax.tree_util.tree_unflatten(treedef, leaves)


# --- multi-query dispatch ----------------------------------------------------
#
# B queries that share one plan STRUCTURE (same signature: shapes, agg tree,
# sort spec) and one split's device arrays execute as ONE XLA program — vmap
# over the stacked per-query scalars with the arrays broadcast — and return
# as ONE packed [B, total] readback. Every dispatch round has a fixed
# host-side cost regardless of program content that pipelining depth
# cannot amortize, while work INSIDE one dispatch runs at device speed.
# Batching concurrent queries per dispatch is also the reference's own
# shape: leaf requests are batched per node
# (`quickwit-search/src/leaf.rs:81` greedy_batch_split).

_MULTI_CACHE: dict[tuple, tuple] = {}
_MULTI_SCALAR_CACHE: dict[tuple, Any] = {}
_MULTI_SCALAR_CACHE_CAP = 128


def _batch_bucket(n: int) -> int:
    """Round a convoy size up to the next power of two: arbitrary convoy
    sizes (2..max_batch) would each compile their own vmapped program —
    seconds of stall per new size over a remote transport. Bucketing
    bounds the distinct programs per signature to ~log2(max_batch);
    surplus lanes repeat the last query and are dropped at readback."""
    b = 1
    while b < n:
        b *= 2
    return b


# qwlint: disable-next-line=QW001 - np.asarray on host scalar tuples for
# jax.eval_shape (trace-time, no data movement)
def _get_packed_multi_executor(plan: LoweredPlan, k: int, batch: int,
                               device_arrays, exact: bool = False,
                               key: tuple = None):
    if key is None:
        key = (plan.signature(k), batch, exact)
    cached = _MULTI_CACHE.get(key)
    if cached is None:
        fn = _build(plan, k, exact)
        # eval_shape only consumes shapes/dtypes — numpy example scalars
        # avoid touching the device (a device upload here would cost the
        # very transfer round this path exists to avoid)
        example_args = (tuple(device_arrays),
                        tuple(np.asarray(s) for s in plan.scalars),
                        np.int32(plan.num_docs))
        shaped = jax.eval_shape(fn, *example_args)
        treedef = jax.tree_util.tree_structure(shaped)
        spec = [(leaf.shape, leaf.dtype)
                for leaf in jax.tree_util.tree_leaves(shaped)]

        def multi(arrays, scal_b, nd_b):
            out = jax.vmap(lambda s, n: fn(arrays, s, n),
                           in_axes=(0, 0))(scal_b, nd_b)
            with jax.named_scope(SCOPE_PACK):
                flat = [leaf.reshape(leaf.shape[0], -1).astype(jnp.float64)
                        for leaf in jax.tree_util.tree_leaves(out)]
                return (jnp.concatenate(flat, axis=1) if flat
                        else jnp.zeros((batch, 0)))

        cached = (jax.jit(_named(multi, f"qw_multi_b{batch}_k{k}")),
                  treedef, spec)
        _MULTI_CACHE[key] = cached
    return cached


# qwlint: disable-next-line=QW001 - host-side scalar staging (stack +
# single device_put); asarray/.item() run on numpy inputs pre-upload
def _device_multi_scalars(plan: LoweredPlan, scalar_sets, use_cache=True):
    """Stacked per-slot [B] scalar arrays + per-lane num_docs, one batched
    H2D transfer, content-cached (repeated batches skip the upload RTT).
    `use_cache=False` forces the upload — the bench uses it so measured
    numbers include the per-batch transfer a mixed workload pays."""
    batch = len(scalar_sets)
    key = None
    if use_cache:
        key = (plan.num_docs, batch,
               tuple(tuple((s.dtype.str, s.item())
                           for s in map(np.asarray, qs))
                     for qs in scalar_sets))
        cached = _MULTI_SCALAR_CACHE.get(key)
        if cached is not None:
            return cached
    stacked = [np.stack([np.asarray(qs[slot]) for qs in scalar_sets])
               for slot in range(len(plan.scalars))]
    nd_b = np.full((batch,), plan.num_docs, np.int32)
    moved = jax.device_put(stacked + [nd_b])
    cached = (tuple(moved[:-1]), moved[-1])
    if key is not None:
        if len(_MULTI_SCALAR_CACHE) >= _MULTI_SCALAR_CACHE_CAP:
            _MULTI_SCALAR_CACHE.pop(next(iter(_MULTI_SCALAR_CACHE)))
        _MULTI_SCALAR_CACHE[key] = cached
    return cached


def dispatch_plan_multi(plan: LoweredPlan, k: int,
                        device_arrays: list[jax.Array],
                        scalar_sets: list, cache_scalars: bool = True,
                        exact: bool = False) -> tuple:
    """Async dispatch of len(scalar_sets) same-structure queries as ONE
    XLA program + ONE packed readback buffer. Each element of
    `scalar_sets` is a full per-query scalar tuple (plan.scalars layout).
    The lane count is padded to a power-of-two bucket (surplus lanes
    repeat the last query and are discarded at readback)."""
    k = max(0, min(k, plan.num_docs_padded))
    SEARCH_KERNEL_LAUNCHES_TOTAL.inc()
    batch = len(scalar_sets)
    bucket = _batch_bucket(batch)
    with profiled_phase(PHASE_DISPATCH_PREPARE):
        padded_sets = list(scalar_sets) + [scalar_sets[-1]] * (bucket - batch)
        scal_b, nd_b = _device_multi_scalars(plan, padded_sets,
                                             use_cache=cache_scalars)
    profile = current_profile()
    recording = flight.recording()
    # shared once-per-dispatch cache key (see dispatch_plan)
    key = (plan.signature(k), bucket, exact) \
        if (recording or profile is not None) else None
    if recording:
        hit = key in _MULTI_CACHE
        flight.emit("compile.hit" if hit else "compile.miss",
                    attrs={"path": "multi", "bucket": bucket})
        flight.emit("dispatch.launch",
                    attrs={"path": "multi", "lanes": batch})
    if profile is None:
        executor, treedef, spec = _get_packed_multi_executor(
            plan, k, bucket, device_arrays, exact, key=key)
        out = executor(tuple(device_arrays), scal_b, nd_b)
    else:
        # same lazy-jit attribution as dispatch_plan, keyed per batch
        # bucket (each bucket size compiles its own vmapped program)
        hit = key in _MULTI_CACHE
        profile.add("compile_cache_hits" if hit else "compile_cache_misses")
        with profile.phase(PHASE_EXECUTE if hit else PHASE_COMPILE,
                           stage="dispatch_multi"):
            executor, treedef, spec = _get_packed_multi_executor(
                plan, k, bucket, device_arrays, exact, key=key)
            out = executor(tuple(device_arrays), scal_b, nd_b)
    if hasattr(out, "copy_to_host_async"):
        out.copy_to_host_async()
    return out, treedef, spec, batch, (plan, k, device_arrays,
                                       list(scalar_sets), cache_scalars)


# qwlint: disable-next-line=QW001 - THE sanctioned packed-readback seam:
# the one deliberate device->host transfer per dispatch, profiled as the
# readback stage (ROADMAP item 1 measures exactly this)
def _profiled_device_get(packed):
    profile = current_profile()
    if not flight.recording():
        if profile is None:
            return jax.device_get(packed)
        with profile.phase(PHASE_EXECUTE, stage="readback"):
            return jax.device_get(packed)
    t0 = _clock_monotonic()
    try:
        if profile is None:
            return jax.device_get(packed)
        with profile.phase(PHASE_EXECUTE, stage="readback"):
            return jax.device_get(packed)
    finally:
        flight.emit("dispatch.readback", attrs={
            "dur_ms": round((_clock_monotonic() - t0) * 1000.0, 3)})


# qwlint: disable-next-line=QW001 - batch variant of the sanctioned seam;
# one transfer for the whole batch, then host-side unpack
def readback_plan_multi(dispatched) -> list[dict[str, Any]]:
    """ONE device→host transfer for the whole batch; per-lane unpack.

    Lanes whose guided top-k screen reports `safe == 0` (an f32 boundary
    tie that could reorder f64 winners — see ops/topk.py:guided_topk) are
    re-dispatched as one exact batch and spliced back in."""
    packed, treedef, spec, batch, redispatch = dispatched
    host = np.asarray(_profiled_device_get(packed))
    results = []
    unsafe_lanes = []
    for lane in range(batch):
        sort_vals, sort_vals2, doc_ids, hit_scores, count, topk_safe, \
            agg_out = _unpack_result(host[lane], treedef, spec)
        if float(topk_safe) < 1.0:
            unsafe_lanes.append(lane)
        results.append({
            "sort_values": sort_vals,
            "sort_values2": sort_vals2,
            "doc_ids": doc_ids,
            "scores": hit_scores,
            "count": int(count),
            "aggs": list(agg_out),
        })
    if unsafe_lanes:
        plan, k, device_arrays, scalar_sets, cache_scalars = redispatch
        _note_guided_fallback(len(unsafe_lanes))
        exact = readback_plan_multi(dispatch_plan_multi(
            plan, k, device_arrays,
            [scalar_sets[lane] for lane in unsafe_lanes],
            cache_scalars=cache_scalars, exact=True))
        for lane, res in zip(unsafe_lanes, exact):
            results[lane] = res
    return results


# --- stacked query-group dispatch (ROADMAP item 2) ---------------------------
#
# N DISTINCT queries that share one plan STRUCTURE (same signature: shapes,
# agg tree, sort spec, threshold/search_after presence) over one split's
# resident arrays execute as ONE XLA program: operand slots whose cache key
# matches across every query (columns, norms, shared postings) stay a single
# broadcast buffer served from the ResidentColumnStore; slots whose key
# differs (per-query postings, predicate masks) are stacked into a leading
# [Q] query axis AT TRACE TIME (jnp.stack inside the jitted body — the
# stack fuses into the program, so the group still costs exactly one device
# dispatch). Per-query scalars — including each query's killing threshold
# from its own ThresholdBox (`plan.threshold_slot` becomes a [Q] lane
# vector) — ride the same stacked scalar path as the convoy batcher, and a
# [Q] validity mask zeroes the packed rows of lanes shed AFTER group
# formation (cancel/deadline) without changing the program shape: masking a
# rider never recompiles.

_STACKED_CACHE: dict[tuple, tuple] = {}


def stacked_slot_split(plans) -> tuple[tuple[int, ...], tuple[int, ...]]:
    """Partition array slots into (shared, stacked) by per-slot cache-key
    agreement across the group. Array keys are content-addressed within a
    split (``col.ts``, ``post.body=alpha#…``, ``mask.<digest>``), and a
    group is only formed over one split (the grouping key carries the
    split identity), so key equality at a slot ⇒ the queries reference the
    same staged device buffer ⇒ the slot broadcasts; disagreement ⇒ the
    slot gets the leading query axis."""
    keys0 = plans[0].array_keys
    shared, stacked = [], []
    for slot, key in enumerate(keys0):
        if all(p.array_keys[slot] == key for p in plans[1:]):
            shared.append(slot)
        else:
            stacked.append(slot)
    return tuple(shared), tuple(stacked)


# qwlint: disable-next-line=QW001 - np.asarray on host scalar tuples for
# jax.eval_shape (trace-time, no data movement)
def _get_packed_stacked_executor(plan: LoweredPlan, k: int, bucket: int,
                                 stacked_slots: tuple[int, ...],
                                 device_arrays, exact: bool = False,
                                 key: tuple = None):
    if key is None:
        key = (plan.signature(k), bucket, stacked_slots, exact)
    cached = _STACKED_CACHE.get(key)
    if cached is None:
        fn = _build(plan, k, exact)
        nslots = len(plan.arrays)
        shared_slots = tuple(s for s in range(nslots)
                             if s not in stacked_slots)
        example_args = (tuple(device_arrays),
                        tuple(np.asarray(s) for s in plan.scalars),
                        np.int32(plan.num_docs))
        shaped = jax.eval_shape(fn, *example_args)
        treedef = jax.tree_util.tree_structure(shaped)
        spec = [(leaf.shape, leaf.dtype)
                for leaf in jax.tree_util.tree_leaves(shaped)]

        def assemble(shared_arrays, lane_arrays):
            arrays = [None] * nslots
            for i, s in enumerate(shared_slots):
                arrays[s] = shared_arrays[i]
            for i, s in enumerate(stacked_slots):
                arrays[s] = lane_arrays[i]
            return tuple(arrays)

        def stacked(shared_arrays, lane_stacks, scal_b, nd_b, valid_b):
            with jax.named_scope(SCOPE_PACK):   # operands onto the query axis
                st = tuple(jnp.stack(qs) for qs in lane_stacks)
            out = jax.vmap(
                lambda lane, s, n: fn(assemble(shared_arrays, lane), s, n),
                in_axes=(0, 0, 0))(st, scal_b, nd_b)
            with jax.named_scope(SCOPE_PACK):
                flat = [leaf.reshape(leaf.shape[0], -1).astype(jnp.float64)
                        for leaf in jax.tree_util.tree_leaves(out)]
                packed = (jnp.concatenate(flat, axis=1) if flat
                          else jnp.zeros((bucket, 0)))
                # masked lanes zero via where, NOT multiply: sort lanes
                # hold -inf pads and -inf * 0 is NaN
                return jnp.where(valid_b[:, None], packed, 0.0)

        cached = (jax.jit(_named(stacked, f"qw_stacked_q{bucket}_k{k}")),
                  treedef, spec)
        _STACKED_CACHE[key] = cached
    return cached


# qwlint: disable-next-line=QW001 - host-side scalar staging (stack +
# single device_put); asarray/.item() run on numpy inputs pre-upload
def _device_group_scalars(plans, use_cache=True):
    """Per-slot [Q] scalar stacks + per-lane num_docs for a query group —
    each query contributes its OWN scalar values (threshold, search_after
    markers, rebase scale/min), stacked into query-axis lane vectors and
    moved in one batched H2D transfer. Shares `_MULTI_SCALAR_CACHE` with
    the convoy path (same content-addressed key space)."""
    batch = len(plans)
    key = None
    if use_cache:
        key = ("group", tuple(p.num_docs for p in plans), batch,
               tuple(tuple((s.dtype.str, s.item())
                           for s in map(np.asarray, p.scalars))
                     for p in plans))
        cached = _MULTI_SCALAR_CACHE.get(key)
        if cached is not None:
            return cached
    stacked = [np.stack([np.asarray(p.scalars[slot]) for p in plans])
               for slot in range(len(plans[0].scalars))]
    nd_b = np.asarray([p.num_docs for p in plans], np.int32)
    moved = jax.device_put(stacked + [nd_b])
    cached = (tuple(moved[:-1]), moved[-1])
    if key is not None:
        if len(_MULTI_SCALAR_CACHE) >= _MULTI_SCALAR_CACHE_CAP:
            _MULTI_SCALAR_CACHE.pop(next(iter(_MULTI_SCALAR_CACHE)))
        _MULTI_SCALAR_CACHE[key] = cached
    return cached


def dispatch_plan_stacked(plans, k: int, arrays_list, valid=None,
                          cache_scalars: bool = True,
                          exact: bool = False) -> tuple:
    """Async dispatch of len(plans) shape-compatible DISTINCT queries as
    ONE XLA program + ONE packed [Q, total] readback buffer. `plans[i]`
    and `arrays_list[i]` are query i's lowered plan and staged device
    arrays; all plans must share `signature(k)` (the QueryGroupPlanner
    guarantees this). `valid[i] = False` masks lane i out of the readback
    (zeroed row) without changing the compiled program — the late-shed
    rider path. Lane count pads to a power-of-two bucket (surplus lanes
    repeat the last query, pre-masked invalid)."""
    base = plans[0]
    k = max(0, min(k, base.num_docs_padded))
    SEARCH_KERNEL_LAUNCHES_TOTAL.inc()
    batch = len(plans)
    bucket = _batch_bucket(batch)
    if valid is None:
        valid = [True] * batch
    pad = bucket - batch
    with profiled_phase(PHASE_DISPATCH_PREPARE):
        plans_b = list(plans) + [plans[-1]] * pad
        arrays_b = list(arrays_list) + [arrays_list[-1]] * pad
        valid_b = np.zeros(bucket, np.bool_)
        valid_b[:batch] = list(valid)
        shared_slots, stacked_slots = stacked_slot_split(plans_b)
        scal_b, nd_b = _device_group_scalars(plans_b,
                                             use_cache=cache_scalars)
        shared_arrays = tuple(arrays_b[0][s] for s in shared_slots)
        lane_stacks = tuple(tuple(arrays_b[q][s] for q in range(bucket))
                            for s in stacked_slots)
        valid_dev = jax.device_put(valid_b)
    profile = current_profile()
    recording = flight.recording()
    # shared once-per-dispatch cache key (see dispatch_plan)
    key = (base.signature(k), bucket, stacked_slots, exact) \
        if (recording or profile is not None) else None
    if recording:
        f_hit = key in _STACKED_CACHE
        flight.emit("compile.hit" if f_hit else "compile.miss",
                    attrs={"path": "stacked", "bucket": bucket})
        flight.emit("dispatch.launch",
                    attrs={"path": "stacked", "lanes": batch})
    if profile is None:
        executor, treedef, spec = _get_packed_stacked_executor(
            base, k, bucket, stacked_slots, arrays_b[0], exact, key=key)
        out = executor(shared_arrays, lane_stacks, scal_b, nd_b, valid_dev)
    else:
        hit = key in _STACKED_CACHE
        profile.add("compile_cache_hits" if hit else "compile_cache_misses")
        with profile.phase(PHASE_EXECUTE if hit else PHASE_COMPILE,
                           stage="dispatch_stacked"):
            executor, treedef, spec = _get_packed_stacked_executor(
                base, k, bucket, stacked_slots, arrays_b[0], exact, key=key)
            out = executor(shared_arrays, lane_stacks, scal_b, nd_b,
                           valid_dev)
    if hasattr(out, "copy_to_host_async"):
        out.copy_to_host_async()
    return out, treedef, spec, batch, (list(plans), k, list(arrays_list),
                                       list(valid), cache_scalars)


# qwlint: disable-next-line=QW001 - stacked variant of the sanctioned
# packed-readback seam; one transfer for the whole query group
def readback_plan_stacked(dispatched) -> list:
    """ONE device→host transfer for the whole query group; per-lane
    unpack. Masked lanes come back as None (their packed row was zeroed on
    device). Valid lanes whose guided top-k screen reports `safe == 0`
    are re-dispatched as one exact stacked group and spliced back in —
    per-query tie-breaks therefore stay bit-identical to solo execution."""
    packed, treedef, spec, batch, redispatch = dispatched
    plans, k, arrays_list, valid, cache_scalars = redispatch
    host = np.asarray(_profiled_device_get(packed))
    results: list = []
    unsafe_lanes = []
    for lane in range(batch):
        if not valid[lane]:
            results.append(None)
            continue
        sort_vals, sort_vals2, doc_ids, hit_scores, count, topk_safe, \
            agg_out = _unpack_result(host[lane], treedef, spec)
        if float(topk_safe) < 1.0:
            unsafe_lanes.append(lane)
        results.append({
            "sort_values": sort_vals,
            "sort_values2": sort_vals2,
            "doc_ids": doc_ids,
            "scores": hit_scores,
            "count": int(count),
            "aggs": list(agg_out),
        })
    if unsafe_lanes:
        _note_guided_fallback(len(unsafe_lanes))
        exact = readback_plan_stacked(dispatch_plan_stacked(
            [plans[lane] for lane in unsafe_lanes], k,
            [arrays_list[lane] for lane in unsafe_lanes],
            cache_scalars=cache_scalars, exact=True))
        for lane, res in zip(unsafe_lanes, exact):
            results[lane] = res
    return results


def dispatch_plan(plan: LoweredPlan, k: int,
                  device_arrays: list[jax.Array], exact: bool = False):
    """Async dispatch: returns (packed_device_array, treedef, spec, ...)
    WITHOUT reading back — the pipelining seam (dispatch query i+1 before
    the readback of query i so concurrent queries amortize the host↔device
    RTT). The whole result tree rides ONE device array (see the packed-
    readback block above); `copy_to_host_async` starts the D2H transfer so
    the later blocking readback only waits out the remainder."""
    k = max(0, min(k, plan.num_docs_padded))
    SEARCH_KERNEL_LAUNCHES_TOTAL.inc()
    with profiled_phase(PHASE_DISPATCH_PREPARE):
        scalars, num_docs = _device_scalars(plan)
    args = (tuple(device_arrays), scalars, num_docs)
    profile = current_profile()
    recording = flight.recording()
    # plan.signature() walks the whole plan tree — compute the cache key
    # at most once per dispatch and share it between the flight event, the
    # profile attribution and the executor getter
    key = (plan.signature(k), exact) \
        if (recording or profile is not None) else None
    if recording:
        f_hit = key in _PACKED_CACHE
        flight.emit("compile.hit" if f_hit else "compile.miss",
                    attrs={"path": "solo"})
        flight.emit("dispatch.launch", attrs={"path": "solo", "lanes": 1})
    if profile is None:
        executor, treedef, spec = _get_packed_executor(plan, k, args, exact,
                                                       key=key)
        out = executor(*args)
    else:
        # Compile-vs-execute attribution: jax.jit compiles lazily on first
        # call, so on a packed-cache MISS this dispatch's wall time is
        # trace+XLA-compile (the dispatch itself is an async enqueue); on a
        # HIT it is a cheap enqueue counted toward execute. The
        # approximation is documented in docs/observability.md.
        hit = key in _PACKED_CACHE
        profile.add("compile_cache_hits" if hit else "compile_cache_misses")
        with profile.phase(PHASE_EXECUTE if hit else PHASE_COMPILE,
                           stage="dispatch"):
            executor, treedef, spec = _get_packed_executor(
                plan, k, args, exact, key=key)
            out = executor(*args)
    if hasattr(out, "copy_to_host_async"):
        out.copy_to_host_async()
    return out, treedef, spec, (plan, k, device_arrays)


def _note_guided_fallback(n: int = 1) -> None:
    """Count guided-top-k exact re-dispatches (f32 screen tie detected)."""
    from ..observability.metrics import METRICS
    METRICS.counter("qw_topk_guided_fallback_total").inc(n)


# qwlint: disable-next-line=QW001 - the sanctioned seam's single-plan
# entry point; the blocking device_get IS the measured readback
def readback_plan_result(dispatched) -> dict[str, Any]:
    """ONE device→host transfer for the entire result tree, unpacked by
    the trace-time spec. A guided top-k lane reporting `safe == 0` is
    re-executed with the exact blockwise kernel before returning."""
    packed, treedef, spec, redispatch = dispatched
    profile = current_profile()
    t0 = _clock_monotonic() if flight.recording() else 0.0
    if profile is None:
        host = jax.device_get(packed)
    else:
        # the blocking readback absorbs the device execution time
        with profile.phase(PHASE_EXECUTE, stage="readback"):
            host = jax.device_get(packed)
    if flight.recording():
        flight.emit("dispatch.readback", attrs={
            "dur_ms": round((_clock_monotonic() - t0) * 1000.0, 3)})
    sort_vals, sort_vals2, doc_ids, hit_scores, count, topk_safe, agg_out = \
        _unpack_result(host, treedef, spec)
    if float(topk_safe) < 1.0:
        plan, k, device_arrays = redispatch
        _note_guided_fallback()
        return readback_plan_result(
            dispatch_plan(plan, k, device_arrays, exact=True))
    return {
        "sort_values": sort_vals,
        "sort_values2": sort_vals2,
        "doc_ids": doc_ids,
        "scores": hit_scores,
        "count": int(count),
        "aggs": list(agg_out),
    }


def execute_plan(plan: LoweredPlan, k: int,
                 device_arrays: list[jax.Array]) -> dict[str, Any]:
    """Run the plan; returns host-side numpy results."""
    return readback_plan_result(dispatch_plan(plan, k, device_arrays))


def executor_cache_size() -> int:
    return len(_JIT_CACHE)


# --- static-audit hooks (tools/qwir) -----------------------------------------
#
# The auditor (`python -m tools.qwir audit`) abstract-evals the SAME
# closures the dispatch paths jit — `_build`, the vmapped multi-query
# wrapper, the mask-fill kernel — over ShapeDtypeStructs. The audited
# jaxpr therefore IS the program the compile caches key (modulo the packed
# f64 readback concat, which is audited separately as the sanctioned
# seam), with zero compilation, zero data movement, and zero devices
# touched. The `*_cache_key` mirrors must stay in lockstep with the
# dict-key expressions in `get_executor` / `_get_packed_executor` /
# `_get_packed_multi_executor` / `_get_packed_stacked_executor` /
# `compute_packed_mask` — the R1 closure certificate is only a proof if
# the audited key IS the cache key.

def program_cache_key(plan: LoweredPlan, k: int, exact: bool = False) -> tuple:
    """The `_JIT_CACHE`/`_PACKED_CACHE` key for this plan, post k-clamp."""
    k = max(0, min(k, plan.num_docs_padded))
    return (plan.signature(k), exact)


def multi_program_cache_key(plan: LoweredPlan, k: int, batch: int,
                            exact: bool = False) -> tuple:
    """The `_MULTI_CACHE` key (batch already bucketed by the caller)."""
    k = max(0, min(k, plan.num_docs_padded))
    return (plan.signature(k), batch, exact)


def stacked_program_cache_key(plans, k: int, bucket=None,
                              exact: bool = False) -> tuple:
    """The `_STACKED_CACHE` key for a query group — MUST stay in lockstep
    with the dict-key expression in `_get_packed_stacked_executor` (same
    R1 lockstep contract as the other mirrors above)."""
    base = plans[0]
    k = max(0, min(k, base.num_docs_padded))
    if bucket is None:
        bucket = _batch_bucket(len(plans))
    _, stacked_slots = stacked_slot_split(plans)
    return (base.signature(k), bucket, stacked_slots, exact)


def abstract_stacked_program(plans, k: int, bucket=None,
                             exact: bool = False):
    """ClosedJaxpr of the stacked query-group program for one batch bucket
    (the closure `_get_packed_stacked_executor` jits, minus the packed f64
    concat — audited separately as the sanctioned seam; the validity mask
    is applied per-leaf so the zeroed-readback semantics stay in the
    audited body)."""
    base = plans[0]
    k = max(0, min(k, base.num_docs_padded))
    if bucket is None:
        bucket = _batch_bucket(len(plans))
    fn = _build(base, k, exact)
    nslots = len(base.arrays)
    shared_slots, stacked_slots = stacked_slot_split(plans)
    arrays, scalars, _ = _abstract_inputs(base)
    shared = tuple(arrays[s] for s in shared_slots)
    lane_stacks = tuple(tuple(arrays[s] for _ in range(bucket))
                        for s in stacked_slots)
    scal_b = tuple(jax.ShapeDtypeStruct((bucket,) + s.shape, s.dtype)
                   for s in scalars)
    nd_b = jax.ShapeDtypeStruct((bucket,), np.int32)
    valid_b = jax.ShapeDtypeStruct((bucket,), np.bool_)

    def assemble(shared_arrays, lane_arrays):
        out = [None] * nslots
        for i, s in enumerate(shared_slots):
            out[s] = shared_arrays[i]
        for i, s in enumerate(stacked_slots):
            out[s] = lane_arrays[i]
        return tuple(out)

    def stacked(shared_arrays, lane_stacks, scal_b, nd_b, valid_b):
        st = tuple(jnp.stack(qs) for qs in lane_stacks)
        out = jax.vmap(
            lambda lane, s, n: fn(assemble(shared_arrays, lane), s, n),
            in_axes=(0, 0, 0))(st, scal_b, nd_b)
        return jax.tree_util.tree_map(
            lambda leaf: jnp.where(
                valid_b.reshape((bucket,) + (1,) * (leaf.ndim - 1)),
                leaf, jnp.zeros_like(leaf)),
            out)

    return jax.make_jaxpr(stacked)(shared, lane_stacks, scal_b, nd_b,
                                   valid_b)


def mask_fill_cache_key(plan: LoweredPlan) -> tuple:
    """The `_MASK_FILL_CACHE` key for this plan's predicate-only kernel."""
    return (plan.root.sig(),
            tuple((a.shape, str(a.dtype)) for a in plan.arrays),
            tuple(str(s.dtype) for s in map(np.asarray, plan.scalars)),
            plan.num_docs_padded)


def _abstract_inputs(plan: LoweredPlan):
    arrays = tuple(jax.ShapeDtypeStruct(a.shape, a.dtype)
                   for a in plan.arrays)
    scalars = tuple(jax.ShapeDtypeStruct(s.shape, s.dtype)
                    for s in map(np.asarray, plan.scalars))
    return arrays, scalars, jax.ShapeDtypeStruct((), np.int32)


def abstract_program(plan: LoweredPlan, k: int, exact: bool = False):
    """ClosedJaxpr of the single-split leaf program — traced, never run."""
    k = max(0, min(k, plan.num_docs_padded))
    fn = _build(plan, k, exact)
    arrays, scalars, num_docs = _abstract_inputs(plan)
    return jax.make_jaxpr(fn)(arrays, scalars, num_docs)


def abstract_multi_program(plan: LoweredPlan, k: int, batch: int,
                           exact: bool = False):
    """ClosedJaxpr of the vmapped multi-query program for one batch bucket
    (the closure `_get_packed_multi_executor` jits, minus the packed
    concat)."""
    k = max(0, min(k, plan.num_docs_padded))
    fn = _build(plan, k, exact)
    arrays, scalars, _ = _abstract_inputs(plan)
    scal_b = tuple(jax.ShapeDtypeStruct((batch,) + s.shape, s.dtype)
                   for s in scalars)
    nd_b = jax.ShapeDtypeStruct((batch,), np.int32)

    def multi(arrays, scal_b, nd_b):
        return jax.vmap(lambda s, n: fn(arrays, s, n),
                        in_axes=(0, 0))(scal_b, nd_b)

    return jax.make_jaxpr(multi)(arrays, scal_b, nd_b)


def abstract_mask_fill(plan: LoweredPlan):
    """ClosedJaxpr of the Tier-A predicate-mask fill kernel
    (`compute_packed_mask`'s jitted body)."""
    arrays, scalars, num_docs = _abstract_inputs(plan)
    return jax.make_jaxpr(_mask_fill_fn(plan))(arrays, scalars, num_docs)


def _mask_fill_fn(plan: LoweredPlan) -> Callable:
    """The unjitted mask-fill body: the plan's predicate root alone, packed.
    Reuses the SAME `_node_evaluator` as the search kernel, so the mask is
    bit-identical to inline evaluation by construction."""
    padded = plan.num_docs_padded
    root = plan.root
    eval_node = _node_evaluator(padded)

    def mask_fn(arrays, scalars, num_docs):
        # one outer scope: the fill's own predicate counts as mask_fill,
        # not as a second term_mask / range_filter
        with jax.named_scope(SCOPE_MASK_FILL):
            mask, _ = eval_node(root, arrays, scalars)
            mask = mask & mask_ops.valid_docs_mask(num_docs, padded)
            return _pack_mask(mask, padded)

    return _named(mask_fn, "qw_mask_fill")


# qwir R2 certification registry: functions in THIS module allowed to mint
# doc-scale f64 lanes or feed f64 sorts. Keys are function qualnames as
# they appear in jaxpr eqn source frames; values are the justification the
# audit report carries. Keep justifications concrete — they are the
# "inline justified suppression" the acceptance gate requires.
QWIR_CERTIFIED_F64 = {
    "_keyed_for": (
        "the unified sort key IS f64 by contract: it must represent i64 "
        "column values and epoch-micros exactly (f32 collapses distinct "
        "timestamps). The corpus-scale-sort hazard this feeds is screened "
        "by guided_topk's f32 path; exact f64 sorts are certified at "
        "their ops/topk.py sites."),
    "_apply_search_after": (
        "search_after eligibility rewrites the f64 key lanes in place "
        "(same dtype in, same dtype out) — no new f64 surface beyond "
        "_keyed_for's certified key."),
}


# --- predicate-mask fill (Tier A, search/mask_cache.py) ----------------------

_MASK_FILL_CACHE: dict[tuple, Callable] = {}


def compute_packed_mask(
        plan: LoweredPlan,
        device_arrays: list[jax.Array]) -> tuple[np.ndarray, jax.Array]:
    """Evaluate ONLY the plan's predicate root over already-staged device
    arrays and return `(host_packed, device_packed)` — the uint8 bitmask in
    np.packbits bit order, both as the host copy destined for the cache tier
    and as the still-device-resident original so callers can seed it into a
    warm split's residency cache without a round trip.

    Runs as its own tiny jitted kernel right after the main execute, while
    the split's arrays are still pinned — so a fill costs one extra launch
    plus a padded/8-byte readback, not a re-staging. Callers must gate on
    `plan.count_override is None` — an impact-prefix-truncated plan
    (format v3) never saw the posting tail, so its mask would be
    incomplete."""
    padded = plan.num_docs_padded
    root = plan.root
    key = (root.sig(),
           tuple((a.shape, str(a.dtype)) for a in plan.arrays),
           tuple(str(s.dtype) for s in plan.scalars),
           padded)
    # a second program and a blocking readback behind whatever the device
    # has queued: the request that fills pays for it, so it is a phase
    profile_add("mask_fills")
    with profiled_phase(PHASE_MASK_FILL) as rec:
        fill = _MASK_FILL_CACHE.get(key)
        if rec is not None:
            rec["compiled"] = fill is None
        if fill is None:
            fill = jax.jit(_mask_fill_fn(plan))
            _MASK_FILL_CACHE[key] = fill
        scalars, num_docs = _device_scalars(plan)
        SEARCH_KERNEL_LAUNCHES_TOTAL.inc()
        packed = fill(tuple(device_arrays), scalars, num_docs)
        # qwlint: disable-next-line=QW001 - deliberate padded/8-byte readback
        # of the freshly computed mask into the host-side cache tier
        return np.asarray(jax.device_get(packed), dtype=np.uint8), packed
