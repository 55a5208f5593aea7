"""Multi-split, multi-chip query execution over a device mesh.

Role of the reference's query fan-out + scatter-gather merge tree
(SURVEY.md §2.3: rendezvous job placement → per-node leaf batches → per-split
tasks → `IncrementalCollector` merges → root `merge_fruits`), re-designed for
TPU: the split dimension becomes a **mesh axis**, the merge tree becomes XLA
collectives over ICI (the pmap'd merge of BASELINE.json):

    mesh = Mesh(devices, ("splits", "docs"))
    arrays: postings stacked [n_splits, plen]       → P("splits")
            columns stacked  [n_splits, padded]     → P("splits", "docs")
    shard_map: each device searches its split shard over its doc shard
      - per-split kernel vmapped over the local split batch
      - doc-axis partials merged by psum (counts/aggs) and
        all_gather + re-top-k (hits) over ICI
      - split-axis partials likewise

The doc axis is the long-dimension ("sequence parallel") analogue: one huge
split's dense doc arrays are sharded across chips, with the same
collective-merge pattern (SURVEY.md §5.7).

Batch restrictions (checked at build): all splits share one doc-mapping and
the query must lower to a split-independent structure — wildcard/regex/
phrase-prefix expand differently per split and fall back to per-split
sequential leaf search in the search service.
"""

from __future__ import annotations

import threading
from dataclasses import dataclass
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from ..common.clock import monotonic as _clock_monotonic
from ..index.format import ZONEMAP_BLOCK
from ..index.reader import SplitReader
from ..models.doc_mapper import DocMapper
from ..observability import flight
from ..observability.metrics import (
    MESH_COLLECTIVE_BYTES_TOTAL, MESH_DEVICES, MESH_DISPATCHES_TOTAL,
    MESH_THRESHOLD_EXCHANGE_ROUNDS_TOTAL, SEARCH_KERNEL_LAUNCHES_TOTAL,
)
from ..observability.profile import (
    PHASE_COMPILE, PHASE_EXECUTE, PHASE_PLAN_BUILD, PHASE_STAGING_CACHE_HIT,
    PHASE_STAGING_UPLOAD, PHASE_TOPK_MERGE, SCOPE_PACK,
    current_profile, profile_add, profiled_phase,
)
from ..query.aggregations import DateHistogramAgg, HistogramAgg, TermsAgg, parse_aggs
from ..search.models import LeafSearchResponse, PartialHit, SearchRequest
from ..search.plan import BucketAggExec, LoweredPlan, MetricAggExec, lower_request
from ..search import executor as executor_mod
from ..search.leaf import (
    _intermediate_aggs, _sort_values_are_int, decode_sort_value_exact,
)


def make_mesh(axis_splits: int, axis_docs: int = 1,
              devices: Optional[Sequence] = None) -> Mesh:
    devs = np.asarray(devices if devices is not None else jax.devices())
    need = axis_splits * axis_docs
    if devs.size < need:
        raise ValueError(f"need {need} devices, have {devs.size}")
    return Mesh(devs[:need].reshape(axis_splits, axis_docs), ("splits", "docs"))


# --------------------------------------------------------------------------

@dataclass
class SplitBatch:
    """Same-structure plans for one query over many splits, stacked."""
    template: LoweredPlan                 # structure donor (slots/signature)
    arrays: list[np.ndarray]              # slot-indexed, stacked [n, ...]
    scalars: list[np.ndarray]             # slot-indexed, stacked [n]
    num_docs: np.ndarray                  # [n] int32
    split_ids: list[str]                  # n entries ("" = padding split)
    num_docs_padded: int                  # uniform padded doc count
    doc_mapper: DocMapper
    sort_field: str
    sort_order: str
    sort2_field: Optional[str] = None     # secondary sort key (2-key sorts)
    sort2_order: str = "desc"
    readers: list[SplitReader] = None  # for exact int sort-value re-reads

    @property
    def n_splits(self) -> int:
        return len(self.split_ids)


def _global_agg_overrides(agg_specs, readers: list[SplitReader],
                          doc_mapper: DocMapper) -> dict:
    """Compute batch-global bucket spaces so per-split states merge on device."""
    histograms: dict[str, tuple[int, int]] = {}
    terms_dicts: dict[str, dict] = {}
    terms_cards: dict[str, int] = {}
    terms_keys: dict[str, list] = {}
    from ..search.plan import MAX_BUCKETS, PlanError
    # nested child buckets need batch-global spaces too (their per-split
    # ordinal/origin spaces would otherwise be summed incoherently on
    # device); children key under "parent>child" since ES names are only
    # unique per level
    expanded: list = []

    from ..query.aggregations import CompositeAgg

    def _expand(spec, path):
        if isinstance(spec, CompositeAgg):
            # composite is per-split by design (split-local key
            # encodings) — lowering raises before any override is read,
            # so computing cross-reader dictionaries here is pure waste
            return
        expanded.append((spec, path))
        for sub in getattr(spec, "sub_buckets", ()):
            _expand(sub, f"{path}>{sub.name}")

    for spec in agg_specs:
        _expand(spec, spec.name)
    for spec, override_key in expanded:
        if isinstance(spec, (DateHistogramAgg, HistogramAgg)):
            vmins, vmaxs = [], []
            for r in readers:
                meta = r.field_meta(spec.field)
                if meta.get("min_value") is not None:
                    vmins.append(meta["min_value"])
                    vmaxs.append(meta["max_value"])
            if isinstance(spec, DateHistogramAgg) and spec.extended_bounds:
                vmins.append(spec.extended_bounds[0])
                vmaxs.append(spec.extended_bounds[1])
            if not vmins:
                histograms[override_key] = (0, 1)
                continue
            interval = spec.interval_micros if isinstance(spec, DateHistogramAgg) \
                else spec.interval
            if isinstance(spec, DateHistogramAgg):
                offset = getattr(spec, "offset_micros", 0)
                origin = ((min(vmins) - offset) // interval) * interval \
                    + offset
            else:
                origin = float(np.floor(min(vmins) / interval) * interval)
            num_buckets = int((max(vmaxs) - origin) // interval) + 1
            if num_buckets > MAX_BUCKETS:
                raise PlanError(
                    f"aggregation {spec.name!r} would create {num_buckets} "
                    f"buckets over the batch (max {MAX_BUCKETS})")
            histograms[override_key] = (origin if isinstance(spec, HistogramAgg)
                                        else int(origin), num_buckets)
        elif isinstance(spec, TermsAgg):
            union: set = set()
            for r in readers:
                meta = r.field_meta(spec.field)
                if meta.get("column_kind") == "ordinal":
                    union.update(r.column_dict(spec.field))
                else:
                    from ..search.plan import ordinalize_numeric_column
                    _, keys = ordinalize_numeric_column(r, spec.field)
                    union.update(keys)
            keys_sorted = sorted(union, key=lambda v: (str(type(v)), v))
            terms_dicts[spec.field] = {k: i for i, k in enumerate(keys_sorted)}
            terms_cards[spec.field] = len(keys_sorted)
            terms_keys[spec.field] = keys_sorted
    return {"histograms": histograms, "terms_dicts": terms_dicts,
            "terms_cards": terms_cards, "terms_keys": terms_keys}


def _pad_fill(key: str, num_docs_padded: int, dtype=None):
    if key.startswith("post.") and key.endswith(".ids"):
        return num_docs_padded        # OOB scatter sentinel
    if key.startswith("pre.") and key.endswith(".ids"):
        return num_docs_padded
    if "ordinals" in key:
        return -1
    if key.endswith(".zmin"):
        # inverted envelope: pad blocks never qualify (harmless either way —
        # their doc lanes carry present=0 — but keep the zonemaps honest)
        return np.inf if dtype.kind == "f" else np.iinfo(dtype).max
    if key.endswith(".zmax"):
        return -np.inf if dtype.kind == "f" else np.iinfo(dtype).min
    return 0


def build_batch(request: SearchRequest, doc_mapper: DocMapper,
                readers: list[SplitReader], split_ids: list[str],
                pad_to_splits: Optional[int] = None,
                absence_sink=None,
                sort_value_threshold: Optional[float] = None) -> SplitBatch:
    """`absence_sink(split_id, field, term)`: term-dictionary misses found
    during lowering, fed to the predicate/negative cache.

    `sort_value_threshold` is the batch-wide dynamic top-K threshold
    (internal encoding): the same value is lowered into every lane's plan,
    so slot layouts stay uniform and the pushdown rides the existing
    stacked-scalar machinery."""
    # plan_build covers per-split lowering (storage byte-range IO surfaces
    # as storage_read_* counters) plus the host-side lane stacking
    with profiled_phase(PHASE_PLAN_BUILD) as rec:
        if rec is not None:
            rec["splits"] = len(split_ids)
            rec["stage"] = "batch"
        return _build_batch(request, doc_mapper, readers, split_ids,
                            pad_to_splits, absence_sink, sort_value_threshold)


def _build_batch(request: SearchRequest, doc_mapper: DocMapper,
                 readers: list[SplitReader], split_ids: list[str],
                 pad_to_splits: Optional[int],
                 absence_sink,
                 sort_value_threshold: Optional[float]) -> SplitBatch:
    agg_specs = parse_aggs(request.aggs) if request.aggs else []
    overrides = _global_agg_overrides(agg_specs, readers, doc_mapper)
    # a term absent from one split lowers to the uniform empty stand-in,
    # whose impact_ordered flag is part of the plan sig since format v3:
    # the stand-in must agree with the splits that DO hold the field, so
    # the lowering needs cross-reader visibility (an empty posting list is
    # vacuously sound under either storage-order claim)
    overrides["batch_readers"] = readers
    sort = request.sort_fields[0] if request.sort_fields else None
    sort_field = sort.field if sort else "_score"
    sort_order = sort.order if sort else "desc"
    sort2 = request.sort_fields[1] if len(request.sort_fields) > 1 else None

    num_docs_padded = max(r.num_docs_padded for r in readers)
    plans: list[LoweredPlan] = []
    for reader, split_id in zip(readers, split_ids, strict=True):
        plan = lower_request(
            request.query_ast, doc_mapper, reader, agg_specs,
            sort_field=sort_field, sort_order=sort_order,
            sort2_field=sort2.field if sort2 else None,
            sort2_order=sort2.order if sort2 else "desc",
            start_timestamp=request.start_timestamp,
            end_timestamp=request.end_timestamp,
            batch_overrides=overrides,
            absence_sink=(None if absence_sink is None else
                          lambda f, t, s=split_id: absence_sink(s, f, t)),
            sort_value_threshold=sort_value_threshold,
        )
        plans.append(plan)
    sigs = {p.root.sig() + p.sort.sig() + ",".join(a.sig() for a in p.aggs)
            for p in plans}
    if len(sigs) != 1:
        raise ValueError(
            "query does not lower to a uniform structure across splits "
            "(wildcard/regex/phrase-prefix queries need per-split execution)")

    template = plans[0]
    n = len(plans)
    total = pad_to_splits or n
    if total < n:
        raise ValueError(
            f"pad_to_splits={pad_to_splits} is smaller than the number of "
            f"splits ({n})")
    num_slots = len(template.arrays)

    stacked_arrays: list[np.ndarray] = []
    for slot in range(num_slots):
        key = template.array_keys[slot]
        per_split = [p.arrays[slot] for p in plans]
        dtype = per_split[0].dtype
        if any(a.dtype != dtype for a in per_split[1:]):
            # e.g. FOR-packed lanes of different widths (u8 vs u16), or a
            # packed/raw mix whose slot layout happened to coincide —
            # numpy slice assignment would truncate silently, so refuse
            # and let the service fall back to per-split execution
            raise ValueError(
                f"array slot {key!r} has non-uniform dtypes across splits "
                "(mixed column packings need per-split execution)")
        fill = _pad_fill(key, num_docs_padded, dtype)
        # uniform last-dim length: postings pad to max, doc-dim pad to padded
        max_len = max(a.shape[0] for a in per_split)
        if key.endswith((".zmin", ".zmax")):
            max_len = num_docs_padded // ZONEMAP_BLOCK
        elif key.startswith(("col.", "norm.")):
            max_len = num_docs_padded
        out = np.full((total, max_len), fill, dtype=dtype)
        for i, a in enumerate(per_split):
            out[i, : a.shape[0]] = a
        stacked_arrays.append(out)

    stacked_scalars: list[np.ndarray] = []
    for slot in range(len(template.scalars)):
        vals = [np.asarray(p.scalars[slot]) for p in plans]
        if any(v.dtype != vals[0].dtype for v in vals[1:]):
            raise ValueError(
                f"scalar slot {slot} has non-uniform dtypes across splits "
                "(mixed column packings need per-split execution)")
        out = np.zeros(total, dtype=vals[0].dtype)
        for i, v in enumerate(vals):
            out[i] = v
        stacked_scalars.append(out)

    num_docs = np.zeros(total, dtype=np.int32)
    num_docs[:n] = [p.num_docs for p in plans]
    ids = list(split_ids) + [""] * (total - n)

    # retarget the template's padded size to the batch-uniform one
    template.num_docs_padded = num_docs_padded
    return SplitBatch(
        template=template, arrays=stacked_arrays, scalars=stacked_scalars,
        num_docs=num_docs, split_ids=ids, num_docs_padded=num_docs_padded,
        doc_mapper=doc_mapper, sort_field=sort_field, sort_order=sort_order,
        sort2_field=sort2.field if sort2 else None,
        sort2_order=sort2.order if sort2 else "desc",
        readers=list(readers),
    )


# --------------------------------------------------------------------------
# merged execution

_BATCH_JIT_CACHE: dict[tuple, Any] = {}


def _agg_leaf_kind(path) -> Optional[str]:
    """The innermost dict key on a merged-agg leaf's tree path — what
    decides its combiner (min / max / hll / stats / sum)."""
    for p in reversed(path):
        if hasattr(p, "key"):
            return p.key
    return None


def batch_shardings(batch: SplitBatch, mesh: Mesh):
    """NamedShardings for the stacked inputs: every slot is sharded over the
    'splits' axis; dense per-doc slots (columns, fieldnorms) additionally
    shard their doc dimension over the 'docs' axis (the long-dimension /
    sequence-parallel analogue). XLA GSPMD inserts the ICI collectives for
    the cross-shard reductions and top-k merges."""
    from jax.sharding import NamedSharding
    array_shardings = []
    for key in batch.template.array_keys:
        if key.startswith(("col.", "norm.")) \
                and not key.endswith((".zmin", ".zmax")):
            array_shardings.append(NamedSharding(mesh, P("splits", "docs")))
        else:
            # zonemaps are per-BLOCK (padded/512), not per-doc: replicate
            # along the doc axis so block gating never crosses shards
            array_shardings.append(NamedSharding(mesh, P("splits", None)))
    scalar_shardings = [NamedSharding(mesh, P("splits"))] * len(batch.template.scalars)
    nd_sharding = NamedSharding(mesh, P("splits"))
    return tuple(array_shardings), tuple(scalar_shardings), nd_sharding


def _mesh_axes(mesh: Mesh) -> tuple[str, Optional[str]]:
    """(split_axis_name, doc_axis_name) of a fanout mesh. Axis names come
    from the mesh itself (not hard-coded literals) so qwir's R4 planted-
    defect fixtures can trace the SAME program builder over a mis-named
    mesh and watch the rule fire."""
    names = mesh.axis_names
    return names[0], (names[1] if len(names) > 1 else None)


def _check_mesh_divides(batch: SplitBatch, mesh: Mesh) -> None:
    """NamedSharding refuses ragged dimension-0 shards, so a split axis
    that does not divide the batch has no program to run. The service's
    `device_mesh` only hands out dividing axes; this guards direct
    `dispatch_batch` / `stage_device_inputs` callers."""
    split_ax, _doc_ax = _mesh_axes(mesh)
    axis_splits = mesh.shape[split_ax]
    if batch.n_splits % axis_splits:
        raise ValueError(
            f"n_splits={batch.n_splits} does not shard over the "
            f"{axis_splits}-way {split_ax!r} mesh axis (pad the batch)")


def _all_reduce_extremum(x, axis_name: str, op: str):
    """Cross-device `max`/`min` over a mesh axis, replicated on every
    device. The TPU compiler lowers only *sum* all-reduces on 64-bit
    element types (f64/i64 are emulated there; a 64-bit pmax/pmin is
    refused with UNIMPLEMENTED), so 64-bit operands `all_gather` along the
    axis and reduce locally — the same values under the same total order,
    hence bit-identical to pmax/pmin and to the host merge. Narrower
    dtypes keep the native all-reduce."""
    from jax import lax
    if x.dtype.itemsize < 8:
        return (lax.pmax if op == "max" else lax.pmin)(x, axis_name)
    gathered = lax.all_gather(x, axis_name)          # [axis_size, ...]
    return (jnp.max if op == "max" else jnp.min)(gathered, axis=0)


def _merge_agg_collective(agg_out, split_ax: str):
    """Merge the per-split agg states: leaves carry a leading split axis
    [local_n, ...] that reduces over axis 0 on each device (counts and
    sums add, min/max/hll combine by leaf name), then the SAME per-leaf
    combiner runs once more across the split mesh axis (psum /
    `_all_reduce_extremum`), so the merged states land replicated on every
    device — no host merge.

    Exactness: counts, bucket tallies, and HLL registers are integral-
    valued, so f64 reduction re-association cannot change them; float
    metric sums reassociate across the device tree exactly like the host
    `jnp.sum` already could across lanes (docs/multichip.md spells out the
    contract the equivalence suite pins with integral fixtures)."""
    from jax import lax

    def red(path, leaf):
        name = _agg_leaf_kind(path)
        if name == "min":
            return _all_reduce_extremum(jnp.min(leaf, axis=0), split_ax,
                                        "min")
        if name in ("max", "hll"):  # HLL registers merge by max too
            return _all_reduce_extremum(jnp.max(leaf, axis=0), split_ax,
                                        "max")
        if name == "stats":
            # state vector [count, sum, sum_sq, min, max]: first three add
            return jnp.concatenate([
                lax.psum(jnp.sum(leaf[:, :3], axis=0), split_ax),
                _all_reduce_extremum(jnp.min(leaf[:, 3:4], axis=0),
                                     split_ax, "min"),
                _all_reduce_extremum(jnp.max(leaf[:, 4:5], axis=0),
                                     split_ax, "max"),
            ])
        return lax.psum(jnp.sum(leaf, axis=0), split_ax)
    return jax.tree_util.tree_map_with_path(red, agg_out)


def mesh_batch_fn(batch: SplitBatch, k: int, mesh: Mesh, exact: bool = False):
    """The whole query as ONE explicitly-collective SPMD program
    (shard_map): each device scores its split shard with the vmapped
    per-split kernel, then the root merge — formerly host Python in
    search/collector.py — runs on-mesh:

      1. threshold exchange: each device's k-th best primary sort value is
         all-reduce-max'd (`_all_reduce_extremum`) across the split axis.
         The max of the per-device k-th values lower-bounds the global
         k-th value (the winning device already holds k candidates at or
         above it), so every candidate STRICTLY below it is provably
         outside the global top-K and is masked to -inf — the cross-device analogue of
         ops/topk.apply_threshold_mask's `>=`-keeps-ties rule, composing
         with the cross-chunk threshold the collector threads between
         dispatches.
      2. top-K merge: surviving candidates `all_gather` along the split
         axis — device order equals split order under the P("splits")
         input sharding, so the concatenation is split-major and
         `lax.top_k`'s lowest-index tie-break reproduces the collector's
         (key desc, split_id asc, doc asc) total order bit-for-bit
         (2-key sorts ride `exact_topk_2key` over the gathered pairs).
      3. agg + count reduce: mergeable agg states, hit counts, and the
         guided-top-k certificate reduce via psum and
         `_all_reduce_extremum`.

    The doc mesh axis shards dense column storage at rest
    (`batch_shardings`); compute replicates along it here, so collectives
    bind only the split axis and every docs replica holds identical
    results — out_specs are fully replicated. One dispatch, one packed
    scalar readback."""
    from jax import lax

    template = batch.template
    single_fn = executor_mod._build(template, k, exact)
    _check_mesh_divides(batch, mesh)
    split_ax, _doc_ax = _mesh_axes(mesh)

    def shard_body(arrays, scalars, num_docs):
        results = jax.vmap(single_fn)(arrays, scalars, num_docs)
        sort_vals, sort_vals2, doc_ids, hit_scores, counts, topk_safe, \
            agg_out = results
        total = lax.psum(jnp.sum(counts), split_ax)
        # one certificate for the whole batch: any unsafe split taints the
        # cross-split merge, so the host re-runs the batch exactly
        safe = _all_reduce_extremum(jnp.min(topk_safe), split_ax, "min")
        merged = _merge_agg_collective(agg_out, split_ax)
        if k == 0:  # count/agg-only: no candidates to exchange or gather
            empty_i = jnp.zeros((0,), jnp.int32)
            return (jnp.zeros((0,), sort_vals.dtype), None, empty_i, empty_i,
                    jnp.zeros((0,), hit_scores.dtype), total, safe, merged)
        flat = sort_vals.reshape(-1)          # [local_n * k], split-major
        neg_inf = jnp.asarray(-jnp.inf, flat.dtype)
        # -- threshold exchange (one all-reduce-max round per dispatch) --
        local_kth = lax.top_k(flat, k)[0][k - 1]
        threshold = _all_reduce_extremum(local_kth, split_ax, "max")
        keep = flat >= threshold              # >= keeps threshold ties
        flat = jnp.where(keep, flat, neg_inf)
        # -- split-axis gather + re-top-k --------------------------------
        g_vals = lax.all_gather(flat, split_ax, axis=0, tiled=True)
        g_ids = lax.all_gather(doc_ids.reshape(-1), split_ax,
                               axis=0, tiled=True)
        g_scores = lax.all_gather(hit_scores.reshape(-1), split_ax,
                                  axis=0, tiled=True)
        if sort_vals2 is None:
            top_vals, pos = lax.top_k(g_vals, k)
            top_vals2 = None
        else:
            flat2 = jnp.where(keep, sort_vals2.reshape(-1), neg_inf)
            g_vals2 = lax.all_gather(flat2, split_ax, axis=0, tiled=True)
            from ..ops import topk as topk_ops
            top_vals, top_vals2, pos = topk_ops.exact_topk_2key(
                g_vals, g_vals2, k)
        split_idx = (pos // k).astype(jnp.int32)
        return (top_vals, top_vals2, split_idx, g_ids[pos], g_scores[pos],
                total, safe, merged)

    in_arrays = tuple(P(split_ax) for _ in batch.arrays)
    in_scalars = tuple(P(split_ax) for _ in batch.scalars)
    return jax.shard_map(shard_body, mesh=mesh,
                         in_specs=(in_arrays, in_scalars, P(split_ax)),
                         out_specs=P(), check_vma=False)


def batch_cache_key(batch: SplitBatch, k: int, mesh: Mesh,
                    exact: bool = False) -> tuple:
    """The `_BATCH_JIT_CACHE` key `dispatch_batch` uses, post k-clamp —
    mirrored here for tools/qwir's compile-cache closure certificate (must
    stay in lockstep with the key expression in `dispatch_batch`)."""
    k = min(k, batch.num_docs_padded)
    return (batch.template.signature(k), batch.n_splits,
            batch.num_docs_padded, mesh, exact)


def abstract_mesh_batch_program(batch: SplitBatch, k: int, mesh: Mesh,
                                exact: bool = False):
    """ClosedJaxpr of the collective whole-query program (`mesh_batch_fn`,
    minus the packed f64 readback concat) — abstract-traced, never
    compiled or executed. The collectives are EXPLICIT eqns (shard_map +
    psum/all_gather, and pmax/pmin on 32-bit leaves), which is what makes
    qwir R4's mesh-axis rule load-bearing: every collective must bind axes
    declared by the program's ProgramSpec."""
    k = min(max(0, k), batch.num_docs_padded)
    fn = mesh_batch_fn(batch, k, mesh, exact)
    arrays = tuple(jax.ShapeDtypeStruct(a.shape, a.dtype)
                   for a in batch.arrays)
    scalars = tuple(jax.ShapeDtypeStruct(s.shape, s.dtype)
                    for s in batch.scalars)
    nd = jax.ShapeDtypeStruct(batch.num_docs.shape, batch.num_docs.dtype)
    return jax.make_jaxpr(fn)(arrays, scalars, nd)


# qwir R2 certification registry (see executor.py's for semantics): the
# cross-split merge re-top-k's the flattened per-split winners — an f64
# sort over n_splits*k lanes, O(fan-out × page size), NOT corpus-scale.
# The corpus-scale sorts it consumes already ran under the certified
# ops/topk.py kernels inside the vmapped per-split programs.
# keyed by qualname, as the jaxpr eqn source frames report it
QWIR_CERTIFIED_F64 = {
    "mesh_batch_fn.<locals>.shard_body": (
        "mesh_batch_fn's on-mesh root merge: lax.top_k / exact_topk_2key "
        "over the all_gather'd [n_splits*k] threshold-surviving winners, "
        "plus the k-element threshold exchange sort — bounded by fan-out "
        "times page size, never by corpus size."),
}


def _collective_payload_bytes(shaped, k: int, n_splits: int,
                              axis_splits: int) -> int:
    """Logical bytes the mesh program's collectives carry per dispatch
    (`qw_mesh_collective_bytes_total` semantics): all_gather candidates +
    the reduced agg/count/certificate leaves + the threshold exchange.
    `shaped` is the eval_shape output tree of `mesh_batch_fn`. A 64-bit
    max/min travels as an all_gather over the `axis_splits` devices of the
    split axis (`_all_reduce_extremum`), so it counts once per device;
    sums and narrower max/mins count once."""
    def extremum(n_elems: int, dtype) -> int:
        itemsize = np.dtype(dtype).itemsize
        return n_elems * itemsize * (axis_splits if itemsize >= 8 else 1)

    def leaf_bytes(path, leaf) -> int:
        n_elems = int(np.prod(leaf.shape))
        kind = _agg_leaf_kind(path)
        if kind in ("min", "max", "hll"):
            return extremum(n_elems, leaf.dtype)
        if kind == "stats":     # [count, sum, sum_sq] add; [min, max] gather
            return (n_elems // 5 * 3 * leaf.dtype.itemsize
                    + extremum(n_elems // 5 * 2, leaf.dtype))
        return n_elems * leaf.dtype.itemsize

    vals, vals2, _split_idx, _ids, _scores, total, safe, merged = shaped
    gather = 0 if k == 0 else \
        n_splits * k * (8 + (8 if vals2 is not None else 0) + 4 + 4)
    reduced = sum(jax.tree_util.tree_leaves(
        jax.tree_util.tree_map_with_path(leaf_bytes, merged)))
    # total count (psum) + safe certificate (f64 min)
    reduced += total.dtype.itemsize + extremum(1, safe.dtype)
    exchange = 0 if k == 0 else extremum(1, vals.dtype)
    return gather + reduced + exchange


def _batch_executor(batch: SplitBatch, k: int, mesh: Mesh,
                    example_args, exact: bool = False):
    """(jitted_packed_fn, treedef, spec, meta): the explicitly-collective
    `mesh_batch_fn` (the whole root merge on-device), its merged result
    tree riding ONE f64 device array so the readback is a single transfer
    (see executor.py packed-readback rationale; exactness argument
    identical). Never donates: the staged tuples may alias mesh-resident
    column stacks (`_stage_resident_stack`) that must survive the query."""
    fn = mesh_batch_fn(batch, k, mesh, exact)
    shaped = jax.eval_shape(fn, *example_args)
    treedef = jax.tree_util.tree_structure(shaped)
    spec = [(leaf.shape, leaf.dtype)
            for leaf in jax.tree_util.tree_leaves(shaped)]
    meta = {"collective_bytes": _collective_payload_bytes(
        shaped, k, batch.n_splits, mesh.shape[_mesh_axes(mesh)[0]])}

    def packed(arrays, scalars, num_docs):
        out = fn(arrays, scalars, num_docs)
        with jax.named_scope(SCOPE_PACK):
            flat = [leaf.reshape(-1).astype(jnp.float64)
                    for leaf in jax.tree_util.tree_leaves(out)]
            return jnp.concatenate(flat) if flat else jnp.zeros((0,))

    # static program name from the cache key alone: family, lanes, k, mesh
    executor_mod._named(
        packed, f"qw_batch_s{batch.n_splits}_k{k}_mesh{mesh.size}")
    arrays_sh, scalars_sh, nd_sh = batch_shardings(batch, mesh)
    return (jax.jit(packed, in_shardings=(arrays_sh, scalars_sh, nd_sh)),
            treedef, spec, meta)


# Column-family slots are query-independent given the split set: packed
# fast-field values, fieldnorms, and their zonemaps derive only from the
# readers and the batch-uniform padded size (even batch-global ordinal
# spaces: the terms dictionary union is over the SPLIT SET, not the
# query). Postings ("pre."/"post.") and masks are query-shaped — for
# format v3 threshold pushdown even re-sliced per threshold — so they
# stream per request and are never stack-resident.
_STACK_RESIDENT_PREFIXES = ("col.", "norm.")


def stack_resident_slots(batch: SplitBatch) -> list[int]:
    """Array slots eligible for the cross-query mesh-resident stack."""
    return [slot for slot, key in enumerate(batch.template.array_keys)
            if key.startswith(_STACK_RESIDENT_PREFIXES)]


def per_device_bytes(batch: SplitBatch, mesh: Mesh,
                     exclude_stack_resident: bool = False) -> int:
    """The PER-DEVICE HBM footprint of the staged batch — what tenant-DRR
    admission pins: the stacks shard over the mesh. Dense column slots
    divide across both axes (P("splits", "docs")); everything else
    divides across the split axis only (`batch_shardings`).

    `exclude_stack_resident` drops the column-family slots: when the
    mesh-resident stack store is active those bytes are admitted under
    the stack owner by `stage_device_inputs` (and stay resident after the
    query), so admitting them under the per-request batch owner too would
    double-pin warm queries."""
    split_ax, doc_ax = _mesh_axes(mesh)
    n_sp = mesh.shape[split_ax]
    n_doc = mesh.shape.get(doc_ax, 1) if doc_ax else 1
    resident = set(stack_resident_slots(batch)) if exclude_stack_resident \
        else set()
    total = 0
    for slot, (key, a) in enumerate(zip(batch.template.array_keys,
                                        batch.arrays)):
        if slot in resident:
            continue
        if key.startswith(("col.", "norm.")) \
                and not key.endswith((".zmin", ".zmax")):
            total += -(-a.nbytes // (n_sp * n_doc))
        else:
            total += -(-a.nbytes // n_sp)
    total += sum(-(-s.nbytes // n_sp) for s in batch.scalars)
    total += batch.num_docs.nbytes
    return total


def release_stack_pin(batch: SplitBatch, budget) -> None:
    """Release the mesh-resident stack's admission pin taken by
    `stage_device_inputs`. The default `release` leaves the bytes RESIDENT
    (the owner carries `_device_array_cache`), so the stack survives for
    the next warm query; LRU pressure evicts it through HbmBudget's
    existing owner seam."""
    pin = getattr(batch, "_mesh_stack_pin", None)
    if pin is None:
        return
    batch._mesh_stack_pin = None
    owner, admitted = pin
    budget.release(owner, admitted)


def _stage_resident_stack(batch: SplitBatch, mesh: Mesh, arrays_sh,
                          store, budget) -> dict[int, Any]:
    """Serve the column-family slots from (and populate) the cross-query
    mesh-resident stack: slot → committed sharded device array. A warm
    repeat query finds every column slot resident and uploads ZERO column
    bytes to ANY chip; per-device byte accounting rides the existing
    HbmBudget owner seam (admit under the stack owner, release-to-resident
    after the query via `release_stack_pin`)."""
    from ..search.residency import mesh_stack_id
    split_ax, doc_ax = _mesh_axes(mesh)
    n_sp = mesh.shape[split_ax]
    n_doc = mesh.shape.get(doc_ax, 1) if doc_ax else 1
    stack_id = mesh_stack_id(batch.split_ids, batch.num_docs_padded, mesh)
    owner = store.columns_for(stack_id)
    dcache = owner._device_array_cache
    slots = stack_resident_slots(batch)
    entries = []
    for slot in slots:
        key = batch.template.array_keys[slot]
        arr = batch.arrays[slot]
        # shape+dtype in the key: format-version packings (u8/u16 FOR
        # lanes) and padding buckets must never alias
        entries.append((slot, (key, arr.shape, str(arr.dtype))))
    missing = [(slot, ck) for slot, ck in entries if ck not in dcache]
    per_dev = 0
    for slot, _ck in missing:
        key = batch.template.array_keys[slot]
        nbytes = batch.arrays[slot].nbytes
        if key.endswith((".zmin", ".zmax")):
            per_dev += -(-nbytes // n_sp)
        else:
            per_dev += -(-nbytes // (n_sp * n_doc))
    admitted = budget.admit(owner, per_dev) if budget is not None else 0
    try:
        if missing:
            for slot, ck in missing:
                dcache[ck] = jax.device_put(batch.arrays[slot],
                                            arrays_sh[slot])
            store.note_upload(stack_id, per_dev, len(missing))
            store.note_hits(len(slots) - len(missing), full=False)
        elif slots:
            store.note_hits(len(slots), full=True)
        batch._mesh_stack_pin = (owner, admitted)
        return {slot: dcache[ck] for slot, ck in entries}
    except BaseException:
        if budget is not None:
            budget.release(owner, admitted, to_resident=False)
        raise


def stage_device_inputs(batch: SplitBatch, mesh: Mesh,
                        resident_store=None, budget=None):
    """Start the batch's host→device transfer (async under JAX dispatch)
    and cache the device arrays on the batch for repeat queries — keyed by
    mesh: arrays committed for one sharding must not feed an executor
    compiled for another. Callable from a prefetch thread so the transfer
    overlaps the previous batch's kernel execution.

    With a resident store, column-family slots are served from the
    cross-query mesh stack (`_stage_resident_stack`): only the
    query-shaped slots (postings, scalars, doc counts) ride this request's
    upload."""
    _check_mesh_divides(batch, mesh)
    cache = getattr(batch, "_device_inputs", None)
    if cache is None:
        cache = batch._device_inputs = {}
    dev = cache.get(mesh)
    if dev is not None:
        # re-dispatch of an already-staged batch (hedged retry, readback
        # replay): record the skip so the waterfall shows where staging
        # would have been
        with profiled_phase(PHASE_STAGING_CACHE_HIT) as rec:
            if rec is not None:
                rec["bytes"] = 0
                rec["stage"] = "batch"
        flight.emit("staging.resident_hit", attrs={"stage": "batch"})
        return dev
    arrays_sh, scalars_sh, nd_sh = batch_shardings(batch, mesh)
    resident: dict[int, Any] = {}
    if resident_store is not None \
            and getattr(resident_store, "enabled", False):
        resident = _stage_resident_stack(batch, mesh, arrays_sh,
                                         resident_store, budget)
    staging_bytes = (sum(a.nbytes for slot, a in enumerate(batch.arrays)
                         if slot not in resident)
                     + sum(s.nbytes for s in batch.scalars)
                     + batch.num_docs.nbytes)
    # staging times the transfer DISPATCH (device_put is async;
    # completion overlaps into the execute phase by design — same
    # contract as the per-split warmup in search/leaf.py)
    with profiled_phase(PHASE_STAGING_UPLOAD) as rec:
        if rec is not None:
            rec["bytes"] = staging_bytes
            rec["stage"] = "batch"
        arrays = tuple(
            resident[slot] if slot in resident
            else jax.device_put(a, arrays_sh[slot])
            for slot, a in enumerate(batch.arrays))
        scalars = tuple(jax.device_put(batch.scalars, list(scalars_sh))) \
            if batch.scalars else ()
        nd = jax.device_put(batch.num_docs, nd_sh)
    profile_add("staging_bytes", staging_bytes)
    if flight.recording():
        flight.emit("staging.upload",
                    attrs={"bytes": staging_bytes,
                           "resident_slots": len(resident)})
    dev = cache[mesh] = (arrays, scalars, nd)
    return dev


# Mesh programs contain cross-device collectives (the on-mesh root
# merge's psums/all-reduces). Two such programs enqueued concurrently
# from different query threads can interleave their per-device rendezvous
# (thread A first on device 0, thread B first on device 1) and deadlock —
# observed as 5s+ AllReduceParticipantData stalls under the soak suite's
# 8-thread storm on the 8-fake-device CPU host platform. Enqueue is
# therefore serialized; on real hardware the per-device streams then
# execute programs in one consistent order, the enqueue itself is a cheap
# async launch, and the lock releases immediately. The CPU host platform
# has NO ordered streams (a shared thread pool with data-dependency
# ordering only), so there the critical section must span enqueue →
# completion: `_enqueue_batch` returns the still-held lock as a guard and
# the caller releases it AFTER awaiting the program (`readback_batch`'s
# device_get, or `abandon_dispatch` on the deadline-shed path) — the
# blocking wait itself runs OUTSIDE any lexical lock scope, so waiters
# queue on the guard, not on a device round-trip hidden inside a `with`
# block.
# qwlint: disable-next-line=QW008 - leaf lock by design: the critical
# section is a jax enqueue (hardware) or enqueue→completion (CPU host
# platform), never a seam primitive, so the gated qwrace scheduler cannot
# preempt inside it and instrumenting it would only serialize jax
# dispatch behind the token
_MESH_DISPATCH_LOCK = threading.Lock()


def _enqueue_batch(ex, arrays, scalars, nd):
    """Enqueue one batch program; returns (out, guard). `guard` is the
    still-held `_MESH_DISPATCH_LOCK` on the CPU host platform (the caller
    MUST hand it to `_finish_mesh_dispatch` once the program has been
    awaited), None otherwise. Every launch of the mesh batch family
    passes here, so here it is counted."""
    SEARCH_KERNEL_LAUNCHES_TOTAL.inc()
    _MESH_DISPATCH_LOCK.acquire()
    try:
        out = ex(arrays, scalars, nd)
    except BaseException:
        _MESH_DISPATCH_LOCK.release()
        raise
    if jax.default_backend() != "cpu":
        _MESH_DISPATCH_LOCK.release()
        return out, None
    return out, _MESH_DISPATCH_LOCK


def _finish_mesh_dispatch(guard, out=None) -> None:
    """Complete the cross-procedural mesh-dispatch critical section: await
    the program if the caller has not already (readback's `device_get`
    subsumes the wait, so it passes out=None), then release the guard."""
    if guard is None:
        return
    try:
        if out is not None:
            jax.block_until_ready(out)
    finally:
        guard.release()


def abandon_dispatch(dispatched) -> None:
    """Deadline-shed seam: the dispatch flew but nobody will await its
    readback. The mesh-dispatch guard (CPU host platform) must still see
    the program complete before the next collective program may enqueue;
    device buffers die with their last reference."""
    out, _treedef, _spec, _ctx, guard = dispatched
    _finish_mesh_dispatch(guard, out)


def dispatch_batch(batch: SplitBatch, request: SearchRequest,
                   mesh: Mesh, exact: bool = False):
    """Async half of the mesh batch dispatch: stage (or reuse) the device
    inputs, enqueue ONE collective XLA program over all splits, start the
    D2H copy of the packed result, and return without blocking.
    `readback_batch` completes it — the seam lets the service shed
    deadline-expired queries before ever paying the readback wait, and
    overlap the next group's dispatch with this one's readback. Raises
    ValueError when the mesh's split axis does not divide the batch."""
    # cancelled queries stop HERE, before staging device inputs or paying
    # an enqueue nobody will read (the readback seam checks again)
    from ..common.deadline import check_cancelled
    check_cancelled("batch dispatch")
    _check_mesh_divides(batch, mesh)
    # k=0 (count/agg-only): per-split executors skip keying/top-k and the
    # batch merge skips the cross-split top_k
    k = min(request.start_offset + request.max_hits, batch.num_docs_padded)
    if batch.template.threshold_slot >= 0 and not exact:
        from ..observability.metrics import SEARCH_KERNEL_THRESHOLD_TOTAL
        # one dispatch, but each real lane's docs are threshold-masked
        SEARCH_KERNEL_THRESHOLD_TOTAL.inc(
            sum(1 for s in batch.split_ids if s))
    arrays, scalars, nd = stage_device_inputs(batch, mesh)
    # Mesh is hashable; id() would go stale if a dead mesh's address is reused
    key = (batch.template.signature(k), batch.n_splits,
           batch.num_docs_padded, mesh, exact)
    profile = current_profile()
    cached = _BATCH_JIT_CACHE.get(key)
    if flight.recording():
        flight.emit("compile.hit" if cached is not None else "compile.miss",
                    attrs={"path": "batch"})
        flight.emit("dispatch.launch",
                    attrs={"path": "batch", "splits": batch.n_splits,
                           "mesh": mesh.size})
    if profile is None:
        if cached is None:
            cached = _batch_executor(batch, k, mesh, (arrays, scalars, nd),
                                     exact)
            _BATCH_JIT_CACHE[key] = cached
        ex, treedef, spec, meta = cached
        out, guard = _enqueue_batch(ex, arrays, scalars, nd)
    else:
        # Compile-vs-execute attribution (same lazy-jit approximation as
        # executor.dispatch_plan): on a batch-jit-cache MISS the first call
        # pays trace+XLA-compile; on a HIT the dispatch is a cheap enqueue
        # and the blocking device_get absorbs the device execution time.
        hit = cached is not None
        profile.add("compile_cache_hits" if hit else "compile_cache_misses")
        with profile.phase(PHASE_EXECUTE if hit else PHASE_COMPILE,
                           stage="dispatch_batch"):
            if cached is None:
                cached = _batch_executor(batch, k, mesh,
                                         (arrays, scalars, nd), exact)
                _BATCH_JIT_CACHE[key] = cached
            ex, treedef, spec, meta = cached
            out, guard = _enqueue_batch(ex, arrays, scalars, nd)
    try:
        MESH_DISPATCHES_TOTAL.inc()
        MESH_DEVICES.set(mesh.size)
        MESH_COLLECTIVE_BYTES_TOTAL.inc(meta["collective_bytes"])
        if k > 0:
            MESH_THRESHOLD_EXCHANGE_ROUNDS_TOTAL.inc()
        if flight.recording():
            flight.emit("mesh.collective",
                        attrs={"devices": mesh.size,
                               "bytes": meta["collective_bytes"],
                               "threshold_exchange": int(k > 0)})
        if hasattr(out, "copy_to_host_async"):
            out.copy_to_host_async()
    except BaseException:
        _finish_mesh_dispatch(guard, out)
        raise
    return out, treedef, spec, (batch, request, mesh, k), guard


def readback_batch(dispatched) -> LeafSearchResponse:
    """Blocking half of the mesh batch dispatch: await the packed scalar
    readback, unpack, host-decode the merged hits/aggs. A `safe == 0`
    guided-top-k certificate triggers one exact re-execution of the whole
    batch (see ops/topk.py:guided_topk)."""
    out, treedef, spec, (batch, request, mesh, k), guard = dispatched
    # the dispatch already flew; a cancel landing in between still saves
    # the device->host transfer wait (the mesh-dispatch guard must still
    # observe completion before releasing — abandon, then re-raise)
    from ..common.deadline import check_cancelled
    try:
        check_cancelled("batch readback")
    except BaseException:
        _finish_mesh_dispatch(guard, out)
        raise
    profile = current_profile()
    t0 = _clock_monotonic() if flight.recording() else 0.0
    try:
        if profile is None:
            packed = jax.device_get(out)
        else:
            with profile.phase(PHASE_EXECUTE, stage="readback"):
                packed = jax.device_get(out)
    except BaseException:
        _finish_mesh_dispatch(guard, out)
        raise
    if flight.recording():
        flight.emit("dispatch.readback", attrs={
            "path": "batch",
            "dur_ms": round((_clock_monotonic() - t0) * 1000.0, 3)})
    # device_get returned only after the program ran to completion — the
    # cross-procedural critical section ends here, BEFORE any exact
    # re-dispatch below re-enters _enqueue_batch (the lock is not
    # re-entrant)
    _finish_mesh_dispatch(guard)
    leaves = []
    offset = 0
    for shape, dtype in spec:
        size = int(np.prod(shape)) if shape else 1
        leaves.append(packed[offset: offset + size]
                      .astype(dtype).reshape(shape))
        offset += size
    top_vals, top_vals2, split_idx, doc_ids, scores, total, topk_safe, \
        merged_aggs = jax.tree_util.tree_unflatten(treedef, leaves)
    if float(topk_safe) < 1.0:
        executor_mod._note_guided_fallback()
        return execute_batch(batch, request, mesh, exact=True)

    return _decode_merged(batch, k, top_vals, top_vals2, split_idx,
                          doc_ids, scores, int(total), merged_aggs)


def _decode_merged(batch: SplitBatch, k: int, top_vals, top_vals2,
                   split_idx, doc_ids, scores, num_hits: int,
                   merged_aggs) -> LeafSearchResponse:
    """Host decode of one merged (cross-split) result into a
    LeafSearchResponse."""
    hits: list[PartialHit] = []
    sort_is_int = _sort_values_are_int(batch.doc_mapper, batch.sort_field)
    sort2_is_int = (_sort_values_are_int(batch.doc_mapper, batch.sort2_field)
                    if batch.sort2_field else False)
    exact_cols: dict[tuple, Any] = {}

    def exact_col(si: int, field: str, is_int: bool):
        if not is_int or batch.readers is None:
            return None
        if (si, field) not in exact_cols:
            exact_cols[(si, field)] = \
                batch.readers[si].column_values(field)[0]
        return exact_cols[(si, field)]

    with profiled_phase(PHASE_TOPK_MERGE) as rec:
        for i in range(min(k, num_hits)):
            internal = float(top_vals[i])
            if internal == float("-inf"):
                break
            si = int(split_idx[i])
            split_id = batch.split_ids[si]
            if split_id == "":
                continue
            doc_id = int(doc_ids[i])
            raw = decode_sort_value_exact(
                internal, batch.sort_field, batch.sort_order, sort_is_int,
                scores[i], doc_id,
                exact_col(si, batch.sort_field, sort_is_int))
            internal2, raw2 = 0.0, None
            if batch.sort2_field is not None and top_vals2 is not None:
                internal2 = float(top_vals2[i])
                raw2 = decode_sort_value_exact(
                    internal2, batch.sort2_field, batch.sort2_order,
                    sort2_is_int, scores[i], doc_id,
                    exact_col(si, batch.sort2_field, sort2_is_int))
            hits.append(PartialHit(sort_value=internal, split_id=split_id,
                                   doc_id=doc_id, raw_sort_value=raw,
                                   sort_value2=internal2,
                                   raw_sort_value2=raw2))
        intermediate = _intermediate_aggs(batch.template, list(merged_aggs))
        if rec is not None:
            rec["hits"] = len(hits)
            rec["stage"] = "batch"
    real_splits = sum(1 for s in batch.split_ids if s)
    return LeafSearchResponse(
        num_hits=num_hits,
        partial_hits=hits,
        num_attempted_splits=real_splits,
        num_successful_splits=real_splits,
        intermediate_aggs=intermediate,
    )


def execute_batch(batch: SplitBatch, request: SearchRequest, mesh: Mesh,
                  exact: bool = False) -> LeafSearchResponse:
    """Run the batch over the mesh and emit one merged LeafSearchResponse
    covering all splits."""
    return readback_batch(dispatch_batch(batch, request, mesh, exact))
