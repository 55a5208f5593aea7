"""The representative plan corpus qwir audits.

Builds synthetic splits IN MEMORY (RamStorage) across the three supported
format versions (v3 default, v2 via QW_DISABLE_IMPACT, v1 via
QW_DISABLE_PACKED) and two padding buckets (1024- and 2048-doc padded),
then enumerates the lowered-program surface of the hot path:

  - single-split leaf programs: scoring term (posting-space path),
    bool+range filters, aggregation-only (k=0), column sorts, 2-key
    sorts, search_after pushdown, threshold pushdown (impact prefix +
    count_override), mask_override (PMaskRef), exact fallbacks
  - multi-query vmapped programs per batch bucket
  - collective mesh batch programs (parallel/fanout.py, with and
    without 2-key / agg merges)
  - the Tier-A predicate-mask fill kernel

Every entry abstract-traces through the SAME build closures the dispatch
paths jit (executor.abstract_program / abstract_multi_program /
abstract_mask_fill, fanout.abstract_mesh_batch_program) and records the
mirrored compile-cache key — the R1 closure certificate is over exactly
the keys the runtime caches key on.

Determinism contract: same code + same jax ⇒ same program set, same
cache-key digests, same jaxpr digests. Everything here derives from
fixed literals and a seeded RNG; no wall clock, no host entropy.
"""

from __future__ import annotations

import hashlib
import os
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Any, Optional

import numpy as np

from . import ir

# --- corpus documents --------------------------------------------------------

T0 = 1_600_000_000
SEVERITIES = ("DEBUG", "INFO", "WARN", "ERROR")

# one padding bucket per entry: DOC_PAD=1024 ⇒ 220 docs pad to 1024,
# 1100 docs pad to 2048
SMALL_DOCS = 220
BIG_DOCS = 1100


def _mapper():
    from quickwit_tpu.models import DocMapper, FieldMapping, FieldType
    return DocMapper(
        field_mappings=[
            FieldMapping("timestamp", FieldType.DATETIME, fast=True,
                         input_formats=("unix_timestamp",)),
            FieldMapping("severity_text", FieldType.TEXT, tokenizer="raw",
                         fast=True),
            FieldMapping("tenant_id", FieldType.U64, fast=True),
            FieldMapping("body", FieldType.TEXT),
            FieldMapping("latency", FieldType.F64, fast=True),
        ],
        timestamp_field="timestamp",
        default_search_fields=("body",),
    )


def _docs(n: int, seed: int):
    rng = np.random.RandomState(seed)
    docs = []
    for i in range(n):
        docs.append({
            "timestamp": T0 + i * 60,
            "severity_text": SEVERITIES[int(rng.randint(0, 4))],
            "tenant_id": int(rng.randint(0, 4)),
            "body": " ".join(["alpha"] * int(rng.randint(1, 3))
                             + ["beta"] * int(rng.randint(0, 2))),
            "latency": float(rng.gamma(2.0, 40.0)),
        })
    return docs


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


def _build_reader(mapper, docs, name: str, env: Optional[dict] = None):
    from quickwit_tpu.common.uri import Uri
    from quickwit_tpu.index import SplitReader, SplitWriter
    from quickwit_tpu.storage import RamStorage
    with _writer_env(**(env or {})):
        writer = SplitWriter(mapper)
        for doc in docs:
            writer.add_json_doc(doc)
        data = writer.finish()
    storage = RamStorage(Uri.parse("ram:///qwir"))
    storage.put(name, data)
    return SplitReader(storage, name)


# --- program specs -----------------------------------------------------------

@dataclass
class ProgramSpec:
    name: str                 # stable corpus id, e.g. "single/v3/term/k10"
    kind: str                 # single | multi | mesh | mask_fill
    closed: Any               # ClosedJaxpr (abstract trace, never executed)
    cache_key: tuple          # the runtime compile-cache key, mirrored
    doc_lanes: int            # total padded doc lanes across vmap/batch dims
    num_docs_padded: int
    mesh_axes: tuple = ("splits", "docs")
    exact: bool = False
    peak: Any = None          # ir.PeakReport, filled by the auditor

    @property
    def cache_key_digest(self) -> str:
        return hashlib.blake2b(repr(_printable_key(self.cache_key)).encode(),
                               digest_size=16).hexdigest()


def _printable_key(key):
    """The cache key with every `Mesh` replaced by what identifies it
    (axis names, device grid shape, device ids): how JAX prints a Mesh
    changes between versions, and the certificate must not."""
    from jax.sharding import Mesh
    if isinstance(key, Mesh):
        return ("Mesh", key.axis_names, key.devices.shape,
                tuple(int(d.id) for d in key.devices.flat))
    if isinstance(key, tuple):
        return tuple(_printable_key(part) for part in key)
    return key


def _queries():
    from quickwit_tpu.query.ast import Bool, MatchAll, Range, RangeBound, Term
    term = Term("body", "alpha")
    bool_range = Bool(
        must=(Term("severity_text", "ERROR"),),
        filter=(Range("timestamp",
                      lower=RangeBound((T0 + 600) * 10**6, True),
                      upper=RangeBound((T0 + 60 * SMALL_DOCS) * 10**6, False)),
                Range("tenant_id", lower=RangeBound(1, True),
                      upper=RangeBound(3, False))),
    )
    filter_only = Bool(
        filter=(Term("severity_text", "ERROR"),
                Range("tenant_id", lower=RangeBound(0, True),
                      upper=RangeBound(2, False))),
    )
    return term, bool_range, filter_only, MatchAll()


def _aggs():
    from quickwit_tpu.query.aggregations import DateHistogramAgg, MetricAgg
    return [
        DateHistogramAgg(name="per_hour", field="timestamp",
                         interval_micros=3_600 * 10**6,
                         sub_metrics=(MetricAgg("lat_avg", "avg", "latency"),)),
        MetricAgg("lat_stats", "stats", "latency"),
        MetricAgg("lat_pctl", "percentiles", "latency"),
    ]


def build_corpus() -> list[ProgramSpec]:
    """Enumerate and abstract-trace the full plan corpus. Host-only: no
    XLA compile, no device execution, no data movement."""
    from quickwit_tpu.parallel import fanout
    from quickwit_tpu.search import executor
    from quickwit_tpu.search.plan import lower_request

    mapper = _mapper()
    small = _docs(SMALL_DOCS, seed=3)
    readers = {
        "v3": _build_reader(mapper, small, "v3.split"),
        "v2": _build_reader(mapper, small, "v2.split",
                            env={"QW_DISABLE_IMPACT": "1"}),
        "v1": _build_reader(mapper, small, "v1.split",
                            env={"QW_DISABLE_PACKED": "1"}),
        "v3big": _build_reader(mapper, _docs(BIG_DOCS, seed=5), "v3b.split"),
        "v3b": _build_reader(mapper, _docs(SMALL_DOCS, seed=7), "v3c.split"),
    }
    term, bool_range, filter_only, match_all = _queries()
    specs: list[ProgramSpec] = []

    def single(name, plan, k, exact=False):
        closed = executor.abstract_program(plan, k, exact)
        specs.append(ProgramSpec(
            name=name, kind="single", closed=closed,
            cache_key=executor.program_cache_key(plan, k, exact),
            doc_lanes=plan.num_docs_padded,
            num_docs_padded=plan.num_docs_padded, exact=exact))
        return plan

    # -- single-split leaf programs, across format versions + padding ----
    for ver in ("v1", "v2", "v3", "v3big"):
        plan = lower_request(term, mapper, readers[ver], [])
        single(f"single/{ver}/term/k10", plan, 10)
    for ver in ("v2", "v3"):
        plan = lower_request(bool_range, mapper, readers[ver], [],
                             sort_field="timestamp", sort_order="desc")
        single(f"single/{ver}/bool_range/k10", plan, 10)
    # aggregation-only (k=0 skips keying/top-k entirely)
    plan = lower_request(match_all, mapper, readers["v3"], _aggs())
    single("single/v3/aggs/k0", plan, 0)
    # count-only term
    plan = lower_request(term, mapper, readers["v3"], [])
    single("single/v3/term/k0", plan, 0)
    # column sort, ascending
    plan = lower_request(match_all, mapper, readers["v3"], [],
                         sort_field="latency", sort_order="asc")
    single("single/v3/sort_col/k5", plan, 5)
    # 2-key lexicographic sort (exact_topk_2key f64 anchor)
    plan = lower_request(match_all, mapper, readers["v3"], [],
                         sort_field="latency", sort_order="desc",
                         sort2_field="timestamp", sort2_order="asc")
    single("single/v3/sort_2key/k5", plan, 5)
    # search_after pushdown (marker value/doc ride traced scalars)
    plan = lower_request(match_all, mapper, readers["v3"], [],
                         sort_field="latency", sort_order="desc",
                         search_after=(123.5, None, "lt_tie", 7))
    single("single/v3/search_after/k5", plan, 5)
    # threshold pushdown over the scoring term: format v3 stages the
    # impact-ordered live prefix and sets count_override
    plan = lower_request(term, mapper, readers["v3"], [],
                         sort_value_threshold=2.0)
    single("single/v3/threshold/k10", plan, 10)
    # the certified exact-fallback program (guided_topk's unsafe-screen
    # re-dispatch lands here)
    plan = lower_request(term, mapper, readers["v3"], [])
    single("single/v3/term_exact/k10", plan, 10, exact=True)
    plan = lower_request(match_all, mapper, readers["v3"], [],
                         sort_field="latency", sort_order="asc")
    single("single/v3/sort_col_exact/k5", plan, 5, exact=True)
    # mask_override: Tier-A cached predicate stands in for the whole root
    padded = readers["v3"].num_docs_padded
    mask = np.zeros(padded, dtype=bool)
    mask[: SMALL_DOCS : 3] = True
    packed_mask = np.packbits(mask)
    plan = lower_request(filter_only, mapper, readers["v3"], [],
                         sort_field="timestamp", sort_order="desc",
                         mask_override=packed_mask,
                         mask_key="mask.qwir")
    single("single/v3/mask_override/k10", plan, 10)

    # -- chunked leaf programs (search/chunkexec.py) ---------------------
    # the resumable scan dispatches one compiled program per doc-block
    # slab; every chunk of a scan shares ONE program per (mode, span) —
    # chunk bounds and threshold updates ride scalar inputs, not traced
    # constants — so the closure grows exactly one entry per chunk mode
    from quickwit_tpu.index.format import DOC_PAD, POSTING_PAD
    from quickwit_tpu.search import chunkexec
    plan = lower_request(term, mapper, readers["v3big"], [])
    single("chunked/v3big/term_posting/k10",
           chunkexec.posting_chunk_plan(plan, 0, POSTING_PAD), 10)
    plan = lower_request(match_all, mapper, readers["v3big"], [],
                         sort_field="latency", sort_order="desc")
    single("chunked/v3big/sort_col_dense/k5",
           chunkexec.dense_chunk_plan(plan, 0, DOC_PAD), 5)

    # -- multi-query vmapped programs (one per batch bucket) -------------
    plan = lower_request(term, mapper, readers["v3"], [])
    for bucket in (2, 4):
        closed = executor.abstract_multi_program(plan, 10, bucket)
        specs.append(ProgramSpec(
            name=f"multi/v3/term/b{bucket}/k10", kind="multi", closed=closed,
            cache_key=executor.multi_program_cache_key(plan, 10, bucket),
            doc_lanes=plan.num_docs_padded * bucket,
            num_docs_padded=plan.num_docs_padded))

    # -- stacked query-group programs (device-side multi-query batching) -
    # DISTINCT shape-compatible queries as lanes of ONE program
    # (search/batcher.py QueryGroupPlanner → executor.dispatch_plan_stacked):
    # shared slots broadcast, query-shaped slots and scalars ride a leading
    # [Q] axis, and the [Q] validity mask is an operand — same kind/rule
    # treatment as the vmapped convoy programs ("multi")
    from quickwit_tpu.query.ast import Term as _Term
    sev_plans = [lower_request(_Term("severity_text", s), mapper,
                               readers["v3"], []) for s in ("ERROR", "INFO")]
    sev_sigs = {p.structure_digest(10) for p in sev_plans}
    assert len(sev_sigs) == 1, "corpus stacked lanes must be shape-compatible"
    closed = executor.abstract_stacked_program(sev_plans, 10)
    specs.append(ProgramSpec(
        name="stacked/v3/term/q2/k10", kind="multi", closed=closed,
        cache_key=executor.stacked_program_cache_key(sev_plans, 10),
        doc_lanes=sev_plans[0].num_docs_padded * 2,
        num_docs_padded=sev_plans[0].num_docs_padded))
    # stacked × chunked: the group scan dispatches chunk sub-plans of every
    # lane as one stacked program per chunk (chunkexec.execute_group_chunked)
    sev_chunks = [chunkexec.posting_chunk_plan(p, 0, POSTING_PAD)
                  for p in sev_plans]
    closed = executor.abstract_stacked_program(sev_chunks, 10)
    specs.append(ProgramSpec(
        name="stacked_chunked/v3/term_posting/q2/k10", kind="multi",
        closed=closed,
        cache_key=executor.stacked_program_cache_key(sev_chunks, 10),
        doc_lanes=sev_chunks[0].num_docs_padded * 2,
        num_docs_padded=sev_chunks[0].num_docs_padded))

    # -- collective mesh root-merge programs (parallel/fanout.py) --------
    # the whole-query shard_map programs: per-shard scoring, the
    # all-reduce-max threshold exchange, the all_gather + re-top-k merge,
    # and the psum/max/min agg reduction are EXPLICIT collective eqns here —
    # R4's mesh-axis rule audits every one against the declared
    # ("splits", "docs") axes
    from quickwit_tpu.search import SearchRequest, SortField

    def mesh_spec(name, request, k, split_keys, mesh):
        rds = [readers[s] for s in split_keys]
        batch = fanout.build_batch(request, mapper, rds, list(split_keys))
        closed = fanout.abstract_mesh_batch_program(batch, k, mesh)
        specs.append(ProgramSpec(
            name=name, kind="mesh", closed=closed,
            cache_key=fanout.batch_cache_key(batch, k, mesh=mesh),
            doc_lanes=batch.num_docs_padded * batch.n_splits,
            num_docs_padded=batch.num_docs_padded))

    mesh21 = fanout.make_mesh(2, 1)
    mesh22 = fanout.make_mesh(2, 2)
    mesh_spec("mesh/v3/term/n2/2x1/k10",
              SearchRequest(index_ids=["t"], query_ast=term, max_hits=10),
              10, ("v3", "v3b"), mesh21)
    mesh_spec("mesh/v3/sort_2key/n2/2x2/k5",
              SearchRequest(index_ids=["t"], query_ast=match_all, max_hits=5,
                            sort_fields=[SortField("latency", "desc"),
                                         SortField("timestamp", "asc")]),
              5, ("v3", "v3b"), mesh22)
    mesh_spec("mesh/v3/aggs/n2/2x1/k0",
              SearchRequest(
                  index_ids=["t"], query_ast=match_all, max_hits=0,
                  aggs={"per_hour": {
                      "date_histogram": {"field": "timestamp",
                                         "fixed_interval": "1h"},
                      "aggs": {"lat_avg": {"avg": {"field": "latency"}}}}}),
              0, ("v3", "v3b"), mesh21)

    # -- Tier-A predicate-mask fill kernel -------------------------------
    plan = lower_request(bool_range, mapper, readers["v3"], [],
                         sort_field="timestamp", sort_order="desc")
    closed = executor.abstract_mask_fill(plan)
    specs.append(ProgramSpec(
        name="mask_fill/v3/bool_range", kind="mask_fill", closed=closed,
        cache_key=executor.mask_fill_cache_key(plan),
        doc_lanes=plan.num_docs_padded,
        num_docs_padded=plan.num_docs_padded))

    names = [s.name for s in specs]
    assert len(names) == len(set(names)), "corpus program names must be unique"
    return specs
