"""Resumable chunked leaf kernels: the host loop between chunk programs.

Since PR 1 the deadline/shed machinery stopped at the XLA boundary: an
in-flight leaf computation was uninterruptible, so expired queries,
cancelled scrolls, and background-class tenants were only shed at host
checkpoints (ROADMAP item 4). This module restructures the leaf kernel as
a chunked scan over doc-block slabs with carried top-K/count/mergeable-agg
state: the staged operands are partitioned into fixed-size chunks, each
chunk executes as ONE compiled program through the existing
`executor.execute_plan` seam, and the host loop between chunks is the
robustness control point. At every chunk boundary the loop

  (a) kills an expired or explicitly cancelled query mid-kernel (the
      ambient `Deadline` / `CancellationToken` from common/deadline.py —
      a cancelled query stops within one boundary and returns either a
      `"partial": true` result or a typed `CancelledQuery`),
  (b) preempts the running query when tenancy/overload.py's ladder trips
      while a higher-class query is active — the carried state parks
      (bounded, byte-accounted against the tenant's DRR quantum in
      `ParkedStateRegistry`) and resumes after, making DRR priorities
      real at kernel granularity instead of only at admission,
  (c) early-terminates when the cross-chunk block-max bound proves the
      remaining chunks cannot beat the current Kth value (the BM25S
      block-max argument applied one level up: impact-ordered prefixes
      put the highest bounds in the earliest chunks), re-reading the
      shared `ThresholdBox` every boundary so pruning tightens DURING a
      query, not just between splits.

Two partitionings cover every chunk-eligible plan:

* posting mode — single-term plans (`_posting_space_eligible`): the
  [P] ids/tfs lanes split on POSTING_PAD boundaries, the quantized
  impact block maxima split with them (IMPACT_BLOCK == POSTING_PAD),
  and every doc-space array passes through whole (the `_GatherView`
  gathers by GLOBAL doc id). Counts sum exactly because the lane
  partition is disjoint; top-K ties merge in chunk order, which IS the
  fused kernel's lowest-lane-index order.
* dense mode — everything `plan.chunk_slot_plan` can classify: the
  padded doc dimension splits on DOC_PAD boundaries; doc columns,
  zonemaps and packed masks slice by the matching granularity; posting
  pairs are host-rebased into the chunk's window (out-of-window lanes
  get the chunk's scatter-drop sentinel); the chunk's global doc offset
  rides a traced `doc_base_slot` scalar so doc-id sort keys and
  search_after comparisons stay in global doc space.

Single-chunk execution falls back to the fused path untouched — it is
bit-identical by construction and stays the compiled-program-count-
friendly default for small splits: the adaptive `_ChunkSizer` only
splits work whose profiled per-chunk latency exceeds the target boundary
interval (~10ms class), so a split the fused kernel finishes faster than
one boundary interval never chunks at all.
"""

from __future__ import annotations

import math
import os
from contextlib import contextmanager
from dataclasses import replace as dc_replace
from typing import Any, Callable, Optional

import numpy as np

from ..common import sync
from ..common.clock import get_clock
from ..common.deadline import (
    CancelledQuery, DeadlineExceeded, current_cancel_token, current_deadline,
)
from ..common.faults import InjectedFault
from ..index.format import DOC_PAD, POSTING_PAD, ZONEMAP_BLOCK
from ..observability import flight
from ..observability.metrics import (
    CHUNK_BOUNDARY_SECONDS, CHUNK_DISPATCHES_TOTAL,
    CHUNK_EARLY_TERMINATIONS_TOTAL, CHUNK_RESTARTS_TOTAL,
    PREEMPT_PARKED_BYTES, PREEMPT_TOTAL,
)
from ..ops import topk as topk_ops
from ..tenancy.context import effective_tenant
from ..tenancy.drr import DEFAULT_QUANTUM_BYTES
from ..tenancy.overload import OVERLOAD
from . import executor
from .plan import CompositeAggExec, LoweredPlan, chunk_slot_plan


# --- configuration ---------------------------------------------------------

class ChunkConfig:
    """Process-wide chunking knobs. Explicit spans (tests, benches, the
    qwir corpus) override the adaptive sizer; `enabled=False` restores the
    fused-only seed behavior byte for byte."""

    def __init__(self):
        self.enabled = True
        # explicit chunk spans (None = adaptive): docs per dense chunk
        # (DOC_PAD multiple) / postings per posting chunk (POSTING_PAD
        # multiple)
        self.doc_span: Optional[int] = _env_int("QW_CHUNK_DOC_SPAN")
        self.posting_span: Optional[int] = _env_int("QW_CHUNK_POSTING_SPAN")
        # the boundary-interval target the sizer steers toward
        self.target_boundary_secs = 0.010
        # cancelled queries return the merged-so-far state with an honest
        # "partial": true marker instead of dropping completed work
        self.partial_on_cancel = True
        # a parked query resumes after this long even if the gate never
        # clears (starvation bound; the deadline still applies on top)
        self.max_park_secs = 2.0

    def set(self, **kwargs) -> None:
        for key, value in kwargs.items():
            if not hasattr(self, key):
                raise AttributeError(f"unknown chunking knob {key!r}")
            setattr(self, key, value)


def _env_int(name: str) -> Optional[int]:
    raw = os.environ.get(name, "").strip()
    return int(raw) if raw else None


CHUNKING = ChunkConfig()


# --- adaptive chunk sizing -------------------------------------------------

class _ChunkSizer:
    """EWMA of per-item chunk latency per mode; suggests the span whose
    predicted chunk time matches the target boundary interval. Knows
    nothing until a chunked execution has been observed, so cold-start
    behavior is the fused path (no span -> no chunking) unless an explicit
    span is configured."""

    ALPHA = 0.3

    def __init__(self):
        # qwlint: disable-next-line=QW008 - leaf lock over two floats; no
        # instrumented ops run under it
        self._lock = sync.lock("_ChunkSizer._lock")
        self._rate: dict[str, float] = {}   # mode -> EWMA secs per item

    def observe(self, mode: str, items: int, secs: float) -> None:
        if items <= 0 or secs <= 0.0:
            return
        rate = secs / items
        with self._lock:
            prev = self._rate.get(mode)
            self._rate[mode] = (rate if prev is None
                                else prev + self.ALPHA * (rate - prev))

    def span_for(self, mode: str, align: int) -> Optional[int]:
        with self._lock:
            rate = self._rate.get(mode)
        if rate is None or rate <= 0.0:
            return None
        span = CHUNKING.target_boundary_secs / rate
        return max(align, int(math.ceil(span / align)) * align)


CHUNK_SIZER = _ChunkSizer()


# --- preemption gate -------------------------------------------------------

class PreemptGate:
    """Who is running at which priority class, for boundary-time yield
    decisions. Fused and chunked executions both register; only chunked
    ones can actually yield (the fused kernel is uninterruptible — that
    is the whole point of this module)."""

    def __init__(self):
        self._cond = sync.condition(name="PreemptGate._cond")
        self._active: dict[int, int] = {}

    @contextmanager
    def running(self, priority: int):
        with self._cond:
            self._active[priority] = self._active.get(priority, 0) + 1
        try:
            yield
        finally:
            with self._cond:
                self._active[priority] -= 1
                if self._active[priority] <= 0:
                    del self._active[priority]
                self._cond.notify_all()

    def _higher_active_locked(self, priority: int) -> bool:
        return any(count > 0 and pri > priority
                   for pri, count in self._active.items())

    def should_yield(self, priority: int) -> bool:
        """True when the overload ladder has tripped AND a strictly
        higher-class query is running right now."""
        if OVERLOAD.shed_floor() <= 0:
            return False
        with self._cond:
            return self._higher_active_locked(priority)

    def wait_until_clear(self, priority: int, max_wait_secs: float,
                         deadline=None, token=None) -> None:
        """Block (in short, cancel/deadline-aware slices) until no
        higher-class query is active, the ladder clears, the starvation
        bound elapses, or the query's own budget/cancel fires."""
        clock = get_clock()
        start = clock.monotonic()
        with self._cond:
            while (self._higher_active_locked(priority)
                   and OVERLOAD.shed_floor() > 0):
                if clock.monotonic() - start >= max_wait_secs:
                    return
                if deadline is not None and deadline.expired:
                    return
                if token is not None and token.cancelled:
                    return
                self._cond.wait(timeout=0.02)


PREEMPT_GATE = PreemptGate()


# --- parked-state accounting -----------------------------------------------

class _ParkTicket:
    __slots__ = ("tenant_id", "nbytes", "evicted", "seq")

    def __init__(self, tenant_id: str, nbytes: int, seq: int):
        self.tenant_id = tenant_id
        self.nbytes = nbytes
        self.evicted = False
        self.seq = seq


class ParkedStateRegistry:
    """Byte-accounts the carried chunk state of preempted queries.

    Parked bytes are bounded per tenant by the DRR quantum (the same unit
    admission charges in) and globally by a small multiple of it. Over
    either cap the OLDEST parked entry (same tenant first) is evicted:
    its owner discards the carried state at resume and re-executes from
    scratch, counted in qw_chunk_restarts_total. Eviction is an
    accounting decision — the owner releases the actual arrays at its
    next boundary check, which is at most one park-wait away."""

    GLOBAL_CAP_FACTOR = 4

    def __init__(self, tenant_cap_bytes: int = DEFAULT_QUANTUM_BYTES):
        self.tenant_cap = tenant_cap_bytes
        self.global_cap = tenant_cap_bytes * self.GLOBAL_CAP_FACTOR
        # qwlint: disable-next-line=QW008 - leaf lock over the accounting
        # dict; no instrumented ops run under it
        self._lock = sync.lock("ParkedStateRegistry._lock")
        self._entries: dict[int, _ParkTicket] = {}
        self._seq = 0

    def park(self, tenant_id: str, nbytes: int) -> _ParkTicket:
        with self._lock:
            self._seq += 1
            ticket = _ParkTicket(tenant_id, int(nbytes), self._seq)
            self._entries[ticket.seq] = ticket
            self._evict_over_caps(ticket.tenant_id)
            PREEMPT_PARKED_BYTES.set(self._total())
            return ticket

    def release(self, ticket: _ParkTicket) -> None:
        with self._lock:
            self._entries.pop(ticket.seq, None)
            PREEMPT_PARKED_BYTES.set(self._total())

    def parked_bytes(self) -> int:
        with self._lock:
            return self._total()

    def _total(self) -> int:
        return sum(t.nbytes for t in self._entries.values())

    def _tenant_total(self, tenant_id: str) -> int:
        return sum(t.nbytes for t in self._entries.values()
                   if t.tenant_id == tenant_id)

    def _evict_over_caps(self, tenant_id: str) -> None:
        # oldest-first within the offending tenant, then globally
        while self._tenant_total(tenant_id) > self.tenant_cap:
            self._evict_oldest(tenant_id)
        while self._total() > self.global_cap:
            self._evict_oldest(None)

    def _evict_oldest(self, tenant_id: Optional[str]) -> None:
        candidates = [t for t in self._entries.values()
                      if tenant_id is None or t.tenant_id == tenant_id]
        victim = min(candidates, key=lambda t: t.seq)
        victim.evicted = True
        del self._entries[victim.seq]


PARKED_STATES = ParkedStateRegistry()


# --- eligibility & chunk-plan construction ---------------------------------

def _has_composite(plan: LoweredPlan) -> bool:
    return any(isinstance(a, CompositeAggExec) for a in plan.aggs)


def chunk_mode(plan: LoweredPlan) -> Optional[tuple[str, int, int]]:
    """(mode, total_items, alignment) or None when the plan cannot chunk.

    Composite aggs never chunk in either mode: their device state is a
    run-compressed sort of the WHOLE doc space and two chunks' runs do
    not merge host-side."""
    if _has_composite(plan):
        return None
    if executor._posting_space_eligible(plan):
        items = int(plan.arrays[plan.root.ids_slot].shape[0])
        return ("posting", items, POSTING_PAD)
    if chunk_slot_plan(plan) is not None:
        return ("dense", int(plan.num_docs_padded), DOC_PAD)
    return None


def posting_chunk_plan(plan: LoweredPlan, lo: int, hi: int) -> LoweredPlan:
    """Sub-plan over posting lanes [lo, hi): ids/tfs (and the aligned
    impact block maxima) slice; every doc-space array passes through
    whole. Counts stay exact because the lane partition is disjoint."""
    root = plan.root
    sliced = {root.ids_slot, root.tfs_slot}
    arrays = list(plan.arrays)
    keys = list(plan.array_keys)
    for slot in sliced:
        arrays[slot] = plan.arrays[slot][lo:hi]
        keys[slot] = f"{plan.array_keys[slot]}#p{lo}:{hi}"
    if root.impact_bmax_slot >= 0:
        slot = root.impact_bmax_slot
        arrays[slot] = plan.arrays[slot][lo // POSTING_PAD: hi // POSTING_PAD]
        keys[slot] = f"{plan.array_keys[slot]}#p{lo}:{hi}"
    return dc_replace(plan, arrays=arrays, array_keys=keys,
                      scalars=list(plan.scalars))


def dense_chunk_plan(plan: LoweredPlan, base: int, span: int) -> LoweredPlan:
    """Sub-plan over padded docs [base, base + span): doc/zone/packed
    slots slice by their granularity, posting pairs are host-rebased into
    the window (out-of-window lanes get sentinel `span`, the chunk's
    scatter-drop id), and the global offset rides a new traced
    `doc_base_slot` scalar."""
    slots = chunk_slot_plan(plan)
    if slots is None:
        raise ValueError("plan is not dense-chunk eligible")
    hi = base + span
    arrays = list(plan.arrays)
    keys = list(plan.array_keys)
    tag = f"#d{base}:{hi}"
    for slot in slots.doc_slots:
        arrays[slot] = plan.arrays[slot][base:hi]
        keys[slot] = plan.array_keys[slot] + tag
    for slot in slots.zone_slots:
        arrays[slot] = plan.arrays[slot][base // ZONEMAP_BLOCK:
                                         hi // ZONEMAP_BLOCK]
        keys[slot] = plan.array_keys[slot] + tag
    for slot in slots.packed_slots:
        arrays[slot] = plan.arrays[slot][base // 8: hi // 8]
        keys[slot] = plan.array_keys[slot] + tag
    for ids_slot, _tfs_slot in slots.posting_pairs:
        ids = plan.arrays[ids_slot]
        # same lane count, window-local ids: the dense evaluator's gather
        # clamps and its scatter drops index == span, so out-of-window
        # postings contribute nothing (tfs lanes pass through unchanged)
        arrays[ids_slot] = np.where((ids >= base) & (ids < hi),
                                    ids - base, span).astype(ids.dtype)
        keys[ids_slot] = plan.array_keys[ids_slot] + tag
    scalars = list(plan.scalars) + [np.int32(base)]
    num_docs = min(max(plan.num_docs - base, 0), span)
    return dc_replace(plan, arrays=arrays, array_keys=keys, scalars=scalars,
                      num_docs=num_docs, num_docs_padded=span,
                      doc_base_slot=len(scalars) - 1)


def chunk_spans(total: int, span: int, align: int) -> list[tuple[int, int]]:
    """[lo, hi) windows covering [0, total): full spans plus one aligned
    remainder — at most two distinct chunk shapes enter the compile
    cache."""
    span = max(align, (span // align) * align)
    out = []
    lo = 0
    while lo < total:
        out.append((lo, min(lo + span, total)))
        lo += span
    return out


# --- host-side carried-state merging ---------------------------------------

def _merge_agg_leaf(name: str, a, b):
    """One mergeable device output leaf — the SAME per-name rules as the
    mesh fan-out's cross-split reduction (`_merge_agg_collective`,
    parallel/fanout.py): min/max/hll envelope, stats component-wise,
    everything else adds."""
    if name == "min":
        return np.minimum(a, b)
    if name in ("max", "hll"):
        return np.maximum(a, b)
    if name == "stats":
        # [count, sum, sum_sq, min, max]
        return np.concatenate([np.asarray(a[:3]) + np.asarray(b[:3]),
                               np.minimum(a[3:4], b[3:4]),
                               np.maximum(a[4:5], b[4:5])])
    return np.asarray(a) + np.asarray(b)


def _merge_agg_state(name: str, a, b):
    if isinstance(a, dict):
        return {key: _merge_agg_state(key, a[key], b[key]) for key in a}
    if isinstance(a, (list, tuple)):
        return [_merge_agg_state(name, xa, xb) for xa, xb in zip(a, b)]
    return _merge_agg_leaf(name, a, b)


def merge_agg_outputs(a: list, b: list) -> list:
    """Merge two chunks' `result["aggs"]` lists leaf-wise."""
    return [_merge_agg_state("", sa, sb) for sa, sb in zip(a, b)]


class _CarriedState:
    """The mergeable cross-chunk state: merged top-K rows, match count,
    agg outputs, and how many chunks contributed."""

    __slots__ = ("topk", "count", "aggs", "chunks_done")

    def __init__(self):
        self.topk = None          # (vals, vals2|None, ids, scores)
        self.count = 0
        self.aggs: Optional[list] = None
        self.chunks_done = 0

    def absorb(self, result: dict[str, Any], k: int) -> None:
        self.count += int(result["count"])
        self.aggs = (list(result["aggs"]) if self.aggs is None
                     else merge_agg_outputs(self.aggs, result["aggs"]))
        piece = (np.asarray(result["sort_values"]),
                 None if result["sort_values2"] is None
                 else np.asarray(result["sort_values2"]),
                 np.asarray(result["doc_ids"]),
                 np.asarray(result["scores"]))
        if self.topk is None:
            self.topk = piece
        else:
            # both inputs are ordered chunk outputs and the earlier one is
            # from strictly earlier lanes — the stable merge reproduces the
            # fused kernel's lowest-lane-index tie order
            vals, vals2, ids, scores = topk_ops.merge_topk_chunks(
                [self.topk, piece], k)
            self.topk = (vals, vals2, ids, scores)
        self.chunks_done += 1

    def kth_value(self, k: int) -> Optional[float]:
        """The current Kth primary key, when K hits exist."""
        if k <= 0 or self.topk is None or self.topk[0].shape[0] < k:
            return None
        kth = float(self.topk[0][k - 1])
        return None if kth == -np.inf else kth

    def nbytes(self) -> int:
        total = 0
        if self.topk is not None:
            total += sum(p.nbytes for p in self.topk if p is not None)
        stack = [self.aggs] if self.aggs is not None else []
        while stack:
            node = stack.pop()
            if isinstance(node, dict):
                stack.extend(node.values())
            elif isinstance(node, (list, tuple)):
                stack.extend(node)
            elif hasattr(node, "nbytes"):
                total += node.nbytes
        return total

    def to_result(self, k: int, partial: bool = False) -> dict[str, Any]:
        if self.topk is None:
            vals = np.zeros((0,), np.float64)
            vals2 = None
            ids = np.zeros((0,), np.int32)
            scores = np.zeros((0,), np.float32)
        else:
            vals, vals2, ids, scores = self.topk
        out = {
            "sort_values": vals,
            "sort_values2": vals2,
            "doc_ids": ids,
            "scores": scores,
            "count": int(self.count),
            "aggs": list(self.aggs or []),
        }
        if partial:
            out["partial"] = True
        return out


# --- the chunk loop --------------------------------------------------------

class _RestartScan(Exception):
    """Carried state was lost (chunk_yield fault / parked-state eviction);
    the query re-executes from scratch."""


def _host_chunk_bounds(plan: LoweredPlan,
                       spans: list[tuple[int, int]]) -> Optional[np.ndarray]:
    """Per-chunk score upper bounds from the quantized impact block maxima
    (posting mode, format v3): the host-side mirror of the kernel's
    `dequantize_block_bounds`."""
    root = plan.root
    if root.impact_bmax_slot < 0 or root.impact_scale_slot < 0:
        return None
    bmax = np.asarray(plan.arrays[root.impact_bmax_slot], dtype=np.float64)
    scale = float(np.asarray(plan.scalars[root.impact_scale_slot]))
    bounds = np.empty(len(spans), dtype=np.float64)
    for i, (lo, hi) in enumerate(spans):
        blocks = bmax[lo // POSTING_PAD: (hi + POSTING_PAD - 1) // POSTING_PAD]
        bounds[i] = blocks.max() * scale if blocks.size else -np.inf
    return bounds


def _early_term_eligible(plan: LoweredPlan, k: int, mode: str) -> bool:
    """Cross-chunk early termination is only EXACT when nothing but the
    top-K depends on the remaining chunks: score-descending single-key
    sort, no aggs, and the exact count known host-side (the impact-prefix
    `count_override`)."""
    return (mode == "posting" and k > 0
            and plan.sort.by == "score" and plan.sort.descending
            and plan.sort.by2 == "none"
            and not plan.aggs
            and plan.count_override is not None)


def _chunk_device_arrays(plan: LoweredPlan, chunk: LoweredPlan,
                         device_arrays: list) -> list:
    """Device inputs for a chunk: pass through untouched slots, slice
    device-side where the host plan sliced, and upload host-rebased
    posting ids (dense mode) fresh."""
    out = []
    import jax
    for slot, (orig, new) in enumerate(zip(plan.arrays, chunk.arrays)):
        if new is orig:
            out.append(device_arrays[slot])
        elif (new.base is not None
              and new.shape[0] <= orig.shape[0]
              and new.ndim == orig.ndim):
            # a slice view of the original — slice the device array the
            # same way (device-side slice, no host round-trip). Doc/zone/
            # packed slots slice from the front only in posting mode;
            # dense mode carries the offset in the key tag.
            lo, hi = _slice_window(orig, new)
            out.append(device_arrays[slot][lo:hi])
        else:
            out.append(jax.device_put(new))
    return out


def _slice_window(orig: np.ndarray, view: np.ndarray) -> tuple[int, int]:
    """Recover [lo, hi) of a 1-D basic-slice view into its base array."""
    offset = (view.__array_interface__["data"][0]
              - orig.__array_interface__["data"][0]) // orig.itemsize
    return int(offset), int(offset) + view.shape[0]


def execute_plan_chunked(plan: LoweredPlan, k: int, device_arrays: list,
                         *, span: Optional[int] = None,
                         threshold_box=None, fault_injector=None
                         ) -> Optional[dict[str, Any]]:
    """Run the plan as a resumable chunked scan; returns the same result
    dict as `executor.execute_plan`, or None when the plan does not chunk
    (caller falls back to the fused path). A cancelled query returns the
    merged-so-far state with `"partial": True` (or raises
    `CancelledQuery` when nothing merged yet / partials disabled)."""
    if not CHUNKING.enabled:
        return None
    mode_info = chunk_mode(plan)
    if mode_info is None:
        return None
    mode, total, align = mode_info
    if total <= 0:
        return None
    if span is None:
        span = (CHUNKING.posting_span if mode == "posting"
                else CHUNKING.doc_span)
    if span is None:
        span = CHUNK_SIZER.span_for(mode, align)
    if span is None or span <= 0:
        return None
    spans = chunk_spans(total, span, align)
    if len(spans) < 2:
        # single chunk == the fused program: keep the seed path (and the
        # seed compile-cache closure) byte-identical
        return None

    tenant = effective_tenant()
    deadline = current_deadline()
    token = current_cancel_token()
    bounds = _host_chunk_bounds(plan, spans) if mode == "posting" else None
    early_ok = _early_term_eligible(plan, k, mode)

    with PREEMPT_GATE.running(tenant.priority):
        for _attempt in range(2):
            try:
                return _run_scan(plan, k, device_arrays, mode, spans, bounds,
                                 early_ok, tenant, deadline, token,
                                 threshold_box, fault_injector)
            except _RestartScan:
                CHUNK_RESTARTS_TOTAL.inc()
                continue
        # two carried-state losses in a row: finish fused so chaos storms
        # degrade to the seed path instead of livelocking the scan
        return executor.execute_plan(plan, k, device_arrays)


def _run_scan(plan, k, device_arrays, mode, spans, bounds, early_ok,
              tenant, deadline, token, threshold_box, fault_injector):
    clock = get_clock()
    state = _CarriedState()
    threshold = (float(np.asarray(plan.scalars[plan.threshold_slot]))
                 if plan.threshold_slot >= 0 else None)
    last_boundary = clock.monotonic()
    for index, (lo, hi) in enumerate(spans):
        if index > 0:
            now = clock.monotonic()
            CHUNK_BOUNDARY_SECONDS.observe(now - last_boundary)
            last_boundary = now
            if flight.recording():
                flight.emit("chunk.boundary",
                            attrs={"index": index, "of": len(spans)})
            # (a) kill: explicit cancel, then deadline — mid-kernel at
            # chunk granularity, the whole point of the boundary
            if token is not None and token.cancelled:
                if CHUNKING.partial_on_cancel and state.chunks_done > 0:
                    return state.to_result(k, partial=True)
                raise CancelledQuery("chunked scan boundary", token.reason)
            if deadline is not None:
                deadline.check("chunked scan boundary")
            # chaos: a fault at the yield point must never wedge the
            # carried state — it is discarded and the scan restarts clean
            if fault_injector is not None:
                try:
                    fault_injector.perturb("kernel.chunk_yield")
                except InjectedFault as exc:
                    raise _RestartScan() from exc
            # (b) preempt: park the carried state while a higher class
            # runs, byte-accounted against the tenant's DRR quantum
            if PREEMPT_GATE.should_yield(tenant.priority):
                PREEMPT_TOTAL.inc()
                ticket = PARKED_STATES.park(tenant.tenant_id, state.nbytes())
                if flight.recording():
                    flight.emit("chunk.preempt_park",
                                attrs={"bytes": state.nbytes(),
                                       "priority": tenant.priority})
                try:
                    if fault_injector is not None:
                        fault_injector.perturb("kernel.preempt_park")
                    PREEMPT_GATE.wait_until_clear(
                        tenant.priority, CHUNKING.max_park_secs,
                        deadline=deadline, token=token)
                except InjectedFault as exc:
                    ticket.evicted = True
                    raise _RestartScan() from exc
                finally:
                    PARKED_STATES.release(ticket)
                if ticket.evicted:
                    # parked-state eviction under byte pressure: the
                    # resumed query has nothing to resume FROM
                    flight.emit("chunk.preempt_evict")
                    raise _RestartScan()
                flight.emit("chunk.preempt_resume")
            # (c) early termination + boundary threshold tightening
            kth = state.kth_value(k)
            if (early_ok and kth is not None and bounds is not None
                    and index < len(bounds)
                    and float(bounds[index:].max()) <= kth):
                CHUNK_EARLY_TERMINATIONS_TOTAL.inc()
                if flight.recording():
                    flight.emit("chunk.early_term",
                                attrs={"after": index, "of": len(spans)})
                result = state.to_result(k)
                # the remaining chunks' matches never ran: the exact count
                # is the host-side impact-prefix override
                result["count"] = plan.count_override
                return result
            if threshold is not None:
                box_value = (threshold_box.get()
                             if threshold_box is not None else None)
                for candidate in (box_value, kth):
                    if candidate is not None and candidate > threshold:
                        # monotone tightening only: the threshold mask
                        # keeps >=, so no final-top-K lane is ever lost
                        threshold = candidate
        chunk = (posting_chunk_plan(plan, lo, hi) if mode == "posting"
                 else dense_chunk_plan(plan, lo, hi - lo))
        if threshold is not None:
            chunk.scalars[plan.threshold_slot] = np.float64(threshold)
        if mode == "dense" and chunk.num_docs <= 0 and state.chunks_done > 0:
            continue  # fully past num_docs: no valid lanes, no new state
        chunk_dev = _chunk_device_arrays(plan, chunk, device_arrays)
        t0 = clock.monotonic()
        result = executor.execute_plan(chunk, k, chunk_dev)
        CHUNK_DISPATCHES_TOTAL.inc()
        CHUNK_SIZER.observe(mode, hi - lo, clock.monotonic() - t0)
        if mode == "dense" and k > 0:
            # chunk doc ids are window-local; hits rebase to global doc
            # space host-side (dead -inf lanes keep id 0 — they pad past
            # the live hits and are never decoded)
            live = result["sort_values"] > -np.inf
            result["doc_ids"] = np.where(
                live, np.asarray(result["doc_ids"]) + lo,
                result["doc_ids"]).astype(np.int32)
        state.absorb(result, k)
    return state.to_result(k)


def maybe_execute_chunked(plan: LoweredPlan, k: int, device_arrays: list,
                          threshold_box=None, fault_injector=None
                          ) -> Optional[dict[str, Any]]:
    """The leaf's entry point: chunked result dict, or None for the fused
    path (ineligible plan, chunking disabled, or work too small to span
    two chunks)."""
    return execute_plan_chunked(plan, k, device_arrays,
                                threshold_box=threshold_box,
                                fault_injector=fault_injector)


# --- query-group chunked scan (ROADMAP item 2 × item 4) ---------------------
#
# A stacked query group (search/batcher.py QueryGroupPlanner) composed with
# chunked execution: the carried state grows a query dim (one _CarriedState
# per lane), each chunk executes as ONE stacked dispatch over all lanes,
# and every chunk boundary applies PER-QUERY masks — a lane cancelled or
# expired mid-scan flips to valid=False in subsequent chunk dispatches
# (same program, zeroed row) while the surviving lanes keep scanning.
# Early termination and threshold tightening are per-lane: each query's
# own ThresholdBox and carried Kth value drive its mask. Preemption is a
# GROUP decision at the maximum priority among live lanes: a group
# carrying an interactive rider never parks for interactive work
# elsewhere, and the park is byte-accounted once for the summed carried
# state.

def execute_group_chunked(plans: list, k: int, arrays_list: list, *,
                          valid=None, tboxes=None, deadlines=None,
                          cancels=None, tenants=None,
                          fault_injector=None,
                          span: Optional[int] = None) -> Optional[list]:
    """Run a shape-compatible query group as one chunked stacked scan.

    Returns a list aligned with `plans`: per lane a result dict, an
    exception instance (CancelledQuery / DeadlineExceeded — the batcher
    fans it to that rider), or None for a lane masked on entry. Returns
    None (the group does not chunk) when the shared structure is
    ineligible or too small to span two chunks — the caller falls back to
    one fused stacked dispatch."""
    if not CHUNKING.enabled:
        return None
    base = plans[0]
    mode_info = chunk_mode(base)
    if mode_info is None:
        return None
    mode, total, align = mode_info
    if total <= 0:
        return None
    if span is None:
        span = (CHUNKING.posting_span if mode == "posting"
                else CHUNKING.doc_span)
    if span is None:
        span = CHUNK_SIZER.span_for(mode, align)
    if span is None or span <= 0:
        return None
    spans = chunk_spans(total, span, align)
    if len(spans) < 2:
        return None

    q = len(plans)
    valid = list(valid) if valid is not None else [True] * q
    tboxes = list(tboxes) if tboxes is not None else [None] * q
    deadlines = list(deadlines) if deadlines is not None else [None] * q
    cancels = list(cancels) if cancels is not None else [None] * q
    if tenants is None:
        tenants = [effective_tenant()] * q
    bounds = [(_host_chunk_bounds(p, spans) if mode == "posting" else None)
              for p in plans]
    early_ok = [_early_term_eligible(p, k, mode) for p in plans]

    for _attempt in range(2):
        try:
            return _run_group_scan(plans, k, arrays_list, mode, spans,
                                   bounds, early_ok, list(valid), tboxes,
                                   deadlines, cancels, tenants,
                                   fault_injector)
        except _RestartScan:
            CHUNK_RESTARTS_TOTAL.inc()
            continue
    # two carried-state losses in a row: finish as one fused stacked
    # dispatch — the group degrades to the unchunked stacked path instead
    # of livelocking the scan
    results = executor.readback_plan_stacked(executor.dispatch_plan_stacked(
        plans, k, arrays_list, valid=valid))
    return results


def _group_park_lane(live, tenants):
    """The lane whose tenant charges (and whose priority gates) a group
    park: the highest-priority live lane."""
    lanes = [i for i, alive in enumerate(live) if alive]
    return max(lanes, key=lambda i: tenants[i].priority)


def _run_group_scan(plans, k, arrays_list, mode, spans, bounds, early_ok,
                    live, tboxes, deadlines, cancels, tenants,
                    fault_injector):
    clock = get_clock()
    q = len(plans)
    base = plans[0]
    states = [_CarriedState() for _ in range(q)]
    outcome: dict[int, Any] = {}
    thresholds = [
        (float(np.asarray(p.scalars[p.threshold_slot]))
         if p.threshold_slot >= 0 else None)
        for p in plans]
    last_boundary = clock.monotonic()
    for index, (lo, hi) in enumerate(spans):
        if index > 0:
            now = clock.monotonic()
            CHUNK_BOUNDARY_SECONDS.observe(now - last_boundary)
            last_boundary = now
            if flight.recording():
                flight.emit("chunk.boundary",
                            attrs={"index": index, "of": len(spans),
                                   "lanes": int(sum(live))})
            # (a) per-query kill masks: a cancelled/expired lane leaves
            # the dispatch via its validity lane — the group's program
            # shape never changes mid-scan
            for i in range(q):
                if not live[i]:
                    continue
                token = cancels[i]
                if token is not None and token.cancelled:
                    if CHUNKING.partial_on_cancel \
                            and states[i].chunks_done > 0:
                        outcome[i] = states[i].to_result(k, partial=True)
                    else:
                        outcome[i] = CancelledQuery(
                            "chunked group boundary", token.reason)
                    live[i] = False
                    continue
                if deadlines[i] is not None and deadlines[i].expired:
                    outcome[i] = DeadlineExceeded("chunked group boundary")
                    live[i] = False
            if not any(live):
                break
            # chaos: a yield fault discards the whole group's carried
            # state — all lanes restart together (same contract as solo)
            if fault_injector is not None:
                try:
                    fault_injector.perturb("kernel.chunk_yield")
                except InjectedFault as exc:
                    raise _RestartScan() from exc
            # (b) group preempt at the max live priority: parks only when
            # EVERY live lane is outranked by the active higher class
            park_lane = _group_park_lane(live, tenants)
            park_tenant = tenants[park_lane]
            if PREEMPT_GATE.should_yield(park_tenant.priority):
                PREEMPT_TOTAL.inc()
                ticket = PARKED_STATES.park(
                    park_tenant.tenant_id,
                    sum(states[i].nbytes() for i in range(q) if live[i]))
                if flight.recording():
                    flight.emit("chunk.preempt_park",
                                attrs={"bytes": ticket.nbytes,
                                       "priority": park_tenant.priority,
                                       "lanes": int(sum(live))})
                try:
                    if fault_injector is not None:
                        fault_injector.perturb("kernel.preempt_park")
                    PREEMPT_GATE.wait_until_clear(
                        park_tenant.priority, CHUNKING.max_park_secs,
                        deadline=deadlines[park_lane],
                        token=cancels[park_lane])
                except InjectedFault as exc:
                    ticket.evicted = True
                    raise _RestartScan() from exc
                finally:
                    PARKED_STATES.release(ticket)
                if ticket.evicted:
                    flight.emit("chunk.preempt_evict")
                    raise _RestartScan()
                flight.emit("chunk.preempt_resume")
            # (c) per-lane early termination + threshold tightening
            for i in range(q):
                if not live[i]:
                    continue
                kth = states[i].kth_value(k)
                if (early_ok[i] and kth is not None and bounds[i] is not None
                        and index < len(bounds[i])
                        and float(bounds[i][index:].max()) <= kth):
                    CHUNK_EARLY_TERMINATIONS_TOTAL.inc()
                    if flight.recording():
                        flight.emit("chunk.early_term",
                                    attrs={"after": index, "lane": i})
                    result = states[i].to_result(k)
                    result["count"] = plans[i].count_override
                    outcome[i] = result
                    live[i] = False
                    continue
                if thresholds[i] is not None:
                    box_value = (tboxes[i].get()
                                 if tboxes[i] is not None else None)
                    for candidate in (box_value, kth):
                        if candidate is not None \
                                and candidate > thresholds[i]:
                            thresholds[i] = candidate
            if not any(live):
                break
        chunks = []
        for i in range(q):
            chunk = (posting_chunk_plan(plans[i], lo, hi)
                     if mode == "posting"
                     else dense_chunk_plan(plans[i], lo, hi - lo))
            if thresholds[i] is not None:
                chunk.scalars[plans[i].threshold_slot] = \
                    np.float64(thresholds[i])
            chunks.append(chunk)
        if (mode == "dense" and chunks[0].num_docs <= 0
                and all(states[i].chunks_done > 0
                        for i in range(q) if live[i])):
            continue  # fully past num_docs for every lane: nothing to add
        chunk_devs = [_chunk_device_arrays(plans[i], chunks[i],
                                           arrays_list[i])
                      for i in range(q)]
        t0 = clock.monotonic()
        results = executor.readback_plan_stacked(
            executor.dispatch_plan_stacked(chunks, k, chunk_devs,
                                           valid=list(live)))
        CHUNK_DISPATCHES_TOTAL.inc()
        CHUNK_SIZER.observe(mode, hi - lo, clock.monotonic() - t0)
        for i in range(q):
            if not live[i] or results[i] is None:
                continue
            result = results[i]
            if mode == "dense" and k > 0:
                live_rows = result["sort_values"] > -np.inf
                result["doc_ids"] = np.where(
                    live_rows, np.asarray(result["doc_ids"]) + lo,
                    result["doc_ids"]).astype(np.int32)
            states[i].absorb(result, k)
    for i in range(q):
        if live[i]:
            outcome[i] = states[i].to_result(k)
    return [outcome.get(i) for i in range(q)]
