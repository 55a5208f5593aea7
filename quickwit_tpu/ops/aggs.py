"""Aggregation kernels with mergeable intermediate states.

Role of the reference's aggregation path (tantivy aggregations driven by
`QuickwitAggregations`, `quickwit-search/src/collector.rs:600`, merged as
serialized intermediate results): each aggregation computes a **fixed-shape
intermediate state** on device (counts / sums / sketch buckets) that merges by
elementwise addition (plus min/max), so the scatter-gather merge tree — and
the multi-chip `psum` — is a pure reduction.

Kernels here: stats state and the percentile sketch. Bucket aggregations
(histogram/date_histogram/terms) are assembled inline by
`search/executor.py::eval_bucket_agg` because they share one bucket-index
computation across counts and per-bucket metrics; the scatter-sentinel
convention (negative indices WRAP in jax scatters, so masked docs are
remapped to a positive out-of-bounds sentinel that mode="drop" drops) is
documented there.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# --- bucket reductions ------------------------------------------------------
# Three ways to count doc lanes into integer buckets, chosen from the static
# bucket count alone. Measured on a v5e over one 10,000,384-lane split with
# 85 % of the lanes masked out (PERF.md, PR 26):
#   * scatter-add: 67-88 ms whether into 257 or 1M buckets. It serializes,
#     so it costs per LANE (7-9 ns), and a masked lane is scattered like any
#     other: it carries the sentinel.
#   * compare-and-reduce over a broadcast [docs, buckets] predicate, fused
#     onto the VPU: 0.9 ms at 4 buckets, 6.4 ms at 256; cost grows with
#     the bucket count. Up to _COMPARE_MAX_BUCKETS.
#   * `histogram_counts`' product of two one-hot matrices on the MXU:
#     1.4 ms at 257 buckets, 1.7 ms at 2,602, 4.3 ms at 18,214, 10.3 ms at
#     65,536, 35.9 ms at 262,144; cost grows with buckets / 64 and would
#     pass the scatter's near 430k. Up to _PRODUCT_MAX_BUCKETS, set well
#     under that; the scatter-add above it.

_COMPARE_MAX_BUCKETS = 256
_COMPARE_MAX_BUCKETS_METRIC = 64
_PRODUCT_MAX_BUCKETS = 65536
_PRODUCT_LO_BITS = 6
# lanes per product: the f32 accumulation of 0/1 products is exact for far
# fewer than 2^24 of them; chunk sums are added in int32
_PRODUCT_CHUNK = 1 << 16


def histogram_counts(idx: jnp.ndarray, num_buckets: int) -> jnp.ndarray:
    """int32[num_buckets] counts of the doc lanes `idx` (int32 [docs]); a
    lane outside [0, num_buckets) — the callers' sentinel is num_buckets —
    counts nowhere. Bit for bit `zeros.at[idx].add(1, mode="drop")`.

    A bucket b splits into hi = b >> 6 and lo = b & 63; for a chunk of
    lanes, onehot(hi)^T @ onehot(lo) is [H, 64] and its flattening is the
    chunk's histogram over [0, 64 * H), the sentinel landing past the
    slice returned. The one-hots feed the contraction as fused operands:
    nothing [docs, buckets] reaches HBM."""
    if num_buckets > _PRODUCT_MAX_BUCKETS:
        return jnp.zeros(num_buckets, dtype=jnp.int32).at[idx].add(
            1, mode="drop")
    lo_n = 1 << _PRODUCT_LO_BITS
    hi_n = -(-(num_buckets + 1) // lo_n)
    n = idx.shape[0]
    chunk = max(1, min(_PRODUCT_CHUNK, n))
    n_chunks = -(-n // chunk)
    chunks = jnp.pad(idx, (0, n_chunks * chunk - n),
                     constant_values=num_buckets).reshape(n_chunks, chunk)
    hi_ids = jnp.arange(hi_n, dtype=jnp.int32)[:, None]
    lo_ids = jnp.arange(lo_n, dtype=jnp.int32)[:, None]

    def add_chunk(counts, lanes):
        hi = (lanes >> _PRODUCT_LO_BITS)[None, :] == hi_ids      # [H, C]
        lo = (lanes & (lo_n - 1))[None, :] == lo_ids             # [64, C]
        part = jax.lax.dot_general(
            hi.astype(jnp.bfloat16), lo.astype(jnp.bfloat16),
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        return counts + part.astype(jnp.int32), None

    with jax.named_scope("histogram"):
        counts, _ = jax.lax.scan(
            add_chunk, jnp.zeros((hi_n, lo_n), dtype=jnp.int32), chunks)
    return counts.reshape(-1)[:num_buckets]


def bucket_counts(idx: jnp.ndarray, num_buckets: int) -> jnp.ndarray:
    """Counts per bucket; `idx` int32 with out-of-range sentinel for dropped
    docs (e.g. num_buckets)."""
    if num_buckets <= _COMPARE_MAX_BUCKETS:
        eq = idx[:, None] == jnp.arange(num_buckets, dtype=jnp.int32)[None, :]
        return jnp.sum(eq, axis=0, dtype=jnp.int32)
    return histogram_counts(idx, num_buckets)


def bucket_sum(idx: jnp.ndarray, values: jnp.ndarray, num_buckets: int,
               dtype=jnp.float64) -> jnp.ndarray:
    """Per-bucket sums of `values` (docs with sentinel idx contribute 0)."""
    if num_buckets <= _COMPARE_MAX_BUCKETS_METRIC:
        eq = idx[:, None] == jnp.arange(num_buckets, dtype=jnp.int32)[None, :]
        return jnp.sum(jnp.where(eq, values[:, None].astype(dtype), 0), axis=0)
    return jnp.zeros(num_buckets, dtype=dtype).at[idx].add(
        values.astype(dtype), mode="drop")


def bucket_min(idx: jnp.ndarray, values: jnp.ndarray, num_buckets: int) -> jnp.ndarray:
    if num_buckets <= _COMPARE_MAX_BUCKETS_METRIC:
        eq = idx[:, None] == jnp.arange(num_buckets, dtype=jnp.int32)[None, :]
        return jnp.min(jnp.where(eq, values[:, None].astype(jnp.float64), jnp.inf), axis=0)
    return jnp.full(num_buckets, jnp.inf, dtype=jnp.float64).at[idx].min(
        values.astype(jnp.float64), mode="drop")


def bucket_max(idx: jnp.ndarray, values: jnp.ndarray, num_buckets: int) -> jnp.ndarray:
    if num_buckets <= _COMPARE_MAX_BUCKETS_METRIC:
        eq = idx[:, None] == jnp.arange(num_buckets, dtype=jnp.int32)[None, :]
        return jnp.max(jnp.where(eq, values[:, None].astype(jnp.float64), -jnp.inf), axis=0)
    return jnp.full(num_buckets, -jnp.inf, dtype=jnp.float64).at[idx].max(
        values.astype(jnp.float64), mode="drop")


# --- stats -----------------------------------------------------------------

def stats_state(values: jnp.ndarray, present: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    """[count, sum, sum_sq, min, max] as float64 — elementwise-mergeable
    (first three add; min/max combine)."""
    m = mask & present.astype(jnp.bool_)
    vals = values.astype(jnp.float64)
    count = jnp.sum(m).astype(jnp.float64)
    s = jnp.sum(jnp.where(m, vals, 0.0))
    s2 = jnp.sum(jnp.where(m, vals * vals, 0.0))
    mn = jnp.min(jnp.where(m, vals, jnp.inf))
    mx = jnp.max(jnp.where(m, vals, -jnp.inf))
    return jnp.stack([count, s, s2, mn, mx])


def merge_stats_states(a, b) -> np.ndarray:
    """Merge two `stats_state` partials ([count, sum, sum_sq, min, max]).

    The layout contract lives here, next to the kernel that emits it: the
    first three components add, min/max combine — which is what makes the
    per-split partials a pure fixed-shape reduction (associative and
    commutative), mergeable host-side at the collector or on device under
    `psum`. Operates on host numpy (post-readback partials)."""
    # qwlint: disable-next-line=QW001 - post-readback host partials by contract
    a, b = np.asarray(a), np.asarray(b)
    return np.array([a[0] + b[0], a[1] + b[1], a[2] + b[2],
                     min(a[3], b[3]), max(a[4], b[4])])


# --- percentiles (DDSketch-compatible log buckets) ------------------------
#
# Bucket mapping matches the sketch the reference drives through tantivy
# (sketches-ddsketch with 1% relative accuracy): γ = (1+α)/(1-α) with
# α = 0.01, a value v > 0 lands in bucket k = ceil(log_γ v), and the
# bucket reports 2γ^k/(γ+1) — verified to reproduce the reference
# conformance corpus values to ~1e-12 (e.g. 100 → 100.49456770856...).
# Non-positive values land in the underflow bucket (reported 0.0);
# positive values below the k-range clip to the FIRST real bucket
# (reported ~2.8e-10 — closer to truth than 0 for tiny durations).

PCTL_ALPHA = 0.01
PCTL_GAMMA = (1.0 + PCTL_ALPHA) / (1.0 - PCTL_ALPHA)
_PCTL_LN_GAMMA = float(np.log(PCTL_GAMMA))
PCTL_K_MIN = -1100   # v ≈ 2.8e-10
PCTL_K_MAX = 1500    # v ≈ 1.1e13
PCTL_NUM_BUCKETS = PCTL_K_MAX - PCTL_K_MIN + 2  # +underflow bucket 0


def percentile_sketch(values: jnp.ndarray, present: jnp.ndarray,
                      mask: jnp.ndarray) -> jnp.ndarray:
    """DDSketch bucket counts [PCTL_NUM_BUCKETS] int32.

    Positive values (durations, sizes); merge = elementwise add."""
    m = mask & present.astype(jnp.bool_)
    bucket = jnp.where(m, _pctl_bucket(values), jnp.int32(PCTL_NUM_BUCKETS))
    return histogram_counts(bucket, PCTL_NUM_BUCKETS)


def _pctl_bucket(values: jnp.ndarray) -> jnp.ndarray:
    """Value → DDSketch bucket index (shared by the global and per-bucket
    sketch builders so their resolution can never drift)."""
    v = values.astype(jnp.float64)
    positive = v > 0.0
    k = jnp.ceil(jnp.log(jnp.maximum(v, 1e-300)) / _PCTL_LN_GAMMA)
    idx = jnp.clip(k.astype(jnp.int32) - PCTL_K_MIN + 1,
                   1, PCTL_NUM_BUCKETS - 1)
    return jnp.where(positive, idx, jnp.int32(0))


def bucket_percentile_sketch(idx: jnp.ndarray, values: jnp.ndarray,
                             num_buckets: int) -> jnp.ndarray:
    """Per-bucket HDR sketches [num_buckets, PCTL_NUM_BUCKETS] int32.

    `idx` int32 with out-of-range sentinel (num_buckets) for dropped docs.
    One `histogram_counts` over the flattened [nb * PCTL] space: the one-hot
    product while that space is at most _PRODUCT_MAX_BUCKETS (25 buckets;
    4.3 ms for 7 on a 10M-doc split), the scatter-add above (67-76 ms)."""
    sb = _pctl_bucket(values)
    flat = jnp.where(idx < num_buckets, idx * PCTL_NUM_BUCKETS + sb,
                     jnp.int32(num_buckets * PCTL_NUM_BUCKETS))
    return histogram_counts(flat, num_buckets * PCTL_NUM_BUCKETS).reshape(
        num_buckets, PCTL_NUM_BUCKETS)


# qwlint: disable-next-line=QW001 - root-side finalize over a host numpy
# sketch already shipped from the leaves; no device data in sight
def sketch_quantiles(counts: np.ndarray, quantiles: list[float]) -> list[float]:
    """Host-side quantile estimation from a (merged) sketch."""
    counts = np.asarray(counts)
    total = counts.sum()
    if total == 0:
        return [float("nan")] * len(quantiles)
    cum = np.cumsum(counts)
    out = []
    for q in quantiles:
        # DDSketch (sketches-ddsketch crate, used by tantivy) rank rule:
        # rank = floor(q·(n-1)), return the first bucket whose cumulative
        # count strictly exceeds it — i.e. the 0-based rank-th item.
        # (p85 of {30,130} → 30's bucket, median of 5 → the 3rd item.)
        rank = int(np.floor(q * (total - 1)))
        target = min(rank + 1, int(total))
        bucket = int(np.searchsorted(cum, target, side="left"))
        bucket = min(bucket, len(counts) - 1)
        if bucket == 0:
            out.append(0.0)
        else:
            k = bucket + PCTL_K_MIN - 1
            out.append(2.0 * PCTL_GAMMA ** k / (PCTL_GAMMA + 1.0))
    return out


# --- cardinality (HyperLogLog) ---------------------------------------------
# 256 registers (p=8, ~6.5% relative error — matching the tolerance band of
# ES's default-precision cardinality). The register vector is the mergeable
# state: cross-split/cross-chip merge is an elementwise max, so it rides the
# same psum-style reduction tree as the other agg states (with max instead
# of add). Register updates use the compare-and-reduce pattern (scatter-max
# into 256 buckets serializes on TPU, same pathology as bucket_counts).

HLL_NUM_REGISTERS = 256
_HLL_P = 8


def hll_hash_bytes(data: bytes) -> int:
    """Host-side hashing of term strings so that identical terms hash
    identically across splits regardless of their ordinals: 64-bit
    FNV-1a + the splitmix64 finalizer. The finalizer is ESSENTIAL —
    HLL's register index is the hash's TOP bits, and raw FNV-1a of
    short, similar terms ("svc0".."svc6") barely diffuses trailing-byte
    differences upward, collapsing every term into one register (a
    cardinality of ~1). The numeric path applies the same finalizer on
    device (_hll_mix64)."""
    h = 0xcbf29ce484222325
    for b in data:
        h = ((h ^ b) * 0x100000001b3) & 0xFFFFFFFFFFFFFFFF
    # splitmix64 finalizer (keep in lockstep with _hll_mix64)
    h = ((h ^ (h >> 30)) * 0xbf58476d1ce4e5b9) & 0xFFFFFFFFFFFFFFFF
    h = ((h ^ (h >> 27)) * 0x94d049bb133111eb) & 0xFFFFFFFFFFFFFFFF
    return h ^ (h >> 31)


def _hll_mix64(x: jnp.ndarray) -> jnp.ndarray:
    """splitmix64 finalizer on uint64 (i64 ops are emulated on TPU but this
    runs once per doc over fused elementwise ops)."""
    x = (x ^ (x >> 30)) * jnp.uint64(0xbf58476d1ce4e5b9)
    x = (x ^ (x >> 27)) * jnp.uint64(0x94d049bb133111eb)
    return x ^ (x >> 31)


def _hll_reg_rho(hashes: jnp.ndarray, valid: jnp.ndarray):
    """(register index, rho) per doc: register = top p hash bits, rho =
    1 + leading zeros of the suffix (capped). Invalid docs get rho 0 and
    the out-of-range register sentinel."""
    reg = (hashes >> jnp.uint64(64 - _HLL_P)).astype(jnp.int32)
    suffix = hashes << jnp.uint64(_HLL_P)
    # leading-zero count of the 64-bit suffix via float exponent is
    # imprecise; use a branchless binary clz on uint64
    clz = jnp.zeros(suffix.shape, dtype=jnp.int32)
    x = suffix
    for shift in (32, 16, 8, 4, 2, 1):
        mask_hi = x >> jnp.uint64(64 - shift)
        zero_hi = mask_hi == 0
        clz = clz + jnp.where(zero_hi, shift, 0)
        x = jnp.where(zero_hi, x << jnp.uint64(shift), x)
    rho = jnp.minimum(clz + 1, 64 - _HLL_P).astype(jnp.int32)
    rho = jnp.where(valid, rho, 0)
    reg = jnp.where(valid, reg, jnp.int32(HLL_NUM_REGISTERS))
    return reg, rho


def hll_registers(hashes: jnp.ndarray, valid: jnp.ndarray) -> jnp.ndarray:
    """[HLL_NUM_REGISTERS] int32 register vector (max of rho per register).

    `hashes` uint64 per doc, `valid` bool per doc."""
    reg, rho = _hll_reg_rho(hashes, valid)
    eq = reg[:, None] == jnp.arange(HLL_NUM_REGISTERS,
                                    dtype=jnp.int32)[None, :]
    return jnp.max(jnp.where(eq, rho[:, None], 0), axis=0)


def bucket_hll_registers(idx: jnp.ndarray, hashes: jnp.ndarray,
                         valid: jnp.ndarray,
                         num_buckets: int) -> jnp.ndarray:
    """Per-bucket HLL registers [num_buckets, HLL_NUM_REGISTERS] int32 —
    cardinality as a bucket sub-metric: one scatter-MAX into the
    flattened [nb * registers] space (the per-bucket twin of
    bucket_percentile_sketch's scatter-add)."""
    reg, rho = _hll_reg_rho(hashes, valid)
    ok = valid & (idx < num_buckets)
    flat = jnp.where(ok, idx * HLL_NUM_REGISTERS + reg,
                     jnp.int32(num_buckets * HLL_NUM_REGISTERS))
    out = jnp.zeros(num_buckets * HLL_NUM_REGISTERS, dtype=jnp.int32)
    return out.at[flat].max(rho, mode="drop").reshape(
        num_buckets, HLL_NUM_REGISTERS)


def hll_from_numeric(values: jnp.ndarray, valid: jnp.ndarray) -> jnp.ndarray:
    """Registers for a numeric column: hash the 64-bit value pattern."""
    bits = values.astype(jnp.int64).astype(jnp.uint64) \
        if values.dtype != jnp.float64 \
        else jax_bitcast_f64(values)
    return hll_registers(_hll_mix64(bits), valid)


def jax_bitcast_f64(values: jnp.ndarray) -> jnp.ndarray:
    return jax.lax.bitcast_convert_type(values, jnp.uint64)


# qwlint: disable-next-line=QW001 - host-side HLL bias correction on the
# merged register array (root finalize, off the dispatch path)
def hll_estimate(registers: np.ndarray) -> float:
    """Classic HLL estimate with small-range (linear counting) correction."""
    registers = np.asarray(registers, dtype=np.float64)
    m = float(HLL_NUM_REGISTERS)
    alpha = 0.7213 / (1 + 1.079 / m)
    harmonic = np.sum(np.exp2(-registers))
    estimate = alpha * m * m / harmonic
    zeros = float(np.sum(registers == 0))
    if estimate <= 2.5 * m and zeros > 0:
        estimate = m * np.log(m / zeros)
    return float(estimate)
