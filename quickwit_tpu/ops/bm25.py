"""BM25 scoring over padded posting arrays.

Role of tantivy's `Bm25Weight`/`Bm25Scorer` (used by the reference's leaf hot
loop): identical formula and defaults (k1=1.2, b=0.75,
idf = ln(1 + (N - df + 0.5)/(df + 0.5))), but evaluated **vectorized over a
whole posting array at once** — a gather of field norms plus a fused
elementwise expression on the VPU — instead of per-hit scalar math.

Pad slots (tf == 0) score exactly 0, so padded postings need no masking.
"""

from __future__ import annotations

import math

import jax.numpy as jnp

K1 = 1.2
B = 0.75


def idf(num_docs: int, df: int) -> float:
    """Static per-term idf, computed host-side at plan time."""
    return math.log(1.0 + (num_docs - df + 0.5) / (df + 0.5))


def score_postings(tfs: jnp.ndarray, doc_ids: jnp.ndarray,
                   fieldnorms: jnp.ndarray, avg_len: float,
                   idf_value: float, boost: float = 1.0) -> jnp.ndarray:
    """Per-posting BM25 partial scores (float32, same shape as `tfs`).

    `fieldnorms` is the dense per-doc token count; pad posting ids gather a
    clipped norm, but tf==0 zeroes the numerator so pads contribute nothing.
    """
    tf = tfs.astype(jnp.float32)
    norms = fieldnorms[jnp.clip(doc_ids, 0, fieldnorms.shape[0] - 1)].astype(jnp.float32)
    return _bm25(tf, norms, avg_len, idf_value, boost)


def score_lanes(lane: jnp.ndarray, fieldnorms: jnp.ndarray, avg_len: float,
                idf_value: float, boost: float = 1.0) -> jnp.ndarray:
    """Per-doc BM25 scores (float32, [num_docs_padded]) of a term held as a
    resident tf lane (search/plan.py::PTermLane): the same expression as
    `score_postings`, read in place over every doc, zero where the term is
    absent (tf == 0) — what scattering `score_postings` into zeros gives,
    since each matching doc receives exactly one partial and `0 + x == x`.
    """
    scores = _bm25(lane.astype(jnp.float32), fieldnorms.astype(jnp.float32),
                   avg_len, idf_value, boost)
    return jnp.where(lane > 0, scores, jnp.float32(0.0))


def _bm25(tf, norms, avg_len, idf_value, boost):
    """THE BM25 expression over f32 tf and fieldnorm operands."""
    denom = tf + K1 * (1.0 - B + B * norms / jnp.maximum(avg_len, 1e-9))
    return (boost * idf_value * (K1 + 1.0)) * tf / jnp.maximum(denom, 1e-9)


def dequantize_block_bounds(bmax: jnp.ndarray, scale) -> jnp.ndarray:
    """Per-block f64 score upper bounds from the u8 block maxima of an
    impact-ordered term (format v3, index/impact.py).

    `scale` is a traced f64 scalar — the persisted per-term dequantization
    scale with the query boost already folded in host-side at lowering,
    mirroring how boost folds into the idf scalar. Soundness
    (`bmax * scale >= score` for every posting of the block) is the
    writer's quantization contract."""
    return bmax.astype(jnp.float64) * scale
