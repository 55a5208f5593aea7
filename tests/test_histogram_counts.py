"""`ops/aggs.py::histogram_counts`: integer bucket counts as a product of two
one-hot matrices must equal the scatter-add it replaced, bit for bit, and the
programs that hold a percentile sketch must no longer scatter."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from quickwit_tpu.common.uri import Uri
from quickwit_tpu.index.reader import SplitReader
from quickwit_tpu.index.synthetic import HDFS_MAPPER, synthetic_hdfs_split
from quickwit_tpu.ops import aggs as agg_ops
from quickwit_tpu.query.ast import Range, RangeBound
from quickwit_tpu.search import SearchRequest, executor
from quickwit_tpu.search.leaf import prepare_plan_only
from quickwit_tpu.storage import RamStorage
from tools.qwir.ir import iter_eqns

CHUNK = agg_ops._PRODUCT_CHUNK
CUT_OVER = agg_ops._PRODUCT_MAX_BUCKETS
PCTL = agg_ops.PCTL_NUM_BUCKETS
QUERIES = 4

LANES = {
    "chunk_multiple": 2 * CHUNK,
    "ragged_tail": 2 * CHUNK + 4_321,
    "under_one_chunk": 20_480,
    "all_sentinel": CHUNK + 7,
    "one_bucket": CHUNK + 7,
}


def scatter_counts(idx, num_buckets):
    """The formulation `histogram_counts` replaced: the reference."""
    return jnp.zeros(num_buckets, dtype=jnp.int32).at[idx].add(1, mode="drop")


def _lanes(case: str, num_buckets: int, rows: int) -> np.ndarray:
    rng = np.random.default_rng(num_buckets * 31 + len(case))
    shape = (rows, LANES[case])
    if case == "all_sentinel":
        return np.full(shape, num_buckets, dtype=np.int32)
    if case == "one_bucket":
        return np.full(shape, num_buckets - 1, dtype=np.int32)
    # skewed, as a sketch's buckets are, and 85 % masked out
    idx = (np.minimum(rng.zipf(1.3, size=shape), num_buckets) - 1) * 7919
    idx = np.where(rng.random(shape) < 0.85, num_buckets, idx % num_buckets)
    idx[:, 0], idx[:, -1] = 0, num_buckets - 1       # both ends of the space
    return idx.astype(np.int32)


@pytest.mark.parametrize("stacked", [False, True], ids=["solo", "vmap4"])
@pytest.mark.parametrize("case", sorted(LANES))
@pytest.mark.parametrize("num_buckets", [257, PCTL, 7 * PCTL, CUT_OVER - 1,
                                         CUT_OVER, CUT_OVER + 1])
def test_histogram_counts_equals_scatter_add(num_buckets, case, stacked):
    idx = _lanes(case, num_buckets, QUERIES if stacked else 1)
    counts = lambda lanes: agg_ops.histogram_counts(lanes, num_buckets)
    reference = lambda lanes: scatter_counts(lanes, num_buckets)
    if stacked:
        got = jax.jit(jax.vmap(counts))(idx)
        want = jax.jit(jax.vmap(reference))(idx)
    else:
        got, want = jax.jit(counts)(idx[0]), jax.jit(reference)(idx[0])
    assert got.dtype == jnp.int32 and got.shape == want.shape
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    kept = int((idx < num_buckets).sum())
    assert int(np.asarray(got).sum()) == kept


T0_US = 1_600_000_000 * 1_000_000
DAY_US = 86_400 * 1_000_000
PCTL_AGGS = {"tenants": {"percentiles": {"field": "tenant_id",
                                         "percents": [50, 95, 99]}}}


def _scatters(closed) -> list:
    return [eqn.primitive.name for eqn in iter_eqns(closed)
            if eqn.primitive.name.startswith("scatter")]


def test_percentile_programs_do_not_scatter_and_outputs_are_unchanged():
    storage = RamStorage(Uri.parse("ram:///histogram-counts"))
    storage.put("hdfs.split", synthetic_hdfs_split(200_000, seed=26))
    reader = SplitReader(storage, "hdfs.split")

    # a range filter compares columns, so the sketch's was the only scatter
    lanes = [prepare_plan_only(
        SearchRequest(index_ids=["hdfs-logs"], max_hits=0, aggs=PCTL_AGGS,
                      query_ast=Range(
                          "timestamp",
                          lower=RangeBound(T0_US + lane * DAY_US // 8, True),
                          upper=RangeBound(T0_US + (3 + lane) * DAY_US, False))),
        HDFS_MAPPER, reader, "s0") for lane in range(QUERIES)]
    assert len({plan.structure_digest(0) for plan in lanes}) == 1
    for closed in (executor.abstract_program(lanes[0], 0),
                   executor.abstract_stacked_program(lanes, 0)):
        assert _scatters(closed) == []
        assert any(eqn.primitive.name == "dot_general"
                   for eqn in iter_eqns(closed))

    tenants, present = reader.column_values("tenant_id")
    micros, _ = reader.column_values("timestamp")
    seconds_of_day = (micros // 1_000_000) % 86_400          # a wide column
    rng = np.random.default_rng(26)
    mask = rng.random(tenants.shape[0]) < 0.15
    live = mask & present.astype(bool)
    for values in (tenants, seconds_of_day):
        sketch_bucket = agg_ops._pctl_bucket(jnp.asarray(values))
        got = agg_ops.percentile_sketch(jnp.asarray(values),
                                        jnp.asarray(present), jnp.asarray(mask))
        want = scatter_counts(jnp.where(live, sketch_bucket, PCTL), PCTL)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        assert int(np.asarray(got).sum()) == int(live.sum())

        for nb in (7, 40):      # flat spaces under and over the cut-over
            idx = np.where(live, values % nb, nb).astype(np.int32)
            got = agg_ops.bucket_percentile_sketch(jnp.asarray(idx),
                                                   jnp.asarray(values), nb)
            flat = jnp.where(idx < nb, idx * PCTL + sketch_bucket, nb * PCTL)
            want = scatter_counts(flat, nb * PCTL).reshape(nb, PCTL)
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    for nb in (4, 1_008, CUT_OVER + 1):   # compare-and-reduce, product, scatter
        idx = np.where(live, seconds_of_day * nb // 86_400, nb).astype(np.int32)
        got = agg_ops.bucket_counts(jnp.asarray(idx), nb)
        np.testing.assert_array_equal(
            np.asarray(got), np.bincount(idx[live], minlength=nb))
