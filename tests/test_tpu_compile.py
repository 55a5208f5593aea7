"""Compiles for a described TPU v5e: the served path's programs must be
accepted by the chip's compiler, checked here with no chip attached.

The TPU compiler is installed in the sandbox and compiles for a topology
that is described, not attached (`on-chip-measurement` guide §2). The CPU
backend accepts what the TPU refuses — a 64-bit max/min all-reduce — so
these are the cases no other tier-1 test can
see; the two mesh cases are the regression test for the 64-bit collective
repair in `parallel/fanout.py`. A compile that passes is a compile, never
a chip run.

Only one process may load the TPU's library, so the topology is described
inside a module-scoped fixture of THIS file (never at import, in a skipif
or in conftest.py), the compiles run in the test's own process, and the
persistent compile cache is off around them. Sizes are small on purpose:
the bool+range program alone compiles for over a minute at 2M docs.
"""

import os

import jax
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from quickwit_tpu.common.uri import Uri
from quickwit_tpu.index.reader import SplitReader
from quickwit_tpu.index.synthetic import HDFS_MAPPER, body_term, \
    synthetic_hdfs_split
from quickwit_tpu.parallel import fanout
from quickwit_tpu.query.ast import Bool, Range, RangeBound, Term
from quickwit_tpu.search import SearchRequest, SortField, executor
from quickwit_tpu.search.leaf import prepare_plan_only
from quickwit_tpu.storage import RamStorage

NUM_DOCS = 20_000
T0_US = 1_600_000_000 * 1_000_000
DAY_US = 86_400 * 1_000_000
ERROR = Term("severity_text", "ERROR")
AGGS = {"over_time": {"date_histogram": {"field": "timestamp",
                                         "fixed_interval": "1d"}},
        "severities": {"terms": {"field": "severity_text", "size": 10}}}


def _window(lo_days: float, hi_days: float) -> Range:
    return Range("timestamp",
                 lower=RangeBound(T0_US + int(lo_days * DAY_US), True),
                 upper=RangeBound(T0_US + int(hi_days * DAY_US), False))


# the benchmark's request shapes (benchmark/shapes/), plus the f64
# sort-key path: a term query sorted by timestamp
REQUESTS = {
    "flagship": SearchRequest(index_ids=["hdfs-logs"], query_ast=ERROR,
                              max_hits=10, aggs=AGGS),
    "c2_bool_range": SearchRequest(
        index_ids=["hdfs-logs"], max_hits=100,
        query_ast=Bool(must=(ERROR,),
                       should=(Term("body", body_term(3)),
                               Term("body", body_term(7))),
                       filter=(_window(1, 4),))),
    "timestamp_sort": SearchRequest(
        index_ids=["hdfs-logs"], max_hits=10,
        query_ast=Bool(must=(ERROR,), filter=(_window(1, 5),)),
        sort_fields=(SortField("timestamp", "desc"),)),
    "agg_only": SearchRequest(index_ids=["hdfs-logs"], query_ast=ERROR,
                              max_hits=0, aggs=AGGS),
    # the sketch's one-hot product: a scanned dot_general over doc chunks
    "agg_percentiles": SearchRequest(
        index_ids=["hdfs-logs"], max_hits=0,
        query_ast=Bool(must=(ERROR,), filter=(_window(1, 4),)),
        aggs={"tenants": {"percentiles": {"field": "tenant_id",
                                          "percents": [50, 95, 99]}}}),
}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as exc:  # noqa: BLE001 - any failure to describe skips
        pytest.skip(f"no v5e:2x2 topology can be described here: {exc}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def reader():
    storage = RamStorage(Uri.parse("ram:///tpu-compile"))
    storage.put("hdfs.split", synthetic_hdfs_split(NUM_DOCS, seed=0))
    return SplitReader(storage, "hdfs.split")


@pytest.fixture()
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip: keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _structs(values, sharding):
    return tuple(jax.ShapeDtypeStruct(np.shape(v), np.asarray(v).dtype,
                                      sharding=sharding) for v in values)


def _plan_args(plan, sharding):
    return (_structs(plan.arrays, sharding), _structs(plan.scalars, sharding),
            jax.ShapeDtypeStruct((), np.int32, sharding=sharding))


def _compile(jitted, args):
    compiled = jitted.lower(*args).compile()
    assert compiled.memory_analysis().generated_code_size_in_bytes > 0
    return compiled.as_text()


@pytest.mark.parametrize("name", sorted(REQUESTS))
def test_single_split_program_compiles(name, reader, one_chip,
                                       no_persistent_cache):
    request = REQUESTS[name]
    plan = prepare_plan_only(request, HDFS_MAPPER, reader, "s0")
    args = _plan_args(plan, one_chip)
    # the runtime program: `_build` behind the packed f64 readback
    jitted, _treedef, _spec = executor._get_packed_executor(
        plan, request.max_hits, args, key=("tpu-compile", name))
    _compile(jitted, args)


def test_stacked_query_group_compiles(reader, one_chip, no_persistent_cache):
    lanes = [prepare_plan_only(
        SearchRequest(index_ids=["hdfs-logs"], max_hits=10,
                      query_ast=Bool(must=(ERROR,),
                                     filter=(_window(i / 8, 3 + i / 8),)),
                      sort_fields=(SortField("timestamp", "desc"),)),
        HDFS_MAPPER, reader, "s0") for i in range(8)]
    assert len({p.structure_digest(10) for p in lanes}) == 1
    _shared, stacked_slots = executor.stacked_slot_split(lanes)
    plan, q = lanes[0], len(lanes)
    slots = _structs(plan.arrays, one_chip)
    jitted, _treedef, _spec = executor._get_packed_stacked_executor(
        plan, 10, q, stacked_slots, slots, key=("tpu-compile", "stacked"))
    shared = tuple(s for i, s in enumerate(slots) if i not in stacked_slots)
    lane_stacks = tuple(tuple(slots[i] for _ in range(q))
                        for i in stacked_slots)
    scalars = tuple(jax.ShapeDtypeStruct((q,), np.asarray(s).dtype,
                                         sharding=one_chip)
                    for s in plan.scalars)
    num_docs = jax.ShapeDtypeStruct((q,), np.int32, sharding=one_chip)
    valid = jax.ShapeDtypeStruct((q,), np.bool_, sharding=one_chip)
    _compile(jitted, (shared, lane_stacks, scalars, num_docs, valid))


def test_mask_fill_compiles(reader, one_chip, no_persistent_cache):
    plan = prepare_plan_only(REQUESTS["timestamp_sort"], HDFS_MAPPER, reader,
                             "s0")
    _compile(jax.jit(executor._mask_fill_fn(plan)),
             _plan_args(plan, one_chip))


@pytest.mark.parametrize("axis_splits,axis_docs", [(4, 1), (2, 2)])
def test_mesh_programs_compile(axis_splits, axis_docs, topo, reader,
                               no_persistent_cache):
    """`mesh_batch_fn` on a described four-chip mesh, in both layouts.
    The TPU compiler lowers only *sum* all-reduces on 64-bit types; the
    f64 threshold exchange, certificate and agg min/max must reach it as
    all_gather + local reduce (`fanout._all_reduce_extremum`)."""
    mesh = fanout.make_mesh(axis_splits, axis_docs, devices=topo.devices)
    readers, split_ids = [reader] * 4, [f"s{i}" for i in range(4)]
    # stats carries a 64-bit min and max through the agg merge as well
    aggs = {**AGGS, "tenants": {"stats": {"field": "tenant_id"}}}
    batch = fanout.build_batch(
        SearchRequest(index_ids=["hdfs-logs"], max_hits=10, aggs=aggs,
                      query_ast=ERROR),
        HDFS_MAPPER, readers, split_ids)
    arrays_sh, scalars_sh, nd_sh = fanout.batch_shardings(batch, mesh)
    num_docs = jax.ShapeDtypeStruct(batch.num_docs.shape,
                                    batch.num_docs.dtype, sharding=nd_sh)
    args = (tuple(jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh)
                  for a, sh in zip(batch.arrays, arrays_sh)),
            tuple(jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh)
                  for s, sh in zip(batch.scalars, scalars_sh)),
            num_docs)
    jitted, _treedef, _spec, meta = fanout._batch_executor(batch, 10, mesh,
                                                           args)
    assert meta["collective_bytes"] > 0
    _compile(jitted, args)
