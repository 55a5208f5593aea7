"""Planted-defect self-test: proves each rule still catches its bug class.

A static auditor that silently stops finding things is worse than none —
this module builds toy programs each containing exactly one planted
defect (an f64 upcast feeding a corpus-scale top_k, a mid-kernel host
callback, an unbounded padding-bucket enumeration breaking cache closure,
a collective over an undeclared mesh axis, an HBM liveness blowup) and
asserts the matching rule reports exactly that finding, with a stable id.
Run via `python -m tools.qwir self-test`; the fixture suite
(tests/test_qwir_rules.py) drives the same functions per rule.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import numpy as np

from . import ir
from .audit import check_closure, manifest_from_programs
from .rules import (
    check_collectives, check_f64, check_hbm, check_transfers,
)


@dataclass
class ToySpec:
    name: str
    closed: Any
    doc_lanes: int = 1024
    num_docs_padded: int = 1024
    mesh_axes: tuple = ("splits", "docs")
    kind: str = "toy"
    cache_key: tuple = ()
    peak: Any = None

    @property
    def cache_key_digest(self) -> str:
        import hashlib
        return hashlib.blake2b(repr(self.cache_key).encode(),
                               digest_size=16).hexdigest()

    def __post_init__(self):
        if self.peak is None:
            self.peak = ir.liveness_peak(self.closed)


def _trace(fn, *shapes):
    import quickwit_tpu  # noqa: F401 — enables x64, matching production tracing
    import jax
    args = [jax.ShapeDtypeStruct(s, d) for s, d in shapes]
    return jax.make_jaxpr(fn)(*args)


# --- planted defects ---------------------------------------------------------

def planted_f64_upcast() -> ToySpec:
    """An innocent-looking f32 score lane promoted to f64 and full-sorted
    at corpus scale — the exact shape of the PR 8 ~290ms top_k bug."""
    import jax
    import jax.numpy as jnp

    def leaf(scores):
        keys = scores.astype(jnp.float64)      # doc-scale f64 promotion
        return jax.lax.top_k(keys, 10)         # f64-keyed corpus-scale sort

    return ToySpec(name="planted/f64_upcast",
                   closed=_trace(leaf, ((16384,), np.float32)),
                   doc_lanes=16384, num_docs_padded=16384)


def planted_host_round_trip() -> ToySpec:
    """A mid-kernel host callback — the traced analogue of calling
    jax.device_get inside the fused dispatch (which cannot trace at all);
    any callback primitive is the same per-query host sync."""
    import jax
    import jax.numpy as jnp

    def leaf(mask):
        count = jax.pure_callback(
            lambda m: np.asarray(m.sum(), dtype=np.int64),
            jax.ShapeDtypeStruct((), np.int64), mask)
        return count + jnp.int64(1)

    return ToySpec(name="planted/host_round_trip",
                   closed=_trace(leaf, ((1024,), np.bool_)))


def planted_bad_collective() -> ToySpec:
    """A psum over a mesh axis the program never declared: the spec says
    the merge runs over ("splits",) only, but the body reduces over
    "docs" — silently wrong replica groups on a real 2D mesh."""
    import jax
    import numpy as np_
    from jax.sharding import Mesh, PartitionSpec as P

    devs = np_.asarray(jax.devices()[:2]).reshape(1, 2)
    mesh = Mesh(devs, ("splits", "docs"))

    def merge(x):
        return jax.lax.psum(x, "docs")

    fn = jax.shard_map(merge, mesh=mesh, in_specs=P(None, "docs"),
                       out_specs=P(None, None), check_vma=False)
    return ToySpec(name="planted/bad_collective",
                   closed=_trace(fn, ((4, 2), np.float32)),
                   mesh_axes=("splits",))


def planted_mesh_axis_leak() -> ToySpec:
    """An undeclared-axis psum through the PRODUCTION collective program
    shape: `fanout.mesh_batch_fn` traced over a mesh whose split axis is
    misnamed ("rows", "docs"). Every collective in the lowered root merge
    — the threshold exchange, the all_gather candidate exchange, the
    psum agg/count reductions — then binds "rows", which the ProgramSpec
    never declared. Catching this through the real builder (not a toy
    body) is what keeps R4 load-bearing for the mesh root-merge programs
    the corpus now pins."""
    import jax
    import numpy as np_
    from jax.sharding import Mesh

    import quickwit_tpu  # noqa: F401 — enables x64, matching production
    from quickwit_tpu.parallel import fanout
    from quickwit_tpu.query.ast import Term
    from quickwit_tpu.search import SearchRequest

    from .corpus import _build_reader, _docs, _mapper

    mapper = _mapper()
    readers = [_build_reader(mapper, _docs(64, seed=11), f"r4mesh{i}.split")
               for i in range(2)]
    request = SearchRequest(index_ids=["t"],
                            query_ast=Term("body", "alpha"), max_hits=5)
    batch = fanout.build_batch(request, mapper, readers, ["a", "b"])
    bad_mesh = Mesh(np_.asarray(jax.devices()[:2]).reshape(2, 1),
                    ("rows", "docs"))
    return ToySpec(name="planted/mesh_axis_leak",
                   closed=fanout.abstract_mesh_batch_program(batch, 5,
                                                             bad_mesh),
                   doc_lanes=batch.num_docs_padded * 2,
                   num_docs_padded=batch.num_docs_padded)


def planted_hbm_blowup() -> ToySpec:
    """A [docs, docs]-ish pairwise f64 temp: 2048×16384 f64 = 256 MiB live
    in one buffer — four DRR admission quanta for one query's scratch."""
    import jax.numpy as jnp

    def leaf(scores):
        pair = scores[:, None] * jnp.ones((1, 16384), jnp.float64)
        return pair.sum()

    return ToySpec(name="planted/hbm_blowup",
                   closed=_trace(leaf, ((2048,), np.float64)),
                   doc_lanes=2048, num_docs_padded=2048)


def planted_unbounded_bucket() -> list[ToySpec]:
    """A padding-bucket enumeration that grew past the pinned closure:
    per-request padded lengths mint per-request cache keys. The manifest
    pins two buckets; the 'corpus' now lowers three."""
    import jax.numpy as jnp

    def leaf(x):
        return jnp.sum(x * 2.0)

    return [ToySpec(name=f"planted/bucket/p{n}",
                    closed=_trace(leaf, ((n,), np.float32)),
                    doc_lanes=n, num_docs_padded=n,
                    cache_key=(("toy", n), False))
            for n in (1024, 2048, 4096)]


# --- the self-test -----------------------------------------------------------

def run_self_test() -> list[str]:
    """Returns a list of failure strings; empty means every planted defect
    was caught by exactly its own rule with a stable finding id."""
    failures: list[str] = []

    def expect(label, findings, rule, site_fragment):
        live = [f for f in findings if not f.suppressed]
        if not live:
            failures.append(f"{label}: planted defect NOT caught")
            return
        for f in live:
            if f.rule != rule:
                failures.append(
                    f"{label}: wrong rule {f.rule} (wanted {rule}): "
                    f"{f.message}")
            if site_fragment not in f.fid:
                failures.append(
                    f"{label}: unstable finding id {f.fid!r} "
                    f"(wanted fragment {site_fragment!r})")

    spec = planted_f64_upcast()
    expect("R2/f64_upcast", check_f64(spec), "R2", "planted/f64_upcast")
    if check_transfers(spec) or check_collectives(spec):
        failures.append("R2/f64_upcast: tripped unrelated rules")

    spec = planted_host_round_trip()
    expect("R3/host_round_trip", check_transfers(spec), "R3",
           "pure_callback")
    if check_f64(spec) or check_collectives(spec) or check_hbm(spec):
        failures.append("R3/host_round_trip: tripped unrelated rules")

    spec = planted_bad_collective()
    expect("R4/bad_collective", check_collectives(spec), "R4", "docs")

    spec = planted_mesh_axis_leak()
    expect("R4/mesh_axis_leak", check_collectives(spec), "R4", "rows")
    if check_transfers(spec):
        failures.append("R4/mesh_axis_leak: tripped unrelated rules")

    spec = planted_hbm_blowup()
    expect("R5/hbm_blowup", check_hbm(spec), "R5", "peak:")
    if check_transfers(spec) or check_collectives(spec):
        failures.append("R5/hbm_blowup: tripped unrelated rules")

    toys = planted_unbounded_bucket()
    from .audit import describe_programs
    for t in toys:
        t.peak = ir.liveness_peak(t.closed)
    programs = describe_programs(toys)
    pinned = manifest_from_programs(
        {k: v for k, v in list(sorted(programs.items()))[:2]})
    r1 = check_closure(programs, pinned)
    expect("R1/unbounded_bucket", r1, "R1", "closure:unpinned")

    # and the negative: a clean toy must stay clean
    import jax.numpy as jnp
    clean = ToySpec(name="planted/clean",
                    closed=_trace(lambda x: jnp.sum(x),
                                  ((1024,), np.float32)))
    for rule in (check_f64, check_transfers, check_collectives, check_hbm):
        extra = [f for f in rule(clean) if not f.suppressed]
        if extra:
            failures.append(
                f"clean program tripped {extra[0].rule}: {extra[0].message}")
    return failures
