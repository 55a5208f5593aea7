"""Planted-defect fixtures for the qwir rules: each defect from the
audit's threat model is planted in a toy program and must be caught by
exactly its own rule, with a finding id that is stable across runs (no
line numbers, no object identities). If a rule stops firing here it has
silently stopped protecting the real corpus."""

from __future__ import annotations

import pytest

from tools.qwir import ir
from tools.qwir.audit import check_closure, describe_programs, \
    manifest_from_programs
from tools.qwir.rules import (check_collectives, check_f64, check_hbm,
                              check_transfers)
from tools.qwir.selftest import (planted_bad_collective, planted_f64_upcast,
                                 planted_hbm_blowup, planted_host_round_trip,
                                 planted_mesh_axis_leak,
                                 planted_unbounded_bucket, run_self_test)


def _live(findings):
    return [f for f in findings if not f.suppressed]


def test_r2_catches_f64_upcast_into_corpus_scale_topk():
    spec = planted_f64_upcast()
    hits = _live(check_f64(spec))
    assert hits, "planted f64 upcast not caught"
    assert all(f.rule == "R2" for f in hits)
    # stable id: rule:program:site, identical across independent traces
    again = _live(check_f64(planted_f64_upcast()))
    assert sorted(f.fid for f in hits) == sorted(f.fid for f in again)


def test_r2_respects_certified_exact_fallback_sites():
    # the real corpus exercises exact_topk/exact_topk_2key: those f64
    # sorts must come back SUPPRESSED with the registry justification
    from tools.qwir.corpus import build_corpus  # cheap relative to value
    specs = [s for s in build_corpus() if s.name == "single/v3/term/k10"]
    findings = check_f64(specs[0])
    assert findings and all(f.suppressed for f in findings)
    assert all(f.justification.strip() for f in findings)


def test_r3_catches_mid_kernel_host_round_trip():
    spec = planted_host_round_trip()
    hits = _live(check_transfers(spec))
    assert hits and all(f.rule == "R3" for f in hits)
    assert any("pure_callback" in f.site for f in hits)


def test_r4_catches_collective_over_undeclared_axis():
    spec = planted_bad_collective()
    hits = _live(check_collectives(spec))
    assert hits and all(f.rule == "R4" for f in hits)
    assert any("docs" in f.site for f in hits)


def test_r4_accepts_declared_axes():
    spec = planted_bad_collective()
    spec.mesh_axes = ("splits", "docs")
    assert not _live(check_collectives(spec))


def test_r4_catches_axis_leak_through_real_mesh_program():
    """The production mesh_batch_fn traced over a misnamed mesh: every
    collective in the root merge binds the undeclared axis and R4 must
    flag it; renaming the declaration to match clears it (proving the
    finding keys on the axis name, not on the program shape)."""
    spec = planted_mesh_axis_leak()
    hits = _live(check_collectives(spec))
    assert hits and all(f.rule == "R4" for f in hits)
    assert any("rows" in f.site for f in hits)
    spec.mesh_axes = ("rows", "docs")
    assert not _live(check_collectives(spec))


def test_r5_catches_hbm_liveness_blowup():
    spec = planted_hbm_blowup()
    hits = _live(check_hbm(spec))
    assert hits and all(f.rule == "R5" for f in hits)
    sites = {f.site for f in hits}
    assert "peak:budget" in sites
    assert "peak:quantum" in sites  # 256 MiB temp > one DRR quantum


def test_r1_catches_unbounded_padding_bucket():
    toys = planted_unbounded_bucket()
    programs = describe_programs(toys)
    pinned = manifest_from_programs(
        {k: v for k, v in sorted(programs.items())[:2]})
    hits = check_closure(programs, pinned)
    assert any(f.site == "closure:unpinned" for f in hits), (
        "a padding bucket outside the pinned closure must fail R1")


def test_r1_catches_jaxpr_drift():
    toys = planted_unbounded_bucket()[:2]
    programs = describe_programs(toys)
    pinned = manifest_from_programs(programs)
    drifted = {k: dict(v) for k, v in programs.items()}
    name = sorted(drifted)[0]
    drifted[name]["jaxpr"] = "0" * 32
    hits = check_closure(drifted, pinned)
    assert [f.site for f in hits] == ["closure:jaxpr"]
    assert hits[0].program == name


def test_r1_catches_cache_key_drift():
    toys = planted_unbounded_bucket()[:2]
    programs = describe_programs(toys)
    pinned = manifest_from_programs(programs)
    drifted = {k: dict(v) for k, v in programs.items()}
    name = sorted(drifted)[0]
    drifted[name]["cache_key"] = "f" * 32
    hits = check_closure(drifted, pinned)
    assert [f.site for f in hits] == ["closure:cache_key"]


def test_r1_catches_cache_key_drift_in_stacked_program():
    """Key drift planted through the REAL stacked query-group programs:
    if `executor.stacked_program_cache_key` stops mirroring what the dispatch path actually caches on — e.g. the
    [Q] validity mask leaking into the key, which would force a
    recompile whenever a rider is shed — R1 must flag exactly the
    drifted stacked entry, not its neighbours."""
    from tools.qwir.corpus import build_corpus
    stacked = [s for s in build_corpus()
               if s.name.startswith(("stacked/", "stacked_chunked/"))]
    assert len(stacked) == 2, "expected the two stacked corpus entries"
    programs = describe_programs(stacked)
    pinned = manifest_from_programs(programs)
    drifted = {k: dict(v) for k, v in programs.items()}
    target = "stacked/v3/term/q2/k10"
    drifted[target]["cache_key"] = "f" * 32
    hits = check_closure(drifted, pinned)
    assert [f.site for f in hits] == ["closure:cache_key"]
    assert hits[0].program == target


def test_liveness_peak_counts_the_planted_temp():
    spec = planted_hbm_blowup()
    # the planted 2048x16384 f64 pairwise temp alone is 256 MiB
    assert spec.peak.peak_bytes >= 2048 * 16384 * 8
    assert spec.peak.largest_bytes >= 2048 * 16384 * 8


def test_self_test_is_green():
    assert run_self_test() == []


def test_cli_self_test_exit_code():
    from tools.qwir.__main__ import main
    assert main(["self-test"]) == 0
