"""Tier-1 gate: the qwir audit over the live plan corpus must be clean
and the compile-cache closure certificate must hold exactly.

EXPECTED_PROGRAM_COUNT is pinned on purpose: any change that grows or
shrinks the set of distinct compiled programs (a new padding bucket, a
new plan variant, a dispatch path dying) must consciously update this
number AND regenerate tools/qwir/manifest.json in the same commit —
that is the review speed bump. ROADMAP items 1 (mesh root merge) and 2
(query batching) are expected to trip it when they land.
"""

from __future__ import annotations

import json
from pathlib import Path

import pytest

from tools.qwir import ir
from tools.qwir.audit import (audit_specs, check_closure, default_manifest_path,
                              describe_programs, load_manifest,
                              manifest_from_programs, run_audit)

EXPECTED_PROGRAM_COUNT = 25


@pytest.fixture(scope="module")
def corpus():
    from tools.qwir.corpus import build_corpus
    return build_corpus()


@pytest.fixture(scope="module")
def report(corpus):
    return audit_specs(corpus)


def test_manifest_is_checked_in():
    assert default_manifest_path().exists(), (
        "tools/qwir/manifest.json missing — run "
        "`python -m tools.qwir audit --write-manifest`")


def test_program_count_is_pinned(corpus):
    manifest = load_manifest(default_manifest_path())
    assert len(corpus) == EXPECTED_PROGRAM_COUNT, (
        f"corpus lowers {len(corpus)} programs, pinned "
        f"{EXPECTED_PROGRAM_COUNT} — a compile-cache entry appeared or "
        "vanished; update EXPECTED_PROGRAM_COUNT and the manifest "
        "deliberately")
    assert manifest["program_count"] == EXPECTED_PROGRAM_COUNT


def test_compile_cache_closure_certificate(report):
    manifest = load_manifest(default_manifest_path())
    drift = check_closure(report.programs, manifest)
    assert not drift, (
        "compile-cache closure drifted from the checked-in certificate:\n"
        + "\n".join(f"  {f.fid}: {f.message}" for f in drift))


def test_audit_clean_modulo_certified_suppressions(report):
    assert report.ok, (
        "qwir found unsuppressed findings:\n"
        + "\n".join(f"  {f.fid}: {f.message}" for f in report.unsuppressed))


def test_every_suppression_carries_a_justification(report):
    bare = [f for f in report.suppressed if not f.justification.strip()]
    assert not bare, (
        "suppressed findings must carry the QWIR_CERTIFIED_F64 "
        "justification text:\n" + "\n".join(f.fid for f in bare))
    # and the f64 exact-fallback certifications actually get exercised:
    # a registry nothing hits is dead weight or a broken attribution
    assert any(f.rule == "R2" for f in report.suppressed)


def test_cache_key_aliasing_is_sound(corpus):
    # programs MAY share a compile-cache key — that is a cache hit (the
    # v1 and v3 term plans lower identically) — but then they must trace
    # to the same jaxpr, or the cache hands one plan the other's
    # executable
    by_key: dict[str, set[str]] = {}
    for spec in corpus:
        by_key.setdefault(spec.cache_key_digest, set()).add(
            ir.jaxpr_digest(spec.closed))
    unsound = {k: v for k, v in by_key.items() if len(v) > 1}
    assert not unsound
    # and the corpus genuinely exercises an alias, so this check is live
    assert len(by_key) < len(corpus)


def test_aliasing_check_catches_key_collisions():
    from tools.qwir.audit import check_aliasing
    programs = {
        "a": {"cache_key": "k", "jaxpr": "x"},
        "b": {"cache_key": "k", "jaxpr": "y"},
        "c": {"cache_key": "k2", "jaxpr": "x"},
    }
    hits = check_aliasing(programs)
    assert len(hits) == 1 and hits[0].site.startswith("closure:alias:")
    assert not check_aliasing({"a": {"cache_key": "k", "jaxpr": "x"},
                               "b": {"cache_key": "k", "jaxpr": "x"}})


def test_digests_are_deterministic(corpus):
    # re-digesting the SAME trace must be stable (no object identities
    # leaking into the hash); retracing determinism is covered by the
    # closure certificate itself matching across audit runs
    for spec in corpus:
        assert ir.jaxpr_digest(spec.closed) == ir.jaxpr_digest(spec.closed)


def test_manifest_round_trips(report, tmp_path):
    path = tmp_path / "manifest.json"
    manifest = manifest_from_programs(report.programs)
    path.write_text(json.dumps(manifest) + "\n")
    assert load_manifest(path) == manifest
    assert not check_closure(report.programs, manifest)


def test_run_audit_flags_missing_and_stale_manifests(tmp_path):
    missing = check_closure({}, None)
    assert [f.site for f in missing] == ["manifest:missing"]
    report = run_audit(manifest_path=tmp_path / "none.json")
    assert any(f.site == "manifest:missing" for f in report.unsuppressed)


def test_cli_exit_codes(tmp_path, capsys):
    from tools.qwir.__main__ import main
    assert main(["audit"]) == 0
    assert main(["audit", "--manifest", str(tmp_path / "nope.json")]) == 1
    capsys.readouterr()
    with pytest.raises(SystemExit) as exc:
        main(["no-such-command"])
    assert exc.value.code == 2
    capsys.readouterr()
