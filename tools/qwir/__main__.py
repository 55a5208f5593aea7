"""CLI: `python -m tools.qwir audit|self-test`.

Exit codes follow qwlint: 0 clean, 1 findings (or self-test failures),
2 usage/internal error. `audit --write-manifest` regenerates the
compile-cache closure certificate (tools/qwir/manifest.json) — do that
only when a cache-key/jaxpr change is intentional, and update the pinned
program count in tests/test_qwir.py in the same commit.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path


def _setup_platform() -> None:
    # Mirror tests/conftest.py: force the CPU backend with 8 virtual
    # devices BEFORE jax initializes, so fused-mesh programs trace the
    # same way under the auditor as under the tier-1 suite.
    import jax
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 8)


def _cmd_audit(args) -> int:
    from .audit import default_manifest_path, run_audit
    manifest_path = Path(args.manifest) if args.manifest else \
        default_manifest_path()
    report = run_audit(manifest_path=manifest_path,
                       update_manifest=args.write_manifest)
    if args.sarif:
        from tools.sarif import write_sarif
        write_sarif(Path(args.sarif), tool="qwir",
                    rules={r: doc for r, doc in report.to_json()["rules"].items()},
                    results=[{"ruleId": f.rule, "id": f.fid,
                              "message": f.message, "site": f.site,
                              "suppressed": f.suppressed,
                              "justification": f.justification}
                             for f in report.findings])
    if args.json:
        json.dump(report.to_json(), sys.stdout, indent=1, sort_keys=True)
        sys.stdout.write("\n")
    else:
        print(f"qwir: audited {report.program_count} lowered programs "
              f"({len(report.suppressed)} certified suppressions)")
        for f in report.unsuppressed:
            print(f"  {f.fid}\n    {f.message}")
        if report.ok:
            print("qwir: compile-cache closure certified; "
                  "no f64/transfer/collective/HBM findings")
    return 0 if report.ok else 1


def _cmd_self_test(args) -> int:
    from .selftest import run_self_test
    failures = run_self_test()
    if args.json:
        json.dump({"tool": "qwir-self-test", "ok": not failures,
                   "failures": failures}, sys.stdout, indent=1)
        sys.stdout.write("\n")
    elif failures:
        print("qwir self-test FAILED:")
        for line in failures:
            print(f"  {line}")
    else:
        print("qwir self-test: every planted defect caught by its rule")
    return 0 if not failures else 1


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m tools.qwir",
        description="jaxpr-level static auditor for the lowered leaf hot "
                    "path (rules R1-R5; see docs/static-analysis.md)")
    sub = parser.add_subparsers(dest="cmd", required=True)
    p_audit = sub.add_parser("audit", help="audit the lowered plan corpus")
    p_audit.add_argument("--json", action="store_true",
                         help="emit the full report as JSON")
    p_audit.add_argument("--sarif", metavar="FILE",
                         help="also write a SARIF 2.1.0 log to FILE")
    p_audit.add_argument("--manifest", metavar="PATH",
                         help="closure manifest path (default: "
                              "tools/qwir/manifest.json)")
    p_audit.add_argument("--write-manifest", action="store_true",
                         help="regenerate the closure certificate from "
                              "the live corpus before checking")
    p_test = sub.add_parser("self-test",
                            help="verify each rule catches its planted "
                                 "defect")
    p_test.add_argument("--json", action="store_true")
    args = parser.parse_args(argv)
    _setup_platform()
    try:
        if args.cmd == "audit":
            return _cmd_audit(args)
        return _cmd_self_test(args)
    except BrokenPipeError:
        return 2


if __name__ == "__main__":
    sys.exit(main())
