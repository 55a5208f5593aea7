#!/usr/bin/env python3
"""The control: one run of a cell as `run.py` makes it, and then the same
check with the reference's own answers, computed one precision step below
what the configuration states (`reference.py`, precision "low"), put in the
program's place for the very requests the run sampled. The control has to
come out as not correct; its numbers are the upper readings the limits are
set under. Not part of a benchmark run: the builder runs it on the chip at
the cell's own size, and `tests/` keeps it at a small size.

    python3 benchmark/control.py --workload <cell> --seed <n> --seconds <s>
"""

from __future__ import annotations

import json
import sys

import run


def control_checks(cell: dict, result: dict, seed: int) -> dict:
    records, splits = result["_window"]

    def low(shape, query, record, reference):
        return reference.render(shape, query, "low")
    return run.check(cell, records, splits, seed, answer=low)


def main(argv=None) -> int:
    kept = {}
    measure = run.measure

    def keeping(args, cell, *rest):
        kept["cell"], kept["seed"] = cell, args.seed
        kept["result"] = measure(args, cell, *rest)
        return kept["result"]
    run.measure = keeping
    code = run.main(argv)
    if code != 0:
        return code
    checks = control_checks(kept["cell"], kept["result"], kept["seed"])
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    print(json.dumps({"control_correct": correct, "control_checks": checks,
                      "program_checks": kept["result"]["checks"]}),
          flush=True)
    return 0 if not correct else 3      # a control that passes is a failure


if __name__ == "__main__":
    sys.exit(main())
