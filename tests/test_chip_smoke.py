"""chip_smoke.py without a chip: it must refuse, not run on the CPU.

The smoke's full flow is rehearsed by hand (see the verify skill); tier-1
keeps the one property a machine without an accelerator can show — the
script starts the node, sees a platform that is not the TPU, exits
non-zero with `"ok": false` as its last line, answers no query and leaves
no child behind. It runs in a copy of the program: the smoke deletes and
rebuilds `native/_build` and its own data directory, which other tests of
this checkout may be using."""

import json
import os
import shutil
import subprocess
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_refuses_to_run_without_an_accelerator(tmp_path):
    shutil.copy(os.path.join(REPO_ROOT, "chip_smoke.py"), tmp_path)
    shutil.copytree(os.path.join(REPO_ROOT, "quickwit_tpu"),
                    tmp_path / "quickwit_tpu",
                    ignore=shutil.ignore_patterns("__pycache__", "_build"))
    run = subprocess.run(
        [sys.executable, str(tmp_path / "chip_smoke.py")],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, cwd=tmp_path,
        capture_output=True, text=True, timeout=300)
    lines = run.stdout.strip().splitlines()
    assert run.returncode != 0, run.stdout + run.stderr
    result = json.loads(lines[-1])
    assert result["ok"] is False and "device" not in result, run.stdout
    assert "platform 'cpu'" in result["error"], run.stdout + run.stderr
    assert not any("== reference" in line for line in lines), run.stdout
    # every child was reaped: no process still has the copy as its
    # working directory (the node and the generator workers all did)
    leftovers = []
    for pid in filter(str.isdigit, os.listdir("/proc")):
        try:
            cwd = os.readlink(f"/proc/{pid}/cwd")
        except OSError:
            continue
        if cwd.startswith(str(tmp_path)):
            leftovers.append(pid)
    assert not leftovers
