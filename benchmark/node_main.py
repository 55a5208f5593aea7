"""The node under test: `python -m quickwit_tpu.cli --config <file> run`,
unchanged, plus a way for the harness to take a `jax.profiler` trace inside
the process that holds the chip.

    python benchmark/node_main.py --config node.yaml [--trace-dir DIR]

As it ends it prints its own peak resident size (`host rss peak bytes=<n>`,
`ru_maxrss`): the harness cannot read a child's peak while it lives, since
the chip machine's `/proc/<pid>/status` carries no `VmHWM`.

With `--trace-dir`, a control thread watches for the files `<DIR>.start` and
`<DIR>.stop`, which the harness creates: the first starts a trace into DIR,
the second stops it; each prints one line the harness waits for. (`cmd_run`'s
main thread only sleeps; a thread does not depend on which thread the kernel
hands a signal to.)
"""

from __future__ import annotations

import os
import resource
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
POLL_S = 0.02


def trace_on_request(trace_dir: str) -> None:
    def wait_for(path: str) -> None:
        while not os.path.exists(path):
            time.sleep(POLL_S)

    def control() -> None:
        wait_for(trace_dir + ".start")
        import jax
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0     # the device and XLA's own host
        options.host_tracer_level = 1       # events; no Python frames
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        started = time.monotonic()
        print("trace started", flush=True)
        wait_for(trace_dir + ".stop")
        window_s = time.monotonic() - started
        jax.profiler.stop_trace()
        print(f"trace stopped window_s={window_s!r}", flush=True)

    threading.Thread(target=control, name="bench-trace", daemon=True).start()


def main(argv: list) -> int:
    sys.path.insert(0, ROOT)
    if "--trace-dir" in argv:
        at = argv.index("--trace-dir")
        trace_on_request(argv[at + 1])
        argv = argv[:at] + argv[at + 2:]
    from quickwit_tpu.cli import main as cli_main
    try:
        return cli_main(argv + ["run"])
    finally:
        print("host rss peak bytes="      # Linux counts ru_maxrss in KiB
              f"{1024 * resource.getrusage(resource.RUSAGE_SELF).ru_maxrss}",
              flush=True)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
