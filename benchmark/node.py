"""The node as a child process, and the HTTP client that drives it.

Copied from `chip_smoke.py::NodeProcess` (PR 21). The child is
`benchmark/node_main.py`, which is `python -m quickwit_tpu.cli run` with a
trace switch; it starts in its own process group with the environment the
benchmark was given, so JAX there picks the chip.
"""

from __future__ import annotations

import glob
import json
import os
import signal
import subprocess
import sys
import time
import urllib.error
import urllib.parse
import urllib.request

from data import ROOT, die_with_parent

# the node is on localhost: no proxy of the environment applies
LOCAL_HTTP = urllib.request.build_opener(urllib.request.ProxyHandler({}))
NODE_ENTRY = os.path.join(ROOT, "benchmark", "node_main.py")


class NodeFailure(Exception):
    """The node did not start, stop or answer as a run needs."""


class NodeProcess:
    started: list = []   # every NodeProcess ever started, for the reaper

    def __init__(self, config_path: str, env: dict, log_path: str,
                 trace_dir: str = None, entry: str = NODE_ENTRY):
        self.log_path = log_path
        self.trace_dir = trace_dir
        self._log = open(log_path, "wb")
        command = [sys.executable, entry, "--config", config_path]
        if trace_dir:
            command += ["--trace-dir", trace_dir]
        self.proc = subprocess.Popen(
            command, cwd=ROOT, env=env, stdout=self._log,
            stderr=subprocess.STDOUT, start_new_session=True,
            preexec_fn=self._in_child)
        NodeProcess.started.append(self)
        self.endpoint = None
        self.device = None
        self.rss_peak_bytes = None      # the node's own report as it stops

    @staticmethod
    def _in_child() -> None:
        # a shell that backgrounds the benchmark leaves SIGINT ignored, and
        # the node's orderly shutdown is its KeyboardInterrupt
        signal.signal(signal.SIGINT, signal.default_int_handler)
        die_with_parent()

    def wait_line(self, marker: str, timeout: float, count: int = 1) -> str:
        """The `count`-th complete log line holding `marker`."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            with open(self.log_path, "r", errors="replace") as fh:
                found = [line for line in fh
                         if marker in line and line.endswith("\n")]
            if len(found) >= count:
                return found[count - 1].strip()
            if self.proc.poll() is not None:
                raise NodeFailure(
                    f"the node exited with {self.proc.returncode} before "
                    f"printing {marker!r}:\n" + self.log_tail())
            time.sleep(0.1)
        raise NodeFailure(f"no {marker!r} line from the node within "
                          f"{timeout:.0f}s\n" + self.log_tail())

    def log_tail(self, lines: int = 30) -> str:
        with open(self.log_path, "r", errors="replace") as fh:
            return "".join(fh.readlines()[-lines:])

    def wait_ready(self) -> dict:
        """The start-up lines: the device as JAX reports it in the node,
        whether the native indexer loaded, and the endpoint."""
        line = self.wait_line(" devices: ", 300)
        report, _, native = line.partition(" devices: ")[2].rpartition(
            " native_indexer=")
        self.device = json.loads(report)
        self.native_indexer = native == "True"
        line = self.wait_line(" listening on ", 300)
        self.endpoint = line.rpartition("listening on ")[2]
        return self.device

    def post(self, path: str, body: bytes, timeout: float) -> bytes:
        """One POST; the reply's bytes. Raises NodeFailure on an HTTP error
        or a dead connection."""
        req = urllib.request.Request(self.endpoint + path, data=body,
                                     method="POST")
        try:
            with LOCAL_HTTP.open(req, timeout=timeout) as resp:
                return resp.read()
        except urllib.error.HTTPError as exc:
            raise NodeFailure(f"POST {path} -> HTTP {exc.code}: "
                              f"{exc.read()[:300]!r}") from exc
        except (urllib.error.URLError, OSError) as exc:
            raise NodeFailure(f"POST {path}: {exc}") from exc

    def metrics(self) -> dict:
        """/metrics as {series: value}; series keep their label text."""
        with LOCAL_HTTP.open(self.endpoint + "/metrics", timeout=60) as resp:
            text = resp.read().decode()
        values = {}
        for line in text.splitlines():
            if line and not line.startswith("#"):
                series, _, value = line.rpartition(" ")
                values[series] = float(value)
        return values

    def start_trace(self) -> None:
        open(self.trace_dir + ".start", "w").close()
        self.wait_line("trace started", 120)

    def stop_trace(self) -> tuple:
        """(the traced window's seconds by the node's clock, the trace
        file's path)."""
        open(self.trace_dir + ".stop", "w").close()
        line = self.wait_line("trace stopped window_s=", 240)
        files = glob.glob(os.path.join(self.trace_dir, "plugins", "profile",
                                       "*", "*.xplane.pb"))
        if len(files) != 1:
            raise NodeFailure(f"expected one trace file, found {files}")
        return float(line.rpartition("=")[2]), files[0]

    def stop(self) -> list:
        """SIGINT, the node's orderly shutdown; returns its per-device
        memory report (live, peak and limit bytes)."""
        self.proc.send_signal(signal.SIGINT)
        try:
            self.proc.wait(timeout=120)
        except subprocess.TimeoutExpired:
            raise NodeFailure("the node did not stop on SIGINT within "
                              "120s\n" + self.log_tail())
        finally:
            self._log.close()
        if self.proc.returncode != 0:
            raise NodeFailure(f"the node exited with {self.proc.returncode}"
                              "\n" + self.log_tail())
        line = self.wait_line(" stopped; device memory: ", 5)
        self.rss_peak_bytes = int(self.wait_line(
            "host rss peak bytes=", 5).rpartition("=")[2])
        return json.loads(line.rpartition("device memory: ")[2])


def reap(workers: list) -> list:
    """Kill whatever is left of every process the run started. Returns what
    was still alive."""
    for worker in workers:
        if worker.is_alive():
            worker.kill()
        worker.join(timeout=30)
    leftover = []
    for node in NodeProcess.started:
        if node.proc.poll() is None:
            leftover.append(f"node pid {node.proc.pid}")
        try:
            os.killpg(node.proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        try:
            node.proc.wait(timeout=30)
        except subprocess.TimeoutExpired:
            leftover.append(f"node pid {node.proc.pid} (unkillable)")
        if not node._log.closed:
            node._log.close()
    return leftover
