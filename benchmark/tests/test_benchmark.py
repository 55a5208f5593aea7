"""The benchmark's own tests: the yardstick's arithmetic, that every name
resolves to its files, that new cells are files and entries only, that a run
refuses a machine without an accelerator, and that `correct` comes out false
for the lower-precision control and for an answer altered in the node.

    python -m pytest benchmark/tests -q

The last three drive a node on the CPU at 20k docs (about half a minute
each). Nothing here describes a topology or loads a TPU library.
"""

import json
import os
import shutil
import signal
import subprocess
import sys

import pytest

TESTS = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(TESTS)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import control
import data
import device_trace
import peaks
import run
import traffic

CELLS = ("hdfs10m.search-c8", "hdfs10m.aggs-c8", "hdfs40m.search-c8")


def manifest() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        return json.load(fh)


def test_trace_reduction_on_a_recorded_trace():
    """A slice of a trace recorded on the chip (PR 24): busy time is the
    union of the operations line, the idle share follows from the window,
    and the busiest operations come out in order."""
    with open(os.path.join(TESTS, "recorded_trace.json")) as fh:
        recorded = json.load(fh)
    (plane, lines), = recorded["planes"].items()
    events = lines[device_trace.OPS_LINE]
    reduced = device_trace.reduce(recorded["planes"], recorded["window_s"])
    assert reduced["chips"] == 1
    assert reduced["busy_s"] == pytest.approx(recorded["busy_s"], rel=1e-9)
    # never more than the plain sum, never less than the longest event
    assert max(e[2] for e in events) / 1e9 <= reduced["busy_s"] \
        <= sum(e[2] for e in events) / 1e9 + 1e-12
    idle = 100.0 * (1 - reduced["busy_s"] / reduced["window_s"])
    assert 0.0 < idle < 100.0
    top = reduced["device_ops"]
    assert top[0][0] == recorded["top_op"] and len(top) <= 10
    assert [s for _, s in top] == sorted((s for _, s in top), reverse=True)
    # overlapping and nested events count once
    assert device_trace.union_ns([["a", 0, 10], ["b", 5, 10], ["c", 6, 2],
                                  ["d", 30, 5]]) == 20
    assert device_trace.reduce({"/device:TPU:0": {device_trace.OPS_LINE: []}},
                               1.0) is None
    # operations traced past the stop stretch the span: never over 100 % busy
    late = device_trace.reduce(recorded["planes"], 0.1)
    assert late["busy_s"] <= late["window_s"] > 0.1


def test_rate_and_percentiles_over_the_whole_window():
    """The rate counts every request answered inside the window over the
    window's seconds; the percentiles are over every answered request."""
    records = [{"shape": "s", "ok": True, "t_send": i * 0.1,
                "t_done": i * 0.1 + (i + 1) / 1000.0,
                "latency_ms": float(i + 1)} for i in range(100)]
    records.append({"shape": "s", "ok": True, "t_send": 9.9, "t_done": 10.4,
                    "latency_ms": 500.0})     # answered after the close
    records.append({"shape": "s", "ok": False, "t_send": 1.0, "t_done": 1.1,
                    "latency_ms": 100.0})     # failed: in neither
    cell = {"mix": {"shape_files": {}}}
    got = run.end_to_end(run.Run(cell, records, (0.0, 10.0), {}, []),
                         10.0, 42.0)
    assert got["search_qps"] == 10.0
    assert got["search_p50_ms"] == 51.0
    assert got["search_p95_ms"] == pytest.approx(96.0)
    assert got["setup_s"] == 42.0


@pytest.mark.parametrize("mix_name", ["search-c8", "aggs-c8"])
def test_traffic_is_seeded_and_never_repeats(mix_name):
    mix = traffic.load_mix(mix_name)
    owners = traffic.client_shapes(mix)

    def draw(seed, stream=0, rounds=25):
        """What the clients get, each taking its shape's next in turn."""
        requests = traffic.RequestStream(mix, seed, stream)
        return [requests.take(shape) for _ in range(rounds)
                for shape in owners]
    first, again, other = draw(2**31 + 5), draw(2**31 + 5), draw(7)
    assert first == again and first != other
    assert first != draw(2**31 + 5, stream=1)
    keys = [(r["shape"], r["lo"], r["hi"]) for r in first]
    assert len(set(keys)) == len(keys)
    # every seed sends the same set of sizes, in another order
    def sizes(rows):
        return sorted((r["shape"], r["hi"] - r["lo"]) for r in rows)
    assert sizes(first) == sizes(other)
    block = [r for r in first if r["index"] < mix["block"]]
    for shape, weight in mix["shapes"].items():
        assert sum(r["shape"] == shape for r in block) == weight * mix["block"]
    spec = mix["range"]
    for r in first:
        assert spec["width_min_s"] <= r["hi"] - r["lo"] <= spec["width_max_s"]
        assert 0 <= r["lo"] - spec["origin_s"] <= spec["lo_max_s"]
    # the clients are shared out by the weights; one that owns a shape takes
    # the stream's requests of that shape in the stream's order
    assert len(owners) == mix["clients"]
    assert {s: owners.count(s) / len(owners) for s in mix["shapes"]} \
        == mix["shapes"]
    with pytest.raises(ValueError):
        traffic.client_shapes(dict(mix, clients=3))
    stream = traffic.RequestStream(mix, 2**31 + 5, 0)
    shape = next(iter(mix["shapes"]))
    mine = [stream.take(shape) for _ in range(30)]
    assert [r["index"] for r in mine] == sorted(r["index"] for r in mine)
    assert mine == sorted((r for r in first if r["shape"] == shape),
                          key=lambda r: r["index"])[:30]


def test_every_name_in_the_manifest_resolves():
    spec = manifest()
    assert spec["command"] == ["python3", "benchmark/run.py"]
    ends = {m["name"] for m in spec["end_to_end"]}
    for name in CELLS:
        cell = run.load_cell(name)
        assert cell["config_file"]["name"] == cell["config"]
        assert set(cell["mix"]["shape_files"]) == set(cell["mix"]["shapes"])
        # a metric that lists its cells is theirs alone (`search_qps`)
        assert {m["name"] for m in cell["end_to_end"]} == {
            m["name"] for m in spec["end_to_end"]
            if name in m.get("workloads", [name])} >= {"setup_s"}
        assert cell["per_layer"]
    for metric in spec["per_layer"]:
        assert metric["moves"] in ends
        with open(os.path.join(BENCH, "metrics", metric["name"] + ".json")) as fh:
            reader = json.load(fh)
        module = run.load_module("readers", reader["reader"])
        assert callable(module.read)
        if "function" in reader.get("args", {}):
            assert callable(run.load_module(
                "rooflines", reader["args"]["function"]).request_bytes)
    for config in spec["configs"]:
        with open(os.path.join(ROOT, config["file"])) as fh:
            held = json.load(fh)
        assert held["source"] == config["source"]
        assert held["reduced"] == config["reduced"]
    # a device kind resolves to its peaks, and an unknown one to none
    assert peaks.peak("TPU v5 lite", "hbm_bytes_per_s") == 819e9
    with pytest.raises(peaks.UnknownDevice):
        peaks.peak("cpu", "hbm_bytes_per_s")


def test_the_split_indexes_the_documents_the_reference_knows(tmp_path,
                                                             monkeypatch):
    """The reference answers from the documents `data.py` drew; the split
    the node serves is the program's writers over those documents. Postings,
    lengths and columns worked out from the documents equal the split's."""
    import reference
    config = run.load_cell(CELLS[0])["config_file"]
    config.update(docs_per_split=20000, name="test-20k")
    path = str(tmp_path / "idx" / "hdfs-5.split")
    monkeypatch.setattr(data, "die_with_parent", lambda: None)  # no worker
    data.generate_split(path, config, 5, 0, str(tmp_path))
    with open(path + ".json") as fh:
        record = json.load(fh)
    split = data.SplitFile(path)
    corpus = run.reference_over(config, [record]).corpora[0]
    n = corpus.num_docs
    assert n == split.num_docs == 20000
    assert (split.array("col.timestamp.values")[:n] == corpus.ts * 10**6).all()
    assert (split.array("col.tenant_id.values")[:n] == corpus.tenant).all()
    assert (split.array("col.severity_text.ordinals")[:n] == corpus.sev).all()
    assert split.strings("col.severity_text.dict") == corpus.severities
    assert (split.array("inv.body.fieldnorm")[:n] == corpus.body_len).all()
    assert split.footer["fields"]["body"]["avg_len"] == corpus.avg_len("body")
    for number in (0, 3, 7, 4321):
        lo = int(split.array("inv.body.terms.post_off")[number])
        df = int(split.array("inv.body.terms.df")[number])
        ids = split.array("inv.body.postings.ids")[lo:lo + df]
        mine = corpus.postings("body", f"term{number:06d}")
        assert sorted(ids) == list(mine)
        assert (split.array("inv.body.postings.tfs")[lo:lo + df] == 1).all()
    # a writer that draws other documents than the configuration says is
    # refused, not indexed
    with pytest.raises(ValueError):
        data.DrawnTokens(reference.np.ones(40, int), 1.5).zipf(1.5, 60)


def copy_of_the_benchmark(tmp_path, with_program: bool):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    if with_program:
        shutil.copytree(os.path.join(ROOT, "quickwit_tpu"),
                        tmp_path / "quickwit_tpu",
                        ignore=shutil.ignore_patterns("__pycache__"))


def test_new_cells_are_new_files_and_entries(tmp_path):
    """A configuration, a shape, a traffic mix, a cell and a per-layer
    metric added as new files plus entries in BENCHMARK.json: the harness
    finds them, and no file that was there is touched."""
    copy_of_the_benchmark(tmp_path, with_program=False)
    bench = tmp_path / "benchmark"
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}
    config = json.loads((bench / "configs" / "hdfs-logs-10m.json").read_text())
    config.update(name="hdfs-logs-80m", num_splits=8, reduced=[])
    (bench / "configs" / "hdfs-logs-80m.json").write_text(json.dumps(config))
    (bench / "shapes" / "term_top10.json").write_text(json.dumps({
        "name": "term_top10",
        "must": [["severity_text", "INFO"]], "should": [], "range": True,
        "size": 10, "sort": None, "aggs": None}))
    (bench / "traffic" / "top10-c4.json").write_text(json.dumps({
        "name": "top10-c4", "shapes": {"term_top10": 1.0}, "clients": 4, "block": 10, "check_sample": 8,
        "range": {"origin_s": 1600000000, "lo_max_s": 86400,
                  "width_min_s": 3600, "width_max_s": 7200}}))
    (bench / "metrics" / "p99_ms.term_top10.json").write_text(json.dumps({
        "reader": "client_percentile",
        "args": {"shape": "term_top10", "percent": 99}}))
    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "hdfs-logs-80m", "source": config["source"],
                            "file": "benchmark/configs/hdfs-logs-80m.json",
                            "reduced": [], "why": "twice the index"})
    spec["workloads"].append({"name": "hdfs80m.top10-c4",
                              "config": "hdfs-logs-80m", "traffic": "top10-c4",
                              "chips": 1, "why": "test"})
    spec["per_layer"].append({
        "name": "p99_ms.term_top10", "unit": "ms", "better": "lower",
        "source": "host_clock", "layer": "REST front end",
        "moves": "search_p95_ms", "workloads": ["hdfs80m.top10-c4"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    probe = (
        "import json, sys; sys.path.insert(0, 'benchmark'); import run\n"
        "cell = run.load_cell('hdfs80m.top10-c4')\n"
        "records = [dict(shape='term_top10', ok=True, latency_ms=float(i))"
        " for i in range(101)]\n"
        "got = run.per_layer(run.Run(cell, records, (0, 1), {}, []))\n"
        "stream = run.traffic.RequestStream(cell['mix'], 1, 0)\n"
        "print(json.dumps([cell['config_file']['num_splits'],"
        " cell['mix']['clients'], sorted(m['name'] for m in"
        " cell['per_layer']), got.get('p99_ms.term_top10'),"
        " stream.take('term_top10')['shape']]))\n")
    done = subprocess.run([sys.executable, "-c", probe], cwd=tmp_path,
                          capture_output=True, text=True, timeout=120)
    assert done.returncode == 0, done.stderr
    splits, clients, metrics, p99, shape = json.loads(
        done.stdout.splitlines()[-1])
    assert (splits, clients, p99, shape) == (8, 4, 99.0, "term_top10")
    assert "p99_ms.term_top10" in metrics
    assert "p50_ms.term_newest10" not in metrics      # another cell's own
    assert "plan_build_ms" in metrics                 # every cell's
    # a metric of the cells that report the rate, which this one does not:
    # `search_qps` lists its cells (PERF.md, section 2)
    assert "device_idle_pct" not in metrics
    assert all(p.read_bytes() == was for p, was in before.items())


def result_line(stdout: str):
    lines = [l for l in stdout.splitlines() if l.startswith('{"correct"')]
    return json.loads(lines[-1]) if lines else None


def test_refuses_to_measure_without_an_accelerator(tmp_path):
    """On a copy at 20k docs: the node reports the CPU, the run exits
    non-zero, prints no result line and leaves no child behind. In a
    directory with the benchmark alone it fails at once."""
    copy_of_the_benchmark(tmp_path, with_program=True)
    path = tmp_path / "benchmark" / "configs" / "hdfs-logs-10m.json"
    config = json.loads(path.read_text())
    config["docs_per_split"] = 20000
    path.write_text(json.dumps(config))
    command = [sys.executable, "benchmark/run.py", "--workload", CELLS[0],
               "--seed", "3000000001", "--seconds", "2", "--trace", "0"]
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "BENCH_RUN": "7"}
    done = subprocess.run(command, cwd=tmp_path, env=env,
                          capture_output=True, text=True, timeout=300)
    assert done.returncode != 0, done.stdout + done.stderr
    assert "platform 'cpu'" in done.stdout, done.stdout + done.stderr
    assert result_line(done.stdout) is None and "[window]" not in done.stdout
    leftovers = []
    for pid in filter(str.isdigit, os.listdir("/proc")):
        try:
            cwd = os.readlink(f"/proc/{pid}/cwd")
        except OSError:
            continue
        if cwd.startswith(str(tmp_path)):
            leftovers.append(pid)
    assert not leftovers
    shutil.rmtree(tmp_path / "quickwit_tpu")
    shutil.rmtree(tmp_path / ".bench_cache", ignore_errors=True)
    alone = subprocess.run(command, cwd=tmp_path, env=env,
                           capture_output=True, text=True, timeout=120)
    assert alone.returncode != 0 and result_line(alone.stdout) is None


@pytest.fixture
def small(monkeypatch, tmp_path):
    """The harness past its look for a chip, at 20k docs, with its caches
    in the test's own directory."""
    load_cell = run.load_cell

    def small_cell(name):
        cell = load_cell(name)
        cell["config_file"].update(docs_per_split=20000, name="test-20k")
        cell["mix"]["check_sample"] = 30
        return cell
    monkeypatch.setattr(run, "load_cell", small_cell)
    monkeypatch.setattr(run, "REQUIRED_PLATFORM", "cpu")
    monkeypatch.setattr(run, "WARMUP_STRETCH_S", 1.0)
    monkeypatch.setattr(run, "WRITE_SETTLE_MAX_S", 0.0)
    monkeypatch.setattr(data, "CACHE_DIR", str(tmp_path / "cache"))
    monkeypatch.setattr(run, "RUN_DIR", str(tmp_path / "cache" / "run"))
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    handler = signal.getsignal(signal.SIGTERM)     # run.main installs its own
    yield
    signal.signal(signal.SIGTERM, handler)


@pytest.mark.parametrize("cell", CELLS)
def test_the_lower_precision_control_is_not_correct(small, capfd, cell):
    """The program's own answers pass; the reference computed in bfloat16
    (scores) and float32 (timestamps), put in the program's place for the
    same requests, fails one of the cell's numbers."""
    code = control.main(["--workload", cell, "--seed", "2147483659",
                         "--seconds", "2", "--trace", "0"])
    out = capfd.readouterr().out
    assert code == 0, out
    result = result_line(out)
    assert result["correct"] is True, out
    # every run says what host memory it took: the generator's workers (one
    # split: one; four, all making their bodies: one at a time), the node
    # and itself, and the most that was alive at one time
    host, = [line for line in out.splitlines() if line.startswith("[host]")]
    assert "(1 at once)" in host and " node 0 MB" not in host \
        and " run 0 MB" not in host
    assert result["device"]["host_rss_peak_bytes"] > 100e6
    assert list(result)[-1] == "checks"
    verdict = json.loads(out.splitlines()[-1])
    assert verdict["control_correct"] is False
    failed = [name for name, c in verdict["control_checks"].items()
              if c["value"] > c["limit"]]
    assert "wrong_answers" in failed
    if cell.endswith(".search-c8"):
        assert "score_rel_err" in failed
        assert verdict["control_checks"]["score_rel_err"]["value"] > 100 * \
            verdict["program_checks"]["score_rel_err"]["value"]


def test_an_answer_altered_in_the_node_is_not_correct(small, capfd):
    """The rest of a run with the timed path broken underneath: a node that
    reports one hit too many in every fifth response."""
    code = run.main(["--workload", CELLS[0], "--seed", "2147483659",
                     "--seconds", "2", "--trace", "0"],
                    node_entry=os.path.join(TESTS, "broken_node.py"))
    out = capfd.readouterr().out
    assert code == 0, out
    result = result_line(out)
    assert result["correct"] is False
    assert result["checks"]["wrong_answers"]["value"] > 0
    assert result["failed"] == 0
