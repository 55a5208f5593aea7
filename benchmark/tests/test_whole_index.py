"""Tests of what lets the harness take a configuration of several splits
(ISSUE 28): generator workers one at a time, so that a run's host memory
does not grow with `num_splits`; each worker's peak and bytes in its split's
record; one body shared by the configurations that draw the same one;
the cache kept by bytes; a one-split configuration's split byte for byte what
it was; the reference's merge over several corpora against a brute-force
pass over the concatenated documents; and every cell of BENCHMARK.json
resolving to files that load.

    python -m pytest benchmark/tests/test_whole_index.py -q

The generator tests spawn real workers at 5,000 docs a split (about 3 s and
120 MB each). Nothing here describes a topology or loads a TPU library.
"""

import hashlib
import json
import os
import sys
import threading

import numpy as np
import pytest

TESTS = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(TESTS)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import data
import reference
import run
import traffic

with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
    MANIFEST = json.load(fh)

# sha256 of the split `generate_split` writes at the parent of ISSUE 28
# (commit 316f97d) for hdfs-logs-10m cut to 5,000 docs, seed 2147483700. It
# moves with the draw and with the program's array writers; a benchmark PR
# that accepts such a move writes the new one here.
ONE_SPLIT_SEED = 2147483700
ONE_SPLIT_SHA256 = ("bf49daad95dec4fbdcec5914a0c09f1d"
                    "acc9452013f32b32d21282888c626460")


def small_config(cell: str, name: str) -> dict:
    config = run.load_cell(cell)["config_file"]
    config.update(docs_per_split=5000, name=name)
    return config


def children_alive() -> int:
    """This process's spawned workers that still run, read from /proc:
    asking `multiprocessing` would reap one under the generator's `join`."""
    alive = 0
    for pid in filter(str.isdigit, os.listdir("/proc")):
        try:
            with open(f"/proc/{pid}/stat") as fh:
                state, parent = fh.read().rpartition(")")[2].split()[:2]
            with open(f"/proc/{pid}/cmdline") as fh:
                worker = "spawn_main" in fh.read()
        except OSError:
            continue
        alive += worker and int(parent) == os.getpid() and state != "Z"
    return alive


class ChildrenWatch:
    """The most children this process had alive at one time."""

    def __enter__(self):
        self.most, self._stop = 0, threading.Event()
        self._thread = threading.Thread(target=self._poll, daemon=True)
        self._thread.start()
        return self

    def _poll(self):
        while not self._stop.wait(0.01):
            self.most = max(self.most, children_alive())

    def __exit__(self, *exc):
        self._stop.set()
        self._thread.join(timeout=10)


def test_four_splits_are_made_one_at_a_time_and_say_what_they_took(
        tmp_path, monkeypatch):
    """Never two workers alive, whether they make their body or find it;
    every record carries its worker's peak and what it wrote; a seed found
    again starts no worker."""
    monkeypatch.setattr(data, "CACHE_DIR", str(tmp_path / "cache"))
    config = small_config("hdfs40m.search-c8", "test-5k-four")
    assert config["num_splits"] == 4
    lines, workers = [], []

    made = []
    for seed in (2147483800, 2147483900):
        with ChildrenWatch() as watch:
            made.append(data.ensure_splits(config, seed, workers,
                                           lines.append))
        assert watch.most == 1
        assert data.generator_peak(made[-1]) == max(
            s["ru_maxrss_bytes"] for s in made[-1])
    first, second = made
    assert len(workers) == 8

    for splits, with_body in ((first, True), (second, False)):
        for split in splits:
            assert not split["cached"]
            assert split["ru_maxrss_bytes"] > 50e6      # an interpreter's
            on_disk = os.path.getsize(split["path"]) + os.path.getsize(
                split["docs"])
            body = split["body_tokens"][:-len(".tokens.npy")]
            if with_body:
                on_disk += sum(os.path.getsize(body + end) for end in
                               (".tokens.npy", ".npz", ".json"))
            assert split["wrote_bytes"] == on_disk
    assert sum("bytes written under .bench_cache" in line
               for line in lines) == 2
    assert all("worker's peak" in line for line in lines
               if "made from its seed" in line)
    assert not [w for w in workers if w.is_alive()]

    # the bodies differ by split, and are the same for every seed
    assert len({s["body_tokens"] for s in first}) == 4
    assert [s["body_tokens"] for s in first] == \
        [s["body_tokens"] for s in second]

    # found again: no worker, no generator reading for this run; a record
    # of an older harness, without the readings, is as good
    with open(second[0]["path"] + ".json") as fh:
        old = json.load(fh)
    del old["ru_maxrss_bytes"], old["wrote_bytes"]
    with open(second[0]["path"] + ".json", "w") as fh:
        json.dump(old, fh)
    again = data.ensure_splits(config, 2147483900, workers, lines.append)
    assert len(workers) == 8 and all(s["cached"] for s in again)
    assert data.generator_peak(again) is None
    assert all("ru_maxrss_bytes" in s for s in again[1:])


def test_a_one_split_configuration_gets_the_split_it_always_got(tmp_path,
                                                                monkeypatch):
    """One worker, and the bytes `generate_split` gave at the parent."""
    monkeypatch.setattr(data, "CACHE_DIR", str(tmp_path / "cache"))
    config = small_config("hdfs10m.search-c8", "test-5k-one")
    assert config["num_splits"] == 1
    workers = []
    with ChildrenWatch() as watch:
        split, = data.ensure_splits(config, ONE_SPLIT_SEED, workers,
                                    lambda line: None)
    assert watch.most == 1 and len(workers) == 1
    with open(split["path"], "rb") as fh:
        assert hashlib.sha256(fh.read()).hexdigest() == ONE_SPLIT_SHA256
    # its body is split 0's of the four-split configuration: made once
    four = small_config("hdfs40m.search-c8", "test-5k-four")
    assert data.split_jobs(four, 7)[0][4] == os.path.dirname(
        split["body_tokens"])
    assert data.body_path("", four, 0) == data.body_path("", config, 0)
    assert data.body_path("", four, 1) != data.body_path("", config, 0)


def test_the_cache_keeps_its_newest_seeds_by_bytes(tmp_path, monkeypatch):
    monkeypatch.setattr(data, "CACHE_DIR", str(tmp_path))
    monkeypatch.setattr(data, "KEEP_BYTES", 250)
    root = tmp_path / "some-config"
    for age, (seed, size) in enumerate([(11, 100), (12, 100), (13, 100),
                                        (14, 100), (15, 400)]):
        (root / str(seed) / "indexes").mkdir(parents=True)
        (root / str(seed) / "indexes" / "a.split").write_bytes(b"x" * size)
        os.utime(root / str(seed), (1000 + age, 1000 + age))
    (root / "notes.txt").write_bytes(b"y" * 1000)       # kept as it is
    data.evict("some-config")
    # the newest two always, however large; then none fits
    assert sorted(os.listdir(root)) == ["14", "15", "notes.txt"]
    os.utime(root / "15", (900, 900))                   # now the oldest
    for seed in (16, 17):
        (root / str(seed)).mkdir()
        (root / str(seed) / "a.split").write_bytes(b"x" * 60)
        os.utime(root / str(seed), (2000 + seed, 2000 + seed))
    data.evict("some-config")
    # 60 + 60 + 100 fit into 250, the 400 behind them does not
    assert sorted(os.listdir(root)) == ["14", "16", "17", "notes.txt"]


# --------------------------------------------------------------------------
# the reference over several corpora


SEVERITIES = ["DEBUG", "ERROR", "INFO", "WARN"]
ORIGIN = 1_600_000_000


def write_corpus(folder, split_id: str, rng, docs: int = 400):
    """A corpus with many equal timestamps and few terms, so that ties and
    every term of the shapes occur."""
    ts = np.sort(ORIGIN + 3600 * rng.integers(0, 96, size=docs))
    kept = {"ts": ts.astype(np.int64),
            "tenant": rng.integers(0, 10, size=docs).astype(np.int64),
            "sev": rng.integers(0, 4, size=docs).astype(np.int32)}
    tokens = rng.integers(0, 12, size=(docs, 6)).astype(np.int32)
    np.savez(folder / f"{split_id}.docs.npz", **kept)
    np.save(folder / f"{split_id}.tokens.npy", tokens)
    return kept, tokens


def corpus_of(folder, split_id: str) -> reference.Corpus:
    return reference.Corpus(split_id, str(folder / f"{split_id}.docs.npz"),
                            str(folder / f"{split_id}.tokens.npy"),
                            SEVERITIES)


def brute_force(shape: dict, query: dict, splits: dict) -> dict:
    """One pass, document by document, over all splits' documents together:
    what matches, its BM25 score with its own split's statistics (tantivy's
    formula, tf 1), then one sort of all of them by (key descending, split
    id, doc id) and plain counting for the buckets."""
    stats = {}
    for split_id, (kept, tokens) in splits.items():
        sets = [set(row) for row in tokens.tolist()]
        lengths = [len(s) for s in sets]
        stats[split_id] = (sets, lengths, sum(lengths) / len(lengths))

    def holds(split_id, doc, field, text):
        if field == "severity_text":
            return SEVERITIES[splits[split_id][0]["sev"][doc]] == text
        return int(text[len("term"):]) in stats[split_id][0][doc]

    frequencies = {}

    def score_of(split_id, doc, field, text):
        n = len(splits[split_id][0]["ts"])
        if (split_id, field, text) not in frequencies:
            frequencies[split_id, field, text] = sum(
                holds(split_id, d, field, text) for d in range(n))
        df = frequencies[split_id, field, text]
        length, average = ((1, 1.0) if field == "severity_text" else
                           (stats[split_id][1][doc], stats[split_id][2]))
        idf = np.log(1.0 + (n - df + 0.5) / (df + 0.5))
        return idf * 2.2 / (1.0 + 1.2 * (0.25 + 0.75 * length / average))

    rows = []
    for split_id, (kept, _) in sorted(splits.items()):
        for doc in range(len(kept["ts"])):
            must = [holds(split_id, doc, *t) for t in query["must"]]
            should = [holds(split_id, doc, *t) for t in query["should"]]
            lo, hi = query["range"]
            if not (all(must) and (must or any(should) or not should)
                    and lo <= kept["ts"][doc] < hi):
                continue
            score = sum(score_of(split_id, doc, *t) for t, has in
                        zip(query["must"] + query["should"], must + should)
                        if has)
            rows.append((split_id, doc, int(kept["ts"][doc]), score,
                         int(kept["sev"][doc])))
    key = 2 if shape.get("sort") else 3
    top = sorted(rows, key=lambda r: (-r[key], r[0], r[1]))[:shape["size"]]
    out = {"num_hits": len(rows),
           "top": [(f"{r[0]}:{r[1]}", r[key], r[2]) for r in top],
           "aggs": {}}
    for name, agg in (shape.get("aggs") or {}).items():
        counts: dict = {}
        for row in rows:
            bucket = (row[2] // 86_400 * 86_400_000
                      if "date_histogram" in agg else SEVERITIES[row[4]])
            counts[bucket] = counts.get(bucket, 0) + 1
        out["aggs"][name] = counts
    return out


@pytest.mark.parametrize("shape_name", ["term_newest10",
                                        "flagship_top10_aggs",
                                        "bool_range_top100"])
def test_the_reference_merges_four_corpora_as_one_pass_over_all_documents(
        tmp_path, shape_name):
    """Counts and buckets add; the top-k is that of all documents together,
    ties by split id and then doc id. Two of the four corpora hold the same
    documents, so every one of their hits ties across splits."""
    rng = np.random.default_rng(28)
    splits = {"hdfs-c": write_corpus(tmp_path, "hdfs-c", rng),
              "hdfs-a": write_corpus(tmp_path, "hdfs-a", rng),
              "hdfs-d": write_corpus(tmp_path, "hdfs-d", rng)}
    kept, tokens = splits["hdfs-a"]
    np.savez(tmp_path / "hdfs-b.docs.npz", **kept)
    np.save(tmp_path / "hdfs-b.tokens.npy", tokens)
    splits["hdfs-b"] = (kept, tokens)
    ref = reference.Reference([corpus_of(tmp_path, split_id)
                               for split_id in splits])    # in any order
    assert [c.split_id for c in ref.corpora] == sorted(splits)
    shape = traffic.load_json("shapes", f"{shape_name}.json")
    tied_across_splits = 0
    for lo, width in ((ORIGIN, 96 * 3600), (ORIGIN + 7 * 3600, 30 * 3600),
                      (ORIGIN + 50 * 3600, 3600), (ORIGIN - 10, 5)):
        query = traffic.shape_query(shape, lo, lo + width)
        got = ref.answer(shape, query)
        want = brute_force(shape, query, splits)
        assert got["num_hits"] == want["num_hits"]
        assert got["aggs"] == want["aggs"]
        assert [hit for hit, _, _ in got["top"]] == \
            [hit for hit, _, _ in want["top"]]
        assert [value for _, value, _ in got["top"]] == pytest.approx(
            [value for _, value, _ in want["top"]], rel=1e-12)
        assert [ts for _, _, ts in got["top"]] == \
            [ts for _, _, ts in want["top"]]
        ids = [hit for hit, _, _ in got["top"]]
        tied_across_splits += sum(
            f"hdfs-b:{hit.partition(':')[2]}" in ids
            for hit in ids if hit.startswith("hdfs-a:"))
        # the served form of the same answer compares as correct
        found = reference.compare(shape, query,
                                  ref.render(shape, query, "full"), ref, 2e-5)
        assert found["wrong"] == []
    assert tied_across_splits > 0


# --------------------------------------------------------------------------
# every cell of the manifest


@pytest.mark.parametrize("cell_name",
                         [cell["name"] for cell in MANIFEST["workloads"]])
def test_a_cell_resolves_to_files_that_load(cell_name):
    cell = run.load_cell(cell_name)
    entry, = (c for c in MANIFEST["configs"] if c["name"] == cell["config"])
    config = cell["config_file"]
    assert config["name"] == entry["name"] and cell["chips"] == config["chips"]
    assert config["source"] == entry["source"] and len(entry["source"]) <= 200
    assert config["reduced"] == entry["reduced"]
    # `reduced` names the keys cut from the published deployment, all of
    # them and no other
    assert sorted(config["reduced"]) == sorted(
        key for key, value in config["published"].items()
        if key in config and config[key] != value)
    assert config["published"]["num_docs"] == (
        config["published"]["num_splits"]
        * config["published"]["docs_per_split"])
    assert set(config["limits"]) >= {"unanswered", "wrong_answers"}
    assert len(cell["why"]) <= 200
    mix = cell["mix"]
    assert set(mix["shape_files"]) == set(mix["shapes"])
    assert len(traffic.client_shapes(mix)) == mix["clients"]
    assert traffic.RequestStream(mix, 2 ** 31 + 7, 0).take(
        next(iter(mix["shapes"])))["shape"] in mix["shapes"]
    cells = {c["name"] for c in MANIFEST["workloads"]}
    # an end-to-end metric that lists its cells is another's where it does
    # not list this one; a per-layer metric moves one this cell reports
    ends = {m["name"] for m in cell["end_to_end"]}
    assert ends == {m["name"] for m in MANIFEST["end_to_end"]
                    if cell_name in m.get("workloads", [cell_name])}
    assert "setup_s" in ends and len(ends) >= 2
    assert cell["per_layer"]
    for metric in cell["per_layer"]:
        assert set(metric.get("workloads", [])) <= cells
        if "workloads" in metric:
            assert metric["moves"] in ends
        assert metric["moves"] in {m["name"] for m in MANIFEST["end_to_end"]}
        with open(os.path.join(BENCH, "metrics",
                               metric["name"] + ".json")) as fh:
            spec = json.load(fh)
        assert callable(run.load_module("readers", spec["reader"]).read)
        for shape in [spec.get("args", {}).get("shape")]:
            assert shape is None or shape in mix["shapes"]
