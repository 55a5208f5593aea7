#!/usr/bin/env python3
"""chip_smoke.py — the served search path, once, on a TPU.

The quickest proof that the system still starts on the chip: a node started
the way users start it (`python -m quickwit_tpu.cli --config node.yaml run`)
loads hdfs-logs data at the upstream tutorial's split size, answers the
benchmark's query shapes over REST and the ES endpoint, and every answer is
compared with a plain numpy reference over the same data.

    python chip_smoke.py              one chip   (what the driver runs)
    python chip_smoke.py --chips 4    the mesh path on a four-chip host

One chip, three node processes one after the other (a chip belongs to one
process at a time, and this script itself never initialises a JAX backend:
it pins itself to the CPU and starts the node with the environment as it
found it):

  A  device check, then 100k documents through POST /ingest -> WAL ->
     indexing pipeline -> publish, searched and checked; meanwhile a worker
     generates the 10M-doc split from the seed.
  B  the 10M-doc split, published through the metastore protocol: every
     query shape cold then warm, then eight concurrent stackable queries.
  C  a restarted node repeats one query of each shape: the persistent
     compile cache must serve them.

Four chips, one node process: four equal splits, the flagship and the
percentile aggregation through REST, compared with the reference over all
four splits; the node's counters must show the collective program ran and
every device held a shard. No other phase.

Timings printed here are smoke timings: they say the path ran, not how fast
it is. The last line of stdout is the result,
`{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}`;
any failed phase, wrong answer, non-TPU platform or leftover child exits
non-zero with `"ok": false`.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import dataclasses
import json
import multiprocessing
import os
import shutil
import signal
import subprocess
import sys
import time
import traceback
import urllib.error
import urllib.parse
import urllib.request

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(HERE, "chiprun_out", "chip_smoke")  # copied back
DATA_DIR = os.path.join(HERE, ".chip_smoke")               # big; stays behind

REQUIRED_PLATFORM = "tpu"
# upstream hdfs-logs tutorial (BASELINE.md): 10M-doc splits, 40M docs
SPLIT_DOCS = 10_000_000
INGEST_DOCS = 100_000
MESH_SPLITS = 4
CONCURRENT_QUERIES = 8
CONCURRENT_ROUNDS = 5
# every request names its own deadline: a cold query compiles for longer
# (85 s for the bool+range program) than the root's 30 s default allows
REQUEST_TIMEOUT_S = 900

T0 = 1_600_000_000          # the generator's start_ts
DAY = 86_400
SPAN_DAYS = 7               # the generator's span_seconds
K1, B = 1.2, 0.75           # BM25, as tantivy and ops/bm25.py fix them
SCORE_RTOL = 2e-5           # device scores are f32 sums of up to 3 terms

AGGS = {"over_time": {"date_histogram": {"field": "timestamp",
                                         "fixed_interval": "1d"}},
        "severities": {"terms": {"field": "severity_text", "size": 10}}}
# the c5 shape (percentiles, agg-only). Over tenant_id, not timestamp: the
# sketch's range ends near 1.1e13 and hdfs timestamps in micros (1.6e15)
# all clip into its last bucket, where they would check nothing
PERCENTILES = {"latency": {"percentiles": {"field": "tenant_id",
                                           "percents": [50, 95, 99]}}}
SKETCH_ACCURACY = 0.01      # DDSketch relative accuracy (ops/aggs.py)


# the node is on localhost: no proxy of the environment applies
LOCAL_HTTP = urllib.request.build_opener(urllib.request.ProxyHandler({}))


class SmokeFailure(Exception):
    """A phase failed or an answer was wrong."""


def say(text: str) -> None:
    print(text, flush=True)


def expect(condition: bool, what: str) -> None:
    if not condition:
        raise SmokeFailure(what)


# --------------------------------------------------------------------------
# the plain reference: numpy over the generator's own arrays


class Corpus:
    """One split as the reference sees it: per-doc timestamp seconds,
    tenant ids and severity ordinals, plus `postings(field, term) ->
    (doc_ids, tfs)`, `norms(field)` and `avg_len(field)` for BM25."""

    def __init__(self, split_id, ts, tenant, sev, severities, body_postings,
                 body_norms, body_avg_len):
        self.split_id = split_id
        self.ts = ts
        self.tenant = tenant
        self.sev = sev
        self.severities = list(severities)
        self._body_postings = body_postings
        self._body_norms = body_norms
        self._body_avg_len = body_avg_len
        self.num_docs = len(ts)

    @classmethod
    def from_split(cls, split_id: str, reader) -> "Corpus":
        """Read back the arrays `synthetic_hdfs_split` wrote."""
        n = reader.num_docs
        post_off = reader.array("inv.body.terms.post_off")
        df = reader.array("inv.body.terms.df")
        ids = reader.array("inv.body.postings.ids")
        tfs = reader.array("inv.body.postings.tfs")

        def body_postings(term: str):
            k = int(term[len("term"):])
            lo, hi = int(post_off[k]), int(post_off[k]) + int(df[k])
            return np.asarray(ids[lo:hi], np.int64), np.asarray(tfs[lo:hi])

        return cls(
            split_id,
            np.asarray(reader.array("col.timestamp.values")[:n]) // 10**6,
            np.asarray(reader.array("col.tenant_id.values")[:n]),
            np.asarray(reader.column_ordinals("severity_text")[:n]),
            reader.column_dict("severity_text"), body_postings,
            np.asarray(reader.array("inv.body.fieldnorm")[:n]),
            float(reader.field_meta("body")["avg_len"]))

    @classmethod
    def from_docs(cls, split_id: str, ts, tenant, sev, severities,
                  body_terms) -> "Corpus":
        """From the raw arrays of documents sent through ingest:
        `body_terms` is the [n, tokens] matrix of body term numbers."""
        n, tokens = body_terms.shape

        def body_postings(term: str):
            k = int(term[len("term"):])
            tf = (body_terms == k).sum(axis=1)
            docs = np.nonzero(tf)[0]
            return docs.astype(np.int64), tf[docs]

        return cls(split_id, ts, tenant, sev, severities, body_postings,
                   np.full(n, tokens, np.int32), float(tokens))

    def postings(self, field: str, term: str):
        if field == "severity_text":
            docs = np.nonzero(self.sev == self.severities.index(term))[0]
            return docs.astype(np.int64), np.ones(len(docs), np.int32)
        return self._body_postings(term)

    def norms(self, field: str):
        if field == "severity_text":
            return np.ones(self.num_docs, np.int32)   # raw tokenizer
        return self._body_norms

    def avg_len(self, field: str) -> float:
        return 1.0 if field == "severity_text" else self._body_avg_len


def evaluate(corpus: Corpus, query: dict):
    """(match mask, BM25 scores) of one split for a query description
    `{"must": [(field, term)], "should": [...], "range": (lo_s, hi_s),
    "boost": x}`: must terms are required and score, should terms score
    where present (and select when there is no must), the half-open
    timestamp range filters without scoring."""
    n = corpus.num_docs
    scores = np.zeros(n, np.float64)
    mask = None

    def term(field, text):
        docs, tfs = corpus.postings(field, text)
        has = np.zeros(n, bool)
        has[docs] = True
        if len(docs):
            idf = np.log(1.0 + (n - len(docs) + 0.5) / (len(docs) + 0.5))
            tf = tfs.astype(np.float64)
            norm = corpus.norms(field)[docs] / corpus.avg_len(field)
            scores[docs] += (query.get("boost", 1.0) * idf * (K1 + 1.0) * tf
                             / (tf + K1 * (1.0 - B + B * norm)))
        return has

    for field, text in query.get("must", ()):
        has = term(field, text)
        mask = has if mask is None else mask & has
    any_should = np.zeros(n, bool)
    for field, text in query.get("should", ()):
        any_should |= term(field, text)
    if mask is None:
        mask = any_should if query.get("should") else np.ones(n, bool)
    if query.get("range"):
        lo, hi = query["range"]
        mask = mask & (corpus.ts >= lo) & (corpus.ts < hi)
    return mask, np.where(mask, scores, 0.0)


class Reference:
    """Answers over all splits of an index, merged the way the root does:
    counts add, hits order by (key desc, split id asc, doc id asc)."""

    def __init__(self, corpora: list):
        self.corpora = sorted(corpora, key=lambda c: c.split_id)

    def answer(self, query: dict) -> dict:
        masks, scores = zip(*(evaluate(c, query) for c in self.corpora))
        ts = np.concatenate([c.ts[m] for c, m in zip(self.corpora, masks)])
        sev = np.concatenate([c.sev[m] for c, m in zip(self.corpora, masks)])
        days, day_counts = np.unique(ts // DAY, return_counts=True)
        return {
            "masks": masks, "scores": scores, "num_hits": int(len(ts)),
            # ES buckets align to multiples of the interval since the epoch
            "over_time": {int(day) * DAY * 1000: int(count)
                          for day, count in zip(days, day_counts)},
            "severities": {self.corpora[0].severities[o]: int(count)
                           for o, count in enumerate(np.bincount(sev))
                           if count},
            "tenants_sorted": np.sort(np.concatenate(
                [c.tenant[m] for c, m in zip(self.corpora, masks)])),
        }

    def top_by(self, answer: dict, key: str, k: int) -> list:
        """[(hit id, key value)] of the k best hits by "score" or "ts",
        descending, ties broken by (split id, doc id) ascending."""
        rows = []
        for c, mask, score in zip(self.corpora, answer["masks"],
                                  answer["scores"]):
            docs = np.nonzero(mask)[0]
            values = score[docs] if key == "score" else c.ts[docs]
            best = np.lexsort((docs, -values))[:k]
            rows += [(-float(values[i]), c.split_id, int(docs[i]))
                     for i in best]
        return [(f"{split}:{doc}", -neg) for neg, split, doc
                in sorted(rows)[:k]]


def check_counts(name: str, got_hits: int, got_aggs, want: dict,
                 aggs: bool) -> None:
    expect(got_hits == want["num_hits"],
           f"{name}: num_hits {got_hits} != reference {want['num_hits']}")
    if not aggs:
        return
    for agg in ("over_time", "severities"):
        got = {bucket["key"]: bucket["doc_count"]
               for bucket in got_aggs[agg]["buckets"] if bucket["doc_count"]}
        got = {(int(key) if agg == "over_time" else key): count
               for key, count in got.items()}
        expect(got == want[agg],
               f"{name}: {agg} buckets {got} != reference {want[agg]}")


def check_scored_hits(name: str, hits: list, reference: Reference,
                      want: dict, k: int) -> None:
    """ES hits against the numpy BM25: every returned doc matches and
    carries its reference score, the score list equals the reference's
    top-k, and every doc strictly above the k-th score is present (docs
    tying at the k-th score may legitimately differ)."""
    top = reference.top_by(want, "score", k)
    expect(len(hits) == len(top),
           f"{name}: {len(hits)} hits, reference has {len(top)}")
    by_split = {c.split_id: i for i, c in enumerate(reference.corpora)}
    got_scores = []
    for hit in hits:
        split, _, doc = hit["_id"].rpartition(":")
        i, doc = by_split[split], int(doc)
        expect(bool(want["masks"][i][doc]),
               f"{name}: hit {hit['_id']} does not match the query")
        ref_score = float(want["scores"][i][doc])
        expect(abs(hit["_score"] - ref_score) <= SCORE_RTOL * ref_score,
               f"{name}: hit {hit['_id']} score {hit['_score']} != "
               f"reference {ref_score}")
        got_scores.append(hit["_score"])
    want_scores = np.array([score for _, score in top])
    expect(np.allclose(got_scores, want_scores, rtol=SCORE_RTOL, atol=0),
           f"{name}: scores {got_scores} != reference {want_scores}")
    kth = want_scores[-1] if len(top) else 0.0
    above = {hit_id for hit_id, score in top
             if score > kth * (1 + 2 * SCORE_RTOL)}
    expect(above <= {hit["_id"] for hit in hits},
           f"{name}: docs above the k-th score are missing: "
           f"{above - {hit['_id'] for hit in hits}}")


def check_sorted_hits(name: str, hits: list, reference: Reference,
                      want: dict, k: int) -> None:
    """ES hits of a timestamp-desc sort: doc ids exactly."""
    top = reference.top_by(want, "ts", k)
    got = [hit["_id"] for hit in hits]
    expect(got == [hit_id for hit_id, _ in top],
           f"{name}: sorted ids {got} != reference {top}")
    got_ts = [hit["sort"][0] for hit in hits]
    expect(got_ts == [int(ts) * 1000 for _, ts in top],
           f"{name}: sort values {got_ts} != reference {top}")


def check_percentiles(name: str, got: dict, want: dict) -> None:
    """Percentiles come from a mergeable sketch that reports the item of
    0-based rank floor(q * (n - 1)) within its relative accuracy, and
    zero as zero."""
    values = want["tenants_sorted"]
    for percent, value in got["values"].items():
        exact = float(values[int(np.floor(float(percent) / 100.0
                                          * (len(values) - 1)))])
        expect(abs(value - exact) <= SKETCH_ACCURACY * exact,
               f"{name}: p{percent} {value} != reference {exact} within "
               f"{SKETCH_ACCURACY:.0%}")


# --------------------------------------------------------------------------
# data, made from the seed


def die_with_parent() -> None:
    """Linux: have the kernel kill this process when its parent dies, so a
    killed smoke run leaves nothing behind (PR_SET_PDEATHSIG)."""
    import ctypes
    ctypes.CDLL(None, use_errno=True).prctl(1, int(signal.SIGKILL))


def generate_split(path: str, num_docs: int, seed: int) -> None:
    """Worker: one synthetic hdfs split written into the index's storage,
    and beside it what its SplitMetadata needs."""
    die_with_parent()
    from quickwit_tpu.index.synthetic import synthetic_hdfs_split
    started = time.monotonic()
    data = synthetic_hdfs_split(num_docs, seed=seed, store_docs=True)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "wb") as fh:
        fh.write(data)
    with open(path + ".json", "w") as fh:
        json.dump({"path": path, "num_docs": num_docs, "bytes": len(data),
                   "seconds": round(time.monotonic() - started, 1)}, fh)


class SplitWorkers:
    """Generator workers, one spawned CPU process per split, so that data
    is made while the node starts and a failed run can stop them."""

    started: list = []   # every worker process ever started, for the reaper

    def __init__(self, jobs: list):
        ctx = multiprocessing.get_context("spawn")
        self.jobs = jobs
        self.procs = [ctx.Process(target=generate_split, args=job)
                      for job in jobs]
        for proc in self.procs:
            proc.start()
        SplitWorkers.started += self.procs

    def wait(self) -> list:
        splits = []
        for proc, (path, _num_docs, _seed) in zip(self.procs, self.jobs):
            proc.join()
            expect(proc.exitcode == 0,
                   f"the generator of {path} exited with {proc.exitcode}")
            with open(path + ".json") as fh:
                splits.append(json.load(fh))
            say(f"[data] {os.path.basename(path)}: {splits[-1]['num_docs']} "
                f"docs, {splits[-1]['bytes']} bytes, made from its seed in "
                f"{splits[-1]['seconds']}s")
        return splits


def ingest_documents(num_docs: int, seed: int):
    """(ndjson lines, ts, sev, body term matrix) of the documents that
    travel the ingest path: the generator's shapes (100k-term Zipf body
    vocabulary, 20 tokens per doc, the same severities and time span)."""
    from quickwit_tpu.index import synthetic
    rng = np.random.RandomState(seed)
    ts = np.sort(rng.randint(0, SPAN_DAYS * DAY, size=num_docs)) + T0
    tenants = rng.randint(0, 10, size=num_docs)
    sev = rng.choice(len(synthetic.SEVERITIES), size=num_docs,
                     p=synthetic._SEVERITY_P)
    body = np.minimum(
        rng.zipf(1.5, size=(num_docs, synthetic._BODY_TOKENS_PER_DOC)) - 1,
        synthetic._BODY_VOCAB_SIZE - 1)
    lines = [json.dumps({
        "timestamp": int(ts[i]), "tenant_id": int(tenants[i]),
        "severity_text": synthetic.SEVERITIES[sev[i]],
        "body": " ".join(map(synthetic.body_term, body[i]))})
        for i in range(num_docs)]
    return lines, ts, tenants, sev, body


def publish_splits(config: dict, index_id: str, splits: list) -> list:
    """Stage and publish generated splits through the metastore protocol
    (no node is running). Returns the Corpus of each."""
    from quickwit_tpu.common.uri import Uri
    from quickwit_tpu.index.reader import SplitReader
    from quickwit_tpu.index.synthetic import HDFS_MAPPER
    from quickwit_tpu.metastore.file_backed import FileBackedMetastore
    from quickwit_tpu.models.split_metadata import SplitMetadata
    from quickwit_tpu.serve.node import IndexService
    from quickwit_tpu.storage.base import StorageResolver
    from quickwit_tpu.storage.local import LocalFileStorage
    resolver = StorageResolver.default()
    metastore = FileBackedMetastore(resolver.resolve(config["metastore_uri"]))
    metadata = IndexService(
        metastore, resolver, config["default_index_root_uri"]).create_index(
        {"index_id": index_id, "doc_mapping": HDFS_MAPPER.to_dict()})
    storage = LocalFileStorage(Uri.parse(metadata.index_config.index_uri))
    corpora, staged = [], []
    for split in splits:
        split_id = os.path.basename(split["path"])[:-len(".split")]
        reader = SplitReader(storage, f"{split_id}.split")
        lo, hi = reader.footer.time_range
        staged.append(SplitMetadata(
            split_id=split_id, index_uid=metadata.index_uid,
            num_docs=split["num_docs"], footprint_bytes=split["bytes"],
            time_range_start=lo, time_range_end=hi))
        corpora.append(Corpus.from_split(split_id, reader))
    metastore.stage_splits(metadata.index_uid, staged)
    metastore.publish_splits(metadata.index_uid,
                             [s.split_id for s in staged])
    return corpora


def split_path(config: dict, index_id: str, split_id: str) -> str:
    root = config["default_index_root_uri"][len("file://"):]
    return os.path.join(root, index_id, f"{split_id}.split")


# --------------------------------------------------------------------------
# the node: a child started the way users start it


class NodeProcess:
    """`python -m quickwit_tpu.cli --config node.yaml run` as a child in
    its own process group, its output in a log under OUT_DIR."""

    started: list = []   # every NodeProcess ever started, for the reaper

    def __init__(self, name: str, config_path: str, env: dict):
        self.name = name
        self.log_path = os.path.join(OUT_DIR, f"node_{name}.log")
        self._log = open(self.log_path, "wb")
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "quickwit_tpu.cli",
             "--config", config_path, "run"],
            cwd=HERE, env=env, stdout=self._log, stderr=subprocess.STDOUT,
            start_new_session=True, preexec_fn=self._in_child)
        NodeProcess.started.append(self)
        self.endpoint = None
        self.device = None

    @staticmethod
    def _in_child() -> None:
        # a shell that backgrounds this script leaves SIGINT ignored, and
        # the node's orderly shutdown is its KeyboardInterrupt
        signal.signal(signal.SIGINT, signal.default_int_handler)
        die_with_parent()

    def _wait_line(self, marker: str, timeout: float) -> str:
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            with open(self.log_path, "r", errors="replace") as fh:
                for line in fh:
                    if marker in line and line.endswith("\n"):
                        return line.strip()
            if self.proc.poll() is not None:
                raise SmokeFailure(
                    f"node {self.name} exited with {self.proc.returncode} "
                    f"before printing {marker!r}; see {self.log_path}:\n"
                    + self.log_tail())
            time.sleep(0.2)
        raise SmokeFailure(f"node {self.name}: no {marker!r} line within "
                           f"{timeout:.0f}s\n" + self.log_tail())

    def log_tail(self, lines: int = 30) -> str:
        with open(self.log_path, "r", errors="replace") as fh:
            return "".join(fh.readlines()[-lines:])

    def wait_ready(self, want_count: int) -> None:
        """The start-up lines: the device as JAX reports it in the node
        (refused unless it is the required platform), whether the native
        indexer loaded, and the endpoint."""
        line = self._wait_line(" devices: ", 300)
        report, _, native = line.partition(" devices: ")[2].rpartition(
            " native_indexer=")
        self.device = json.loads(report)
        say(f"[node {self.name}] {line}")
        expect(self.device["platform"] == REQUIRED_PLATFORM,
               f"node runs on platform {self.device['platform']!r}, not "
               f"{REQUIRED_PLATFORM!r}: no accelerator, no smoke run")
        expect(self.device["count"] == want_count,
               f"node sees {self.device['count']} devices, this run needs "
               f"{want_count}")
        expect(native == "True", "the native indexer did not load (built "
               "from quickwit_tpu/native/fastindex.cpp at start-up)")
        line = self._wait_line(" listening on ", 120)
        self.endpoint = line.rpartition("listening on ")[2]

    def request(self, method: str, path: str, body=None, params=None,
                timeout: float = 1100.0):
        url = self.endpoint + path
        if params:
            url += "?" + urllib.parse.urlencode(params)
        data = None
        if body is not None:
            data = body if isinstance(body, bytes) else json.dumps(
                body).encode()
        req = urllib.request.Request(url, data=data, method=method)
        try:
            with LOCAL_HTTP.open(req, timeout=timeout) as resp:
                raw = resp.read()
        except urllib.error.HTTPError as exc:
            raise SmokeFailure(f"{method} {path} -> HTTP {exc.code}: "
                               f"{exc.read()[:500]!r}") from exc
        return json.loads(raw) if raw[:1] in (b"{", b"[") else raw.decode()

    def metrics(self) -> dict:
        """/metrics as {series: value}; series keep their label text."""
        values = {}
        for line in self.request("GET", "/metrics").splitlines():
            if line and not line.startswith("#"):
                series, _, value = line.rpartition(" ")
                values[series] = float(value)
        return values

    def stop(self) -> str:
        """SIGINT, the node's orderly shutdown; returns its last log line
        (peak and limit of device memory)."""
        self.proc.send_signal(signal.SIGINT)
        try:
            self.proc.wait(timeout=120)
        except subprocess.TimeoutExpired:
            raise SmokeFailure(f"node {self.name} did not stop on SIGINT "
                               f"within 120s\n" + self.log_tail())
        finally:
            self._log.close()
        expect(self.proc.returncode == 0,
               f"node {self.name} exited with {self.proc.returncode}\n"
               + self.log_tail())
        line = self._wait_line(" stopped; device memory: ", 5)
        say(f"[node {self.name}] {line}")
        return line


def reap_children() -> list:
    """Kill whatever is left of every process this script started. Returns
    the names of nodes that were still alive."""
    for worker in SplitWorkers.started:
        if worker.is_alive():
            worker.kill()
        worker.join(timeout=30)
    leftover = []
    for node in NodeProcess.started:
        if node.proc.poll() is None:
            leftover.append(node.name)
        try:
            os.killpg(node.proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        try:
            node.proc.wait(timeout=30)
        except subprocess.TimeoutExpired:
            leftover.append(f"{node.name} (unkillable)")
        if not node._log.closed:
            node._log.close()
    return leftover


def series_moved(before: dict, after: dict, name: str) -> float:
    """How far a counter moved between two readings of /metrics."""
    return series_sum(after, name) - series_sum(before, name)


def series_sum(metrics: dict, name: str) -> float:
    return sum(value for series, value in metrics.items()
               if series == name or series.startswith(name + "{"))


# --------------------------------------------------------------------------
# queries


def es_query(query: dict) -> dict:
    """The ES DSL of a query description (see `evaluate`)."""
    def term(field, text):
        spec = {"value": text}
        if query.get("boost", 1.0) != 1.0:
            spec["boost"] = query["boost"]
        return {"term": {field: spec}}
    must = [term(*t) for t in query.get("must", ())]
    should = [term(*t) for t in query.get("should", ())]
    filters = []
    if query.get("range"):
        lo, hi = query["range"]
        # bare numbers on a datetime field are unix seconds
        filters.append({"range": {"timestamp": {"gte": lo, "lt": hi}}})
    if len(must) == 1 and not should and not filters:
        return must[0]
    if not must and not should and len(filters) == 1:
        return filters[0]
    return {"bool": {**({"must": must} if must else {}),
                     **({"should": should} if should else {}),
                     **({"filter": filters} if filters else {})}}


def rest_params(query: dict) -> dict:
    """The native REST form: a query string, plus the request's own time
    filter (whole seconds, half-open) for the range."""
    clauses = [f"{field}:{text}" for field, text in query.get("must", ())]
    expect(not query.get("should") and query.get("boost", 1.0) == 1.0,
           "the query-string form carries no should clauses or boosts")
    params = {"query": " AND ".join(clauses) if clauses else "*"}
    if query.get("range"):
        params["start_timestamp"], params["end_timestamp"] = query["range"]
    return params


@dataclasses.dataclass
class Shape:
    """One query shape: how it is sent, and its cold and warm variants
    (same plan structure, different traced inputs)."""
    name: str
    endpoint: str            # "rest" or "es", for the cold round
    cold: dict = None
    warm: dict = None
    how_warm: str = ""
    k: int = 10
    aggs: dict = None
    sort_ts: bool = False


def run_query(node: NodeProcess, index: str, shape: Shape, query: dict,
              endpoint: str) -> dict:
    """Send one query with profiling on; return hits, aggs and the
    evidence counters in one normalised record."""
    before = node.metrics()
    started = time.monotonic()
    if endpoint == "rest":
        body = {**rest_params(query), "max_hits": shape.k, "profile": True,
                "timeout_ms": REQUEST_TIMEOUT_S * 1000}
        if shape.aggs:
            body["aggs"] = shape.aggs
        if shape.sort_ts:
            body["sort_by"] = "-timestamp"
        raw = node.request("POST", f"/api/v1/{index}/search", body)
        record = {"num_hits": raw["num_hits"], "docs": raw["hits"],
                  "hits": None, "aggs": raw.get("aggregations"),
                  "failed": raw.get("failed_splits", []) + raw["errors"]}
    else:
        body = {"query": es_query(query), "size": shape.k, "profile": True,
                "timeout": f"{REQUEST_TIMEOUT_S}s"}
        if shape.aggs:
            body["aggs"] = shape.aggs
        if shape.sort_ts:
            body["sort"] = [{"timestamp": {"order": "desc"}}]
        raw = node.request("POST", f"/api/v1/_elastic/{index}/_search", body)
        record = {"num_hits": raw["hits"]["total"]["value"],
                  "hits": raw["hits"]["hits"],
                  "docs": [h["_source"] for h in raw["hits"]["hits"]],
                  "aggs": raw.get("aggregations"),
                  "failed": raw.get("_shards", {}).get("failures", [])}
    record["wall_ms"] = round((time.monotonic() - started) * 1000, 1)
    after = node.metrics()
    # worth keeping: where each query's time went, phase by phase
    with open(os.path.join(OUT_DIR, "queries.jsonl"), "a") as fh:
        fh.write(json.dumps({
            "node": node.name, "shape": shape.name, "endpoint": endpoint,
            "query": query, "wall_ms": record["wall_ms"],
            "profile": raw["profile"]}) + "\n")
    expect(not record["failed"],
           f"{shape.name}: failed splits {record['failed']}")
    counters = profile_counters(raw["profile"])
    record["compile_misses"] = counters.get("compile_cache_misses", 0)
    record["compile_hits"] = counters.get("compile_cache_hits", 0)
    record["compile_s"] = round(phase_seconds(raw["profile"], "compile"), 2)
    record["staged_bytes"] = counters.get("staging_bytes", 0)
    # every dispatch site records a compile-cache hit or miss in the
    # profile; /metrics counts per-split and mask-fill launches and mesh
    # dispatches, but not the fused batch program on a single device
    record["dispatches"] = int(record["compile_misses"]
                               + record["compile_hits"])
    record["launches"] = int(
        series_moved(before, after, "qw_search_kernel_launches_total")
        + series_moved(before, after, "qw_mesh_dispatches_total"))
    record["resident_bytes"] = int(series_sum(after, "qw_resident_bytes"))
    record["metrics_after"] = after
    record["metrics_before"] = before
    return record


def profile_nodes(profile: dict):
    """The root profile and every leaf profile under it."""
    stack = [profile]
    while stack:
        node = stack.pop()
        yield node
        stack.extend(node.get("leaves") or [])


def profile_counters(profile: dict) -> dict:
    """Counters of a query profile, summed over the root and its leaves."""
    total: dict = {}
    for node in profile_nodes(profile):
        for key, value in (node.get("counters") or {}).items():
            if isinstance(value, (int, float)):
                total[key] = total.get(key, 0) + value
    return total


def phase_seconds(profile: dict, phase: str) -> float:
    return sum(record.get("duration_ms", 0.0) / 1000.0
               for node in profile_nodes(profile)
               for record in node.get("phases") or []
               if record.get("name") == phase)


def check_answer(shape: Shape, record: dict, reference: Reference,
                 query: dict) -> None:
    want = reference.answer(query)
    check_counts(shape.name, record["num_hits"], record["aggs"], want,
                 aggs=shape.aggs is AGGS)
    if shape.aggs is PERCENTILES:
        check_percentiles(shape.name, record["aggs"]["latency"], want)
    expect(len(record["docs"]) == min(shape.k, want["num_hits"]),
           f"{shape.name}: {len(record['docs'])} hits returned")
    for field, text in query.get("must", ()):
        if field == "severity_text":
            expect(all(doc[field] == text for doc in record["docs"]),
                   f"{shape.name}: a returned doc is not {field}:{text}")
    if shape.sort_ts:
        top = reference.top_by(want, "ts", shape.k)
        expect([doc["timestamp"] for doc in record["docs"]]
               == [int(ts) for _, ts in top],
               f"{shape.name}: sorted timestamps differ from the reference")
        if record["hits"] is not None:
            check_sorted_hits(shape.name, record["hits"], reference, want,
                              shape.k)
    elif record["hits"] is not None and shape.k:
        check_scored_hits(shape.name, record["hits"], reference, want,
                          shape.k)


def describe(record: dict) -> str:
    return (f"wall {record['wall_ms']} ms, compile {record['compile_s']} s "
            f"(cache misses {record['compile_misses']:.0f}, hits "
            f"{record['compile_hits']:.0f}), device dispatches "
            f"{record['dispatches']} (kernel launches and mesh dispatches "
            f"in /metrics: {record['launches']}), staged "
            f"{record['staged_bytes']:.0f} B, "
            f"resident {record['resident_bytes']} B")


def hdfs_shapes() -> list:
    """The BASELINE.json request shapes. A repeated
    query is a leaf-cache hit and never reaches the device, so each warm
    variant changes what the plan carries as traced inputs."""
    error = [("severity_text", "ERROR")]
    boost = ("the ES endpoint with a term boost: the traced idf*boost "
             "scalar changes, the executable is shared")
    return [
        Shape("c1_term_top10", "rest", {"must": error},
              {"must": error, "boost": 2.0}, boost),
        Shape("c2_bool_range_top100", "es",
              {"must": error,
               "should": [("body", "term000003"), ("body", "term000007")],
               "range": (T0 + DAY, T0 + 4 * DAY)},
              {"must": error,
               "should": [("body", "term000003"), ("body", "term000007")],
               "range": (T0 + 2 * DAY, T0 + 5 * DAY)},
              "other range bounds (traced scalars)", k=100),
        Shape("c3_agg_only", "rest", {"must": [("severity_text", "WARN")]},
              {"must": [("severity_text", "WARN")], "boost": 2.0},
              "the ES endpoint with a boost wrapper: an agg-only plan does "
              "not score, so the traced inputs are the same and only the "
              "cache keys differ", k=0, aggs=AGGS),
        Shape("flagship", "rest", {"must": error},
              {"must": error, "boost": 3.0}, boost, aggs=AGGS),
        Shape("term_sort_timestamp_desc", "rest",
              {"must": error, "range": (T0 + DAY, T0 + 5 * DAY)},
              {"must": error, "range": (T0 + 2 * DAY, T0 + 6 * DAY)},
              "the ES endpoint with other range bounds (traced scalars)",
              sort_ts=True),
        Shape("es_date_histogram", "es",
              {"range": (T0 + DAY, T0 + 3 * DAY)},
              {"range": (T0 + 2 * DAY, T0 + 4 * DAY)},
              "other range bounds (traced scalars)", k=0, aggs=AGGS),
    ]


def run_shapes(node: NodeProcess, index: str, reference: Reference,
               shapes: list, rounds: tuple) -> dict:
    """Each shape in each round ("cold", "warm"), checked against the
    reference. Returns {shape: {round: record}}."""
    records: dict = {}
    for shape in shapes:
        for round_name in rounds:
            query = shape.cold if round_name == "cold" else shape.warm
            endpoint = shape.endpoint if round_name == "cold" else "es"
            record = run_query(node, index, shape, query, endpoint)
            check_answer(shape, record, reference, query)
            say(f"[{shape.name}] {round_name} over {endpoint}: "
                f"{record['num_hits']} hits == reference; "
                + describe(record))
            expect(record["dispatches"] >= 1,
                   f"{shape.name} {round_name}: no device dispatch")
            records.setdefault(shape.name, {})[round_name] = record
        if "warm" in rounds:
            say(f"[{shape.name}] warm variant: {shape.how_warm}")
    return records


def run_concurrent(node: NodeProcess, index: str,
                   reference: Reference) -> None:
    """Eight shape-compatible queries at once: the batcher must form a
    stacked dispatch. Arrival order decides who rides with whom, so a
    round is repeated with fresh bounds until a group of two or more is
    seen; every answer of every round is checked."""
    shape = Shape("concurrent_term_sort", "es", sort_ts=True)
    hour = 3600
    for round_no in range(CONCURRENT_ROUNDS):
        queries = [{"must": [("severity_text", "ERROR")],
                    "range": (T0 + (round_no * 16 + i) * hour,
                              T0 + (round_no * 16 + i) * hour + 3 * DAY)}
                   for i in range(CONCURRENT_QUERIES)]
        before = node.metrics()
        with concurrent.futures.ThreadPoolExecutor(
                CONCURRENT_QUERIES) as pool:
            records = list(pool.map(
                lambda q: run_query(node, index, shape, q, "es"), queries))
        after = node.metrics()
        for query, record in zip(queries, records):
            check_answer(shape, record, reference, query)
        groups = series_moved(before, after, "qw_qbatch_groups_total")
        stacked = series_moved(before, after,
                               "qw_qbatch_queries_per_dispatch_sum")
        launches = series_moved(before, after,
                                "qw_search_kernel_launches_total")
        say(f"[concurrent x{CONCURRENT_QUERIES}] round {round_no}: all "
            f"answers == reference; stacked groups {groups:.0f} carrying "
            f"{stacked:.0f} queries, device dispatches {launches:.0f}, "
            f"compile misses "
            f"{sum(r['compile_misses'] for r in records):.0f}")
        if groups >= 1 and stacked >= 2:
            return
    raise SmokeFailure(f"no stacked dispatch formed in {CONCURRENT_ROUNDS} "
                       f"rounds of {CONCURRENT_QUERIES} concurrent queries")


# --------------------------------------------------------------------------
# phases


def write_config(name: str) -> tuple:
    config = {
        "node_id": f"smoke-{name}",
        "metastore_uri": f"file://{DATA_DIR}/metastore",
        "default_index_root_uri": f"file://{DATA_DIR}/indexes",
        "data_dir": f"{DATA_DIR}/node-data",
        "rest": {"listen_host": "127.0.0.1", "listen_port": 0},
    }
    path = os.path.join(DATA_DIR, f"node_{name}.yaml")
    with open(path, "w") as fh:
        json.dump(config, fh, indent=1)   # JSON is YAML
    return config, path


def cache_files() -> int:
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        HERE, ".jax_cache")
    return len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0


def published_splits(node: NodeProcess, index: str) -> list:
    """Metadata of the index's published splits, from the REST API."""
    splits = node.request("GET", f"/api/v1/indexes/{index}/splits")["splits"]
    return [s["metadata"] for s in splits if s["state"] == "Published"]


def ingest_phase(node: NodeProcess, seed: int) -> tuple:
    """100k documents through POST /ingest -> WAL -> indexing pipeline ->
    publish, in two requests, the second sent once the first is published:
    two splits, so a search of this index takes the fused multi-split
    path (on one device: the batch program with donated inputs). Returns
    (index id, the documents' raw arrays)."""
    from quickwit_tpu.index.synthetic import HDFS_MAPPER, SEVERITIES
    index = "hdfs-ingest"
    node.request("POST", "/api/v1/indexes",
                 {"index_id": index, "doc_mapping": HDFS_MAPPER.to_dict()})
    lines, ts, tenants, sev, body = ingest_documents(INGEST_DOCS,
                                                    seed + 1000)
    for lo, hi in ingest_halves(len(lines)):
        started = time.monotonic()
        reply = node.request(
            "POST", f"/api/v1/{index}/ingest",
            "\n".join(lines[lo:hi]).encode(), params={"commit": "wal"})
        expect(reply["num_docs"] == hi - lo, f"ingest acknowledged {reply}")
        acked = time.monotonic()
        published = 0
        while published < hi:
            expect(time.monotonic() < acked + 300,
                   f"only {published} of {hi} ingested docs were published "
                   "within 300s")
            time.sleep(0.5)
            published = sum(s["num_docs"]
                            for s in published_splits(node, index))
        say(f"[ingest] docs {lo}..{hi}: acknowledged by the WAL in "
            f"{acked - started:.1f}s, published and listed "
            f"{time.monotonic() - acked:.1f}s later")
    return index, (ts, tenants, sev, SEVERITIES, body)


def ingest_halves(num_docs: int) -> list:
    return [(0, num_docs // 2), (num_docs // 2, num_docs)]


def ingest_reference(node: NodeProcess, index: str, raw) -> Reference:
    """The ingested docs as reference corpora, one per request: documents
    are sent in timestamp order, so the split with the earlier time range
    holds the first request, and a doc's id is its position within it."""
    ts, tenants, sev, severities, body = raw
    splits = sorted(published_splits(node, index),
                    key=lambda s: (s["time_range_start"],
                                   s["time_range_end"]))
    halves = ingest_halves(len(ts))
    expect([s["num_docs"] for s in splits] == [hi - lo for lo, hi in halves],
           f"expected one split per ingest request, found "
           f"{[(s['split_id'], s['num_docs']) for s in splits]}")
    return Reference([
        Corpus.from_docs(split["split_id"], ts[lo:hi], tenants[lo:hi],
                         sev[lo:hi], severities, body[lo:hi])
        for split, (lo, hi) in zip(splits, halves)])


def ingest_shapes() -> list:
    info = [("severity_text", "INFO")]
    return [
        Shape("ingested_term_top10", "es", {"must": info}),
        Shape("ingested_body_term_top10", "es",
              {"must": [("body", "term000001")]}),
        Shape("ingested_flagship", "rest", {"must": info}, aggs=AGGS),
        Shape("ingested_sort_timestamp_desc", "es",
              {"must": info, "range": (T0 + DAY, T0 + 5 * DAY)},
              sort_ts=True),
    ]


def one_chip(seed: int, node_env: dict) -> dict:
    config, config_path = write_config("one")
    workers = SplitWorkers([(split_path(config, "hdfs-logs", f"hdfs-{seed}"),
                             SPLIT_DOCS, seed)])

    # -- node A: device check, then the ingest path ----------------------
    node = NodeProcess("A", config_path, node_env)
    node.wait_ready(want_count=1)
    device = node.device
    index, raw = ingest_phase(node, seed)
    reference = ingest_reference(node, index, raw)
    run_shapes(node, index, reference, ingest_shapes(), ("cold",))
    node.stop()

    reference = Reference(publish_splits(config, "hdfs-logs",
                                         workers.wait()))

    # -- node B: the 10M-doc split, cold then warm, then concurrent -------
    files_before = cache_files()
    node = NodeProcess("B", config_path, node_env)
    node.wait_ready(want_count=1)
    first = run_shapes(node, "hdfs-logs", reference, hdfs_shapes(),
                       ("cold", "warm"))
    for name, rounds in first.items():
        expect(rounds["cold"]["compile_misses"] >= 1,
               f"{name}: the cold round compiled nothing")
        expect(rounds["warm"]["compile_misses"] == 0,
               f"{name}: the warm round compiled "
               f"{rounds['warm']['compile_misses']} program(s)")
    run_concurrent(node, "hdfs-logs", reference)
    from quickwit_tpu.search.admission import DEFAULT_BUDGET_BYTES
    say(f"[residency] resident columns "
        f"{int(series_sum(node.metrics(), 'qw_resident_bytes'))} B of the "
        f"{DEFAULT_BUDGET_BYTES} B HbmBudget default")
    node.stop()
    files_first = cache_files()
    say(f"[compile cache] {files_first - files_before} new file(s) written "
        f"by node B ({files_first} in all)")

    # -- node C: restarted, served from the persistent compile cache ------
    node = NodeProcess("C", config_path, node_env)
    node.wait_ready(want_count=1)
    again = run_shapes(node, "hdfs-logs", reference, hdfs_shapes(),
                       ("cold",))
    run_shapes(node, "hdfs-ingest", ingest_reference(node, index, raw),
               ingest_shapes()[:1], ("cold",))
    node.stop()
    expect(cache_files() == files_first,
           f"the restarted node wrote {cache_files() - files_first} new "
           "compile-cache file(s): the cache did not serve it")
    for name, rounds in again.items():
        was, now = first[name]["cold"]["compile_s"], \
            rounds["cold"]["compile_s"]
        say(f"[compile cache] {name}: compile phase {was}s on the first "
            f"node, {now}s on the restarted one")
        expect(now <= max(0.25 * was, 5.0),
               f"{name}: restarted node spent {now}s compiling against "
               f"{was}s at first: not served from the persistent cache")
    return device


def four_chips(seed: int, node_env: dict) -> dict:
    config, config_path = write_config("four")
    workers = SplitWorkers(
        [(split_path(config, "hdfs-logs", f"hdfs-{seed + i}"), SPLIT_DOCS,
          seed + i) for i in range(MESH_SPLITS)])
    reference = Reference(publish_splits(config, "hdfs-logs",
                                         workers.wait()))

    node = NodeProcess("mesh", config_path, node_env)
    node.wait_ready(want_count=MESH_SPLITS)
    device = node.device
    error = [("severity_text", "ERROR")]
    shapes = [
        Shape("mesh_flagship", "rest", {"must": error},
              {"must": error, "boost": 2.0},
              "the ES endpoint with a term boost (traced idf*boost)",
              aggs=AGGS),
        Shape("c5_percentiles", "rest", {"must": error},
              {"must": error, "boost": 2.0},
              "the ES endpoint with a boost wrapper (agg-only plans do not "
              "score: same traced inputs, other cache keys)",
              k=0, aggs=PERCENTILES),
    ]
    records = run_shapes(node, "hdfs-logs", reference, shapes,
                         ("cold", "warm"))
    flagship = records["mesh_flagship"]
    for round_name, record in flagship.items():
        moved = series_moved(record["metrics_before"],
                             record["metrics_after"],
                             "qw_mesh_collective_bytes_total")
        devices = series_sum(record["metrics_after"], "qw_mesh_devices")
        say(f"[mesh_flagship] {round_name}: collective payload {moved:.0f} "
            f"B over {devices:.0f} devices")
        expect(moved > 0, f"mesh_flagship {round_name}: the collective "
               "program did not run (no collective bytes counted)")
    expect(flagship["warm"]["compile_misses"] == 0,
           "mesh_flagship: the warm round compiled")
    expect(series_sum(flagship["warm"]["metrics_after"], "qw_mesh_devices")
           == MESH_SPLITS, "the mesh does not span all four devices")
    # eight at once through the mesh: concurrent collective programs must
    # not deadlock, whoever rides with whom
    shape = Shape("mesh_concurrent", "es", aggs=AGGS)
    queries = [{"must": error, "boost": 4.0 + i}
               for i in range(CONCURRENT_QUERIES)]
    with concurrent.futures.ThreadPoolExecutor(CONCURRENT_QUERIES) as pool:
        concurrent_records = list(pool.map(
            lambda q: run_query(node, "hdfs-logs", shape, q, "es"), queries))
    for query, record in zip(queries, concurrent_records):
        check_answer(shape, record, reference, query)
    groups = series_moved(concurrent_records[0]["metrics_before"],
                          concurrent_records[-1]["metrics_after"],
                          "qw_qbatch_groups_total")
    say(f"[mesh_concurrent] {CONCURRENT_QUERIES} concurrent mesh queries "
        f"== reference (stacked groups formed: {groups:.0f})")
    resident = int(series_sum(flagship["warm"]["metrics_after"],
                              "qw_resident_bytes"))
    line = node.stop()
    devices = json.loads(line.rpartition("device memory: ")[2])
    live = [entry["live_bytes"] for entry in devices]
    say(f"[mesh] bytes each device still holds at shutdown: {live}; the "
        f"resident store counted {resident} B per device after the mesh "
        f"flagship; allocator peaks "
        f"{[entry['peak_bytes_in_use'] for entry in devices]}")
    # the store's accounting rounds each slot up, so allow it a little
    expect(len(live) == MESH_SPLITS and min(live) >= 0.99 * resident > 0,
           f"the four devices did not each hold a shard: {live} against "
           f"{resident} B of resident columns per device")
    return device


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = parser.parse_args(argv)

    # the node child gets the environment as it was found, so JAX there
    # picks the chip; this process (and its generator workers) stays on
    # the CPU and never initialises a backend
    node_env = dict(os.environ)
    os.environ["JAX_PLATFORMS"] = "cpu"
    started = time.monotonic()
    result: dict = {"ok": False}
    # a terminated run still reaps its children (the finally below)
    signal.signal(signal.SIGTERM, lambda signum, frame: sys.exit(143))
    try:
        shutil.rmtree(DATA_DIR, ignore_errors=True)
        # native code is rebuilt from source on this machine
        shutil.rmtree(os.path.join(HERE, "quickwit_tpu", "native", "_build"),
                      ignore_errors=True)
        os.makedirs(DATA_DIR)
        shutil.rmtree(OUT_DIR, ignore_errors=True)
        os.makedirs(OUT_DIR)
        sys.path.insert(0, HERE)
        device = (one_chip if args.chips == 1 else four_chips)(
            args.seed, node_env)
        result = {"ok": True, "device": device}
    except SmokeFailure as exc:
        say(f"FAILED: {exc}")
        result["error"] = str(exc).splitlines()[0]
    except Exception as exc:  # the boundary: report, reap, exit non-zero
        traceback.print_exc()
        say(f"FAILED: {type(exc).__name__}: {exc}")
        result["error"] = f"{type(exc).__name__}: {exc}".splitlines()[0]
    finally:
        leftover = reap_children()
        shutil.rmtree(DATA_DIR, ignore_errors=True)
    if leftover and result["ok"]:
        say(f"FAILED: children were still running at the end: {leftover}")
        result = {"ok": False, "error": f"leftover children {leftover}"}
    say(f"[smoke] {time.monotonic() - started:.0f}s in all")
    print(json.dumps(result), flush=True)
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
