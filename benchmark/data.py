"""Data, made from the seed: one synthetic hdfs split per split of the
configuration, generated by a spawned CPU worker each, one at a time, kept
under `<checkout>/.bench_cache/<config>/<seed>/` (git-ignored; a seed's
splits are made once per checkout and found again by every later run and
cell; the bodies, which no seed changes, under `.bench_cache/bodies/` for
every configuration of the same shapes), and published through the metastore
protocol before the node starts.

The documents are the benchmark's: `draw_docs` and `body_tokens` draw every
document's timestamp, tenant, severity and body terms, and keep them beside
the split (`*.docs.npz`, `bodies/body-*.tokens.npy`) for the reference, which reads
nothing else. `build_split` hands those documents to the program's array
writers (as `index/synthetic.py::synthetic_hdfs_split` does) to make the
split file the node serves; `generate_split`, the workers and
`publish_splits` are copied from `chip_smoke.py` (PR 21). `SplitFile` reads
a split's footer and arrays for the roofline reckoning only.
"""

from __future__ import annotations

import ctypes
import json
import multiprocessing
import os
import resource
import shutil
import signal
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CACHE_DIR = os.path.join(ROOT, ".bench_cache")
# A configuration's newest seeds are kept up to this many bytes, and never
# fewer than KEEP_SEEDS_MIN: six 10M-doc seeds were 9.7 GB, six 40M-doc
# seeds would be 39 GB. The bodies (once per checkout) are kept as they are.
KEEP_BYTES = 14_000_000_000
KEEP_SEEDS_MIN = 2


MAGIC = b"QWTPU001"


class SplitFile:
    """Read-only view of one split file: footer and memory-mapped arrays."""

    def __init__(self, path: str):
        self.path = path
        self._map = np.memmap(path, dtype=np.uint8, mode="r")
        if bytes(self._map[-8:]) != MAGIC:
            raise ValueError(f"{path}: not a split file")
        meta_len = int.from_bytes(bytes(self._map[-16:-8]), "little")
        self.footer = json.loads(bytes(self._map[-16 - meta_len:-16]))
        self.arrays = {a["name"]: a for a in self.footer["arrays"]}
        self.num_docs = self.footer["num_docs"]

    def array(self, name: str) -> np.ndarray:
        meta = self.arrays[name]
        raw = self._map[meta["offset"]:meta["offset"] + meta["nbytes"]]
        return raw.view(np.dtype(meta["dtype"])).reshape(meta["shape"])

    def strings(self, prefix: str) -> list:
        blob = bytes(self.array(f"{prefix}_blob"))
        offsets = self.array(f"{prefix}_offsets")
        return [blob[offsets[i]:offsets[i + 1]].decode()
                for i in range(len(offsets) - 1)]


def rss_peak_bytes() -> int:
    """This process's peak resident size (`ru_maxrss`; Linux counts KiB)."""
    return 1024 * resource.getrusage(resource.RUSAGE_SELF).ru_maxrss


def die_with_parent() -> None:
    """Linux: have the kernel kill this process when its parent dies
    (PR_SET_PDEATHSIG), so a killed run leaves nothing behind."""
    ctypes.CDLL(None, use_errno=True).prctl(1, int(signal.SIGKILL))


class ArrayRecorder:
    """Stands in for a SplitFileBuilder while the body field is written, so
    that its arrays can be kept and replayed."""

    def __init__(self):
        self.arrays: dict = {}

    def add_array(self, name: str, array) -> None:
        self.arrays[name] = np.ascontiguousarray(array)


class DrawnTokens:
    """Stands in for the random state `_write_body` draws its tokens from:
    hands it the benchmark's own draw, so the writer indexes the very
    documents the reference knows."""

    def __init__(self, draws: np.ndarray, exponent: float):
        self.draws, self.exponent = draws, exponent

    def zipf(self, a, size):
        if a != self.exponent or size != len(self.draws):
            raise ValueError(f"the program's body writer draws zipf({a}, "
                             f"{size}); the configuration says zipf("
                             f"{self.exponent}, {len(self.draws)})")
        return self.draws


def body_path(body_dir: str, config: dict, number: int) -> str:
    """Where the body of a configuration's split `number` is kept, named by
    all that it is drawn from, so that configurations of the same `assumed`
    shapes share it (split 0 of `hdfs-logs-40m` is `hdfs-logs-10m`'s)."""
    assumed = config["assumed"]
    return os.path.join(body_dir, "body-{}-{}-{}-{}-{}".format(
        config["docs_per_split"], assumed["body_vocabulary_terms"],
        assumed["body_zipf_exponent"], assumed["body_tokens_per_doc"],
        assumed["body_seed"] + number))


def body_arrays(body_dir: str, config: dict, num_docs_padded: int,
                number: int) -> tuple:
    """(arrays, field metadata) of the body field. The benchmark draws each
    document's `body_tokens_per_doc` terms, Zipf over the vocabulary, from
    `body_seed` and keeps them (`body-*.tokens.npy`, [docs, tokens]) for
    the reference; the program's `_write_body` indexes that draw. Both are
    kept under `.bench_cache/bodies`: the body is the same for every
    `--seed` (see build_split) and is four fifths of a split's generation."""
    from quickwit_tpu.index import synthetic
    assumed, num_docs = config["assumed"], config["docs_per_split"]
    path = body_path(body_dir, config, number)
    body_seed = assumed["body_seed"] + number
    if os.path.exists(path + ".json"):
        with open(path + ".json") as fh:
            fields = json.load(fh)
        with np.load(path + ".npz") as kept:
            return {name: kept[name] for name in kept.files}, fields
    draws = np.random.RandomState(body_seed).zipf(
        assumed["body_zipf_exponent"],
        size=num_docs * assumed["body_tokens_per_doc"])
    os.makedirs(os.path.dirname(path), exist_ok=True)
    np.save(path + ".tokens.npy", np.minimum(
        draws - 1, assumed["body_vocabulary_terms"] - 1).astype(
        np.int32).reshape(num_docs, assumed["body_tokens_per_doc"]))
    recorder, fields = ArrayRecorder(), {}
    synthetic._write_body(recorder, fields,
                          DrawnTokens(draws, assumed["body_zipf_exponent"]),
                          num_docs, num_docs_padded)
    np.savez(path + ".tmp.npz", **recorder.arrays)
    os.replace(path + ".tmp.npz", path + ".npz")
    with open(path + ".json", "w") as fh:
        json.dump(fields, fh)
    return recorder.arrays, fields


def draw_docs(config: dict, seed: int) -> dict:
    """Every document's timestamp (seconds, ascending), tenant and severity
    (an ordinal into the sorted severity names), drawn from the seed. Each
    severity gets its exact share of the documents; the seed draws which."""
    assumed, num_docs = config["assumed"], config["docs_per_split"]
    rng = np.random.RandomState(seed)
    ts = np.sort(rng.randint(0, assumed["span_days"] * 86_400,
                             size=num_docs)) + assumed["start_ts"]
    tenant = rng.randint(0, assumed["tenants"], size=num_docs)
    names = sorted(assumed["severities"])
    counts = [int(round(assumed["severities"][name] * num_docs))
              for name in names]
    counts[-1] += num_docs - sum(counts)
    sev = rng.permutation(np.repeat(np.arange(len(counts)), counts))
    return {"ts": ts.astype(np.int64), "tenant": tenant.astype(np.int64),
            "sev": sev.astype(np.int32)}


def build_split(config: dict, docs: dict, number: int,
                body_dir: str) -> bytes:
    """One hdfs-logs split over the benchmark's documents, as
    `index/synthetic.py::synthetic_hdfs_split` builds it (same writers, same
    arrays, doc store on). Two things keep every array's SHAPE the same for
    every seed — a posting list's padded length is part of every compiled
    program's key (PERF.md), so a split whose lengths moved with the seed
    would make every new seed a new set of programs and minutes of
    compiling: each severity has its exact share of the documents
    (`draw_docs`), and the body's terms come from the configuration's
    `body_seed`, not from `--seed`."""
    from quickwit_tpu.index import synthetic
    from quickwit_tpu.index.format import (DOC_PAD, SplitFileBuilder,
                                           SplitFooter, pad_to)
    assumed = config["assumed"]
    num_docs = config["docs_per_split"]
    num_docs_padded = pad_to(num_docs, DOC_PAD)
    builder, fields = SplitFileBuilder(), {}

    ts_micros = np.zeros(num_docs_padded, dtype=np.int64)
    ts_micros[:num_docs] = docs["ts"] * 1_000_000
    present = np.zeros(num_docs_padded, dtype=np.uint8)
    present[:num_docs] = 1
    builder.add_array("col.timestamp.values", ts_micros)
    builder.add_array("col.timestamp.present", present)
    fields["timestamp"] = {
        "type": "datetime", "fast": True, "column_kind": "numeric",
        "min_value": int(ts_micros[0]),
        "max_value": int(ts_micros[num_docs - 1])}

    tenant_col = np.zeros(num_docs_padded, dtype=np.int64)
    tenant_col[:num_docs] = docs["tenant"]
    builder.add_array("col.tenant_id.values", tenant_col)
    builder.add_array("col.tenant_id.present", present)
    fields["tenant_id"] = {"type": "u64", "fast": True,
                           "column_kind": "numeric", "min_value": 0,
                           "max_value": assumed["tenants"] - 1}

    if sorted(assumed["severities"]) != synthetic.SEVERITIES:
        raise ValueError("the configuration's severities are not the "
                         "mapper's")
    synthetic._write_categorical(builder, fields, "severity_text",
                                 synthetic.SEVERITIES, docs["sev"], num_docs,
                                 num_docs_padded)

    arrays, body_fields = body_arrays(body_dir, config, num_docs_padded,
                                      number)
    for name, array in arrays.items():
        builder.add_array(name, array)
    fields.update(body_fields)
    synthetic._write_store(builder, docs["ts"], docs["tenant"], docs["sev"],
                           num_docs)
    footer = SplitFooter(
        num_docs=num_docs, num_docs_padded=num_docs_padded, arrays={},
        fields=fields,
        time_range=(int(ts_micros[0]), int(ts_micros[num_docs - 1])),
        extra={"synthetic": True, "benchmark": config["name"]})
    return builder.finish(footer)


def generate_split(path: str, config: dict, seed: int, number: int,
                   body_dir: str) -> None:
    """Worker: one split written where the index's storage will look for
    it, and beside it what its SplitMetadata needs."""
    die_with_parent()
    started = time.monotonic()
    body = body_path(body_dir, config, number)
    body_files = [] if os.path.exists(body + ".json") else [
        body + end for end in (".tokens.npy", ".npz", ".json")]
    docs = draw_docs(config, seed)
    data = build_split(config, docs, number, body_dir)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    np.savez(path + ".docs.npz", **docs)
    with open(path + ".tmp", "wb") as fh:
        fh.write(data)
        # on disk before the node starts: 1.4 GB of dirty pages written
        # back during the window would be this run's noise, not the node's
        os.fsync(fh.fileno())
    os.replace(path + ".tmp", path)
    with open(path + ".json", "w") as fh:
        json.dump({"path": path, "num_docs": config["docs_per_split"],
                   "bytes": len(data), "docs": path + ".docs.npz",
                   "body_tokens": body + ".tokens.npy",
                   "seconds": round(time.monotonic() - started, 1),
                   # this worker's own peak, and what it left on the
                   # disk: the split, its documents and, where it had to
                   # make it, the body
                   "ru_maxrss_bytes": rss_peak_bytes(),
                   "wrote_bytes": len(data) + sum(
                       os.path.getsize(made) for made in
                       [path + ".docs.npz"] + body_files)}, fh)


def index_root(config: dict, seed: int) -> str:
    """Where a seed's splits live: the node's index root for the run."""
    return os.path.join(CACHE_DIR, config["name"], str(seed), "indexes")


def split_jobs(config: dict, seed: int) -> list:
    """[(path, configuration, split seed, split number, the directory of the
    bodies)] of a configuration's splits."""
    root = os.path.join(index_root(config, seed), config["index_id"])
    return [(os.path.join(root, f"hdfs-{seed + i}.split"), config, seed + i,
             i, os.path.join(CACHE_DIR, "bodies"))
            for i in range(config["num_splits"])]


def ensure_splits(config: dict, seed: int, started: list, say) -> list:
    """The configuration's splits for this seed, generated where the cache
    has none by one worker at a time, so that the generator's host memory
    does not grow with `num_splits` (ISSUE 28): a worker that has to make its
    split's body peaks at 12.95 GB at 10M docs, one that finds it kept at
    4.68 GB (PR 28's chip runs, README.md). Fixed here, not read from the
    machine, no option. `started` collects worker processes for the reaper.
    Returns each split's record, with `cached` saying where it came from."""
    jobs = split_jobs(config, seed)
    missing = [job for job in jobs if not os.path.exists(job[0] + ".json")]
    ctx = multiprocessing.get_context("spawn")
    for job in missing:
        proc = ctx.Process(target=generate_split, args=job)
        proc.start()
        started.append(proc)
        proc.join()
        if proc.exitcode != 0:
            raise RuntimeError(f"the generator of {job[0]} exited with "
                               f"{proc.exitcode}")
    splits = []
    for job in jobs:
        with open(job[0] + ".json") as fh:
            record = json.load(fh)
        record["cached"] = job not in missing
        record["written_at"] = None if record["cached"] else time.monotonic()
        splits.append(record)
        say(f"[data] {os.path.basename(job[0])}: {record['num_docs']} docs, "
            f"{record['bytes']} bytes, " + (
                "found in the cache" if record["cached"] else
                f"made from its seed in {record['seconds']}s, wrote "
                f"{record['wrote_bytes']} bytes, worker's peak "
                f"{record['ru_maxrss_bytes'] / 1e6:.0f} MB"))
    made = [s for s in splits if not s["cached"]]
    if made:
        say(f"[data] {len(made)} split(s) made, one worker at a time: "
            f"{sum(s['wrote_bytes'] for s in made)} bytes written under "
            ".bench_cache")
    os.utime(os.path.dirname(index_root(config, seed)))     # newest seed
    evict(config["name"])
    return splits


def generator_peak(splits: list):
    """The largest peak of this run's generator workers, which ran one at a
    time; None where every split was found."""
    return max((s["ru_maxrss_bytes"] for s in splits if not s["cached"]),
               default=None)


def tree_bytes(path: str) -> int:
    return sum(os.path.getsize(os.path.join(folder, name))
               for folder, _, names in os.walk(path) for name in names)


def evict(config_name: str) -> None:
    """Delete a configuration's older seeds: the newest stay, as many as
    fit into KEEP_BYTES and never fewer than KEEP_SEEDS_MIN."""
    root = os.path.join(CACHE_DIR, config_name)
    seeds = sorted((os.path.join(root, d) for d in os.listdir(root)
                    if d.isdigit()), key=os.path.getmtime, reverse=True)
    held = 0
    for number, folder in enumerate(seeds):
        held += tree_bytes(folder)
        if number >= KEEP_SEEDS_MIN and held > KEEP_BYTES:
            for old in seeds[number:]:
                shutil.rmtree(old, ignore_errors=True)
            return


def publish_splits(node_config: dict, index_id: str, splits: list) -> None:
    """Create the index and stage and publish the generated splits through
    the metastore protocol (no node is running)."""
    from quickwit_tpu.common.uri import Uri
    from quickwit_tpu.index.reader import SplitReader
    from quickwit_tpu.index.synthetic import HDFS_MAPPER
    from quickwit_tpu.metastore.file_backed import FileBackedMetastore
    from quickwit_tpu.models.split_metadata import SplitMetadata
    from quickwit_tpu.serve.node import IndexService
    from quickwit_tpu.storage.base import StorageResolver
    from quickwit_tpu.storage.local import LocalFileStorage
    resolver = StorageResolver.default()
    metastore = FileBackedMetastore(
        resolver.resolve(node_config["metastore_uri"]))
    metadata = IndexService(
        metastore, resolver,
        node_config["default_index_root_uri"]).create_index(
        {"index_id": index_id, "doc_mapping": HDFS_MAPPER.to_dict()})
    storage = LocalFileStorage(Uri.parse(metadata.index_config.index_uri))
    staged = []
    for split in splits:
        split_id = os.path.basename(split["path"])[:-len(".split")]
        lo, hi = SplitReader(storage, f"{split_id}.split").footer.time_range
        staged.append(SplitMetadata(
            split_id=split_id, index_uid=metadata.index_uid,
            num_docs=split["num_docs"], footprint_bytes=split["bytes"],
            time_range_start=lo, time_range_end=hi))
    metastore.stage_splits(metadata.index_uid, staged)
    metastore.publish_splits(metadata.index_uid,
                             [s.split_id for s in staged])
