"""Data, made from the seed: one synthetic hdfs split per split of the
configuration, generated by a spawned CPU worker each, kept under
`<checkout>/.bench_cache/<config>/<seed>/` (git-ignored; a seed's splits are
made once per checkout and found again by every later run and cell), and
published through the metastore protocol before the node starts.

The documents are the benchmark's: `draw_docs` and `body_tokens` draw every
document's timestamp, tenant, severity and body terms, and keep them beside
the split (`*.docs.npz`, `body-*.tokens.npy`) for the reference, which reads
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
import shutil
import signal
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CACHE_DIR = os.path.join(ROOT, ".bench_cache")
KEEP_SEEDS = 6      # of one configuration; older ones are deleted


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


def body_path(config_dir: str, num_docs: int, body_seed: int) -> str:
    return os.path.join(config_dir, f"body-{num_docs}-{body_seed}")


def body_arrays(config_dir: str, config: dict, num_docs_padded: int,
                body_seed: int) -> tuple:
    """(arrays, field metadata) of the body field. The benchmark draws each
    document's `body_tokens_per_doc` terms, Zipf over the vocabulary, from
    `body_seed` and keeps them (`body-*.tokens.npy`, [docs, tokens]) for
    the reference; the program's `_write_body` indexes that draw. Both are
    kept under the configuration's cache directory: the body is the same
    for every `--seed` (see build_split) and is four fifths of a split's
    generation."""
    from quickwit_tpu.index import synthetic
    assumed, num_docs = config["assumed"], config["docs_per_split"]
    path = body_path(config_dir, num_docs, body_seed)
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
                config_dir: str) -> bytes:
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

    arrays, body_fields = body_arrays(config_dir, config, num_docs_padded,
                                      assumed["body_seed"] + number)
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
                   config_dir: str) -> None:
    """Worker: one split written where the index's storage will look for
    it, and beside it what its SplitMetadata needs."""
    die_with_parent()
    started = time.monotonic()
    docs = draw_docs(config, seed)
    data = build_split(config, docs, number, config_dir)
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
                   "body_tokens": body_path(
                       config_dir, config["docs_per_split"],
                       config["assumed"]["body_seed"] + number)
                   + ".tokens.npy",
                   "seconds": round(time.monotonic() - started, 1)}, fh)


def index_root(config: dict, seed: int) -> str:
    """Where a seed's splits live: the node's index root for the run."""
    return os.path.join(CACHE_DIR, config["name"], str(seed), "indexes")


def split_jobs(config: dict, seed: int) -> list:
    """[(path, configuration, split seed, split number, the configuration's
    cache directory)] of a configuration's splits."""
    root = os.path.join(index_root(config, seed), config["index_id"])
    return [(os.path.join(root, f"hdfs-{seed + i}.split"), config, seed + i,
             i, os.path.join(CACHE_DIR, config["name"]))
            for i in range(config["num_splits"])]


def ensure_splits(config: dict, seed: int, started: list, say) -> list:
    """The configuration's splits for this seed, generated where the cache
    has none. `started` collects worker processes for the reaper. Returns
    each split's record, with `cached` saying where it came from."""
    jobs = split_jobs(config, seed)
    missing = [job for job in jobs if not os.path.exists(job[0] + ".json")]
    ctx = multiprocessing.get_context("spawn")
    procs = [ctx.Process(target=generate_split, args=job) for job in missing]
    for proc in procs:
        proc.start()
    started += procs
    for proc, job in zip(procs, missing):
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
                f"made from its seed in {record['seconds']}s"))
    os.utime(os.path.dirname(index_root(config, seed)))     # newest seed
    evict(config["name"])
    return splits


def evict(config_name: str) -> None:
    root = os.path.join(CACHE_DIR, config_name)
    seeds = sorted((os.path.join(root, d) for d in os.listdir(root)
                    if d.isdigit()), key=os.path.getmtime)
    for old in seeds[:-KEEP_SEEDS]:
        shutil.rmtree(old, ignore_errors=True)


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
