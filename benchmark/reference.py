"""The plain reference: numpy over the documents the benchmark drew, and the
comparison that decides `correct`.

The evaluation is copied from `chip_smoke.py` (PR 21), which proved it
against the chip, and cut loose from the program: it imports nothing of
`quickwit_tpu` and reads nothing the program wrote. A corpus is what
`data.py` drew from the seed and kept beside the split: every document's
timestamp, tenant and severity (`*.docs.npz`) and its body terms
(`body-*.tokens.npy`). Postings, document frequencies and lengths are
worked out here from those.

`precision="low"` is the control: the same reference computed one step below
what the configuration states — BM25 in bfloat16 where the configuration
says float32, the timestamp comparison in float32 where the column is int64.
Its answers, put in the program's place, must come out as not correct.
"""

from __future__ import annotations

import numpy as np

DAY = 86_400
K1, B = 1.2, 0.75           # BM25, as tantivy fixes them


class Corpus:
    """One split's documents: timestamp seconds, tenant ids, severity
    ordinals (into `severities`, the sorted names) and body terms
    ([docs, tokens] vocabulary numbers). The body is indexed without term
    frequencies (`record: basic`): a document is the set of its distinct
    terms, so tf is 1 and its length is the size of that set."""

    def __init__(self, split_id: str, docs_path: str, tokens_path: str,
                 severities: list):
        self.split_id = split_id
        with np.load(docs_path) as docs:
            self.ts, self.tenant, self.sev = (docs["ts"], docs["tenant"],
                                              docs["sev"])
        self.num_docs = len(self.ts)
        self.severities = severities
        self._tokens = np.load(tokens_path, mmap_mode="r")
        self.term_parts: dict = {}      # see term_part

    @property
    def ts_low(self) -> np.ndarray:
        """The control's timestamps: float32 where the column is int64."""
        if "ts_low" not in self.term_parts:
            self.term_parts["ts_low"] = self.ts.astype(np.float32)
        return self.term_parts["ts_low"]

    @property
    def body_len(self) -> np.ndarray:
        if "body_len" not in self.term_parts:
            ordered = np.sort(self._tokens, axis=1)
            self.term_parts["body_len"] = 1 + (
                ordered[:, 1:] != ordered[:, :-1]).sum(axis=1)
        return self.term_parts["body_len"]

    def postings(self, field: str, term: str) -> np.ndarray:
        """The documents that hold `term`, ascending."""
        if field == "severity_text":
            return np.nonzero(self.sev == self.severities.index(term))[0]
        number = int(term[len("term"):])    # the body vocabulary: term<number>
        return np.nonzero((self._tokens == number).any(axis=1))[0]

    def lengths(self, field: str, docs: np.ndarray) -> np.ndarray:
        if field == "severity_text":
            return np.ones(len(docs), np.int64)   # raw tokenizer: one token
        return self.body_len[docs]

    def avg_len(self, field: str) -> float:
        return (1.0 if field == "severity_text"
                else float(self.body_len.mean()))


def real_type(precision: str):
    if precision == "low":
        import ml_dtypes
        return ml_dtypes.bfloat16
    return np.float64


def round_bf16(values: np.ndarray) -> np.ndarray:
    """float64 values rounded to the nearest bfloat16 (ties to even), kept
    as float64: the control's sums over 10M lanes stay fast in numpy."""
    bits = values.astype(np.float32).view(np.uint32)
    bits = (bits + 0x7FFF + ((bits >> 16) & 1)) & np.uint32(0xFFFF0000)
    return bits.view(np.float32).astype(np.float64)


def term_part(corpus: Corpus, field: str, text: str, score: bool,
              precision: str):
    """(has[n], docs, BM25 contribution of each doc or None) of one term,
    computed in float64 or, for the control, in bfloat16 (returned as
    float64 either way). Kept per corpus: a cell's terms are few and fixed, its ranges are not."""
    key = (field, text, score, precision)
    if key not in corpus.term_parts:
        n, real = corpus.num_docs, real_type(precision)
        docs = corpus.postings(field, text)
        has = np.zeros(n, bool)
        has[docs] = True
        part = None
        if score and len(docs):
            idf = real(np.log(1.0 + (n - len(docs) + 0.5) / (len(docs) + 0.5)))
            tf = np.ones(len(docs), real)
            norm = (corpus.lengths(field, docs).astype(real)
                    / real(corpus.avg_len(field)))
            part = (idf * real(K1 + 1.0) * tf
                    / (tf + real(K1) * (real(1.0 - B) + real(B) * norm))
                    ).astype(np.float64)
        corpus.term_parts[key] = (has, docs, part)
    return corpus.term_parts[key]


def evaluate(corpus: Corpus, query: dict, score: bool, precision: str):
    """(match mask, BM25 scores or None) of one split for a query
    description `{"must": [(field, term)], "should": [...], "range": (lo_s,
    hi_s)}`: must terms are required and score, should terms score where
    present (and select when there is no must), the half-open timestamp
    range filters without scoring."""
    n = corpus.num_docs
    scores = np.zeros(n, np.float64) if score else None
    mask = None

    def term(field, text):
        has, docs, part = term_part(corpus, field, text, score, precision)
        if part is not None and precision == "low":
            scores[docs] = round_bf16(scores[docs] + part)
        elif part is not None:
            scores[docs] += part
        return has

    for field, text in query.get("must") or ():
        has = term(field, text)
        mask = has if mask is None else mask & has
    any_should = np.zeros(n, bool)
    for field, text in query.get("should") or ():
        any_should |= term(field, text)
    if mask is None:
        mask = any_should if query.get("should") else np.ones(n, bool)
    if query.get("range"):
        lo, hi = query["range"]
        ts = corpus.ts
        if precision == "low":
            ts, lo, hi = corpus.ts_low, np.float32(lo), np.float32(hi)
        mask = mask & (ts >= lo) & (ts < hi)
    if score:
        scores = np.where(mask, scores, 0.0)
    return mask, scores


def parse_interval_s(text: str) -> int:
    return int(text[:-1]) * {"s": 1, "m": 60, "h": 3600, "d": DAY}[text[-1]]


class Reference:
    """Answers over all splits of an index, merged the way a root must:
    counts add, hits order by (key desc, split id asc, doc id asc)."""

    def __init__(self, corpora: list):
        self.corpora = sorted(corpora, key=lambda c: c.split_id)

    def answer(self, shape: dict, query: dict, precision: str = "full") -> dict:
        """What a correct response to `shape` over `query` holds, in the
        form `normalise` gives a served response: only what the shape asks
        for is computed."""
        scored = shape["size"] > 0 and not shape.get("sort")
        parts = [evaluate(c, query, scored, precision) for c in self.corpora]
        masks = [mask for mask, _ in parts]
        want = {"num_hits": int(sum(int(m.sum()) for m in masks)),
                "masks": masks, "scores": [s for _, s in parts], "aggs": {}}
        for name, agg in (shape.get("aggs") or {}).items():
            (kind, spec), = agg.items()
            values = np.concatenate([
                {"timestamp": c.ts, "severity_text": c.sev,
                 "tenant_id": c.tenant}[spec["field"]][m]
                for c, m in zip(self.corpora, masks)])
            if kind == "date_histogram":
                step = parse_interval_s(spec["fixed_interval"])
                keys, counts = np.unique(values // step, return_counts=True)
                # ES buckets align to multiples of the interval since the
                # epoch; keys are milliseconds
                want["aggs"][name] = {int(k) * step * 1000: int(c)
                                      for k, c in zip(keys, counts)}
            elif kind == "terms":
                want["aggs"][name] = {
                    self.corpora[0].severities[o]: int(c)
                    for o, c in enumerate(np.bincount(values)) if c}
            elif kind == "percentiles":
                # the item of 0-based rank floor(q * (n - 1))
                ordered = np.sort(values)
                want["aggs"][name] = {
                    float(p): float(ordered[int(np.floor(
                        p / 100.0 * (len(ordered) - 1)))])
                    for p in spec["percents"]} if len(ordered) else {}
            else:
                raise ValueError(f"the reference has no {kind} aggregation")
        if shape["size"] > 0:
            want["top"] = self.top(want, "ts" if shape.get("sort") else
                                   "score", shape["size"])
        return want

    def top(self, want: dict, key: str, k: int) -> list:
        """[(hit id, key value, timestamp)] of the k best hits by "score" or
        "ts", descending, ties broken by (split id, doc id) ascending."""
        rows = []
        for c, mask, score in zip(self.corpora, want["masks"],
                                  want["scores"]):
            docs = np.nonzero(mask)[0]
            values = score[docs] if key == "score" else c.ts[docs]
            best = np.lexsort((docs, -values))[:k]
            rows += [(-float(values[i]), c.split_id, int(docs[i]),
                      int(c.ts[docs[i]])) for i in best]
        return [(f"{split}:{doc}", -neg, ts)
                for neg, split, doc, ts in sorted(rows)[:k]]

    def render(self, shape: dict, query: dict, precision: str) -> dict:
        """The reference's own answer as a normalised response record: what
        the control puts in the program's place."""
        want = self.answer(shape, query, precision)
        hits = []
        for hit_id, value, ts in want.get("top", ()):
            hit = {"_id": hit_id, "_source": {"timestamp": ts}}
            if shape.get("sort"):
                hit["_score"], hit["sort"] = None, [ts * 1000]
            else:
                hit["_score"] = value
            hits.append(hit)
        aggs = {}
        for name, agg in (shape.get("aggs") or {}).items():
            if "percentiles" in agg:
                aggs[name] = {"values": {str(p): v for p, v
                                         in want["aggs"][name].items()}}
            else:
                aggs[name] = {"buckets": [{"key": k, "doc_count": c} for k, c
                                          in want["aggs"][name].items()]}
        return {"num_hits": want["num_hits"], "hits": hits, "aggs": aggs,
                "failed": []}


def normalise(raw: dict) -> dict:
    """An ES search response as the record `compare` reads."""
    return {"num_hits": raw["hits"]["total"]["value"],
            "hits": raw["hits"]["hits"],
            "aggs": raw.get("aggregations") or {},
            "failed": raw.get("_shards", {}).get("failures", [])}


def compare(shape: dict, query: dict, record: dict, reference: Reference,
            score_tol: float) -> dict:
    """One served answer against the reference. Returns
    `{"wrong": [reasons], "score_rel_err": x or None, "pct_rel_err": x or
    None}`: `wrong` lists what must be exact and is not (counts, buckets,
    sorted ids, membership of the top-k, failed splits); the two gaps are the
    widest relative distance of a returned score, and of a percentile, from
    the reference's. `score_tol` only decides which docs tie at the k-th
    score and may differ."""
    want = reference.answer(shape, query)
    wrong, score_err, pct_err = [], None, None
    if record["failed"]:
        wrong.append(f"failed splits {record['failed']}")
    if record["num_hits"] != want["num_hits"]:
        wrong.append(f"num_hits {record['num_hits']} != {want['num_hits']}")
    for name, agg in (shape.get("aggs") or {}).items():
        got = record["aggs"].get(name)
        if got is None:
            wrong.append(f"aggregation {name} is missing")
        elif "percentiles" in agg:
            pct_err = 0.0
            for percent, exact in want["aggs"][name].items():
                value = {float(p): v for p, v in got["values"].items()
                         }.get(percent)
                if value is None or (exact == 0.0 and value != 0.0):
                    wrong.append(f"{name} p{percent}: {value} against "
                                 f"{exact}")
                elif exact != 0.0:
                    pct_err = max(pct_err, abs(value - exact) / exact)
        else:
            buckets = {(int(b["key"]) if "date_histogram" in agg
                        else b["key"]): b["doc_count"]
                       for b in got["buckets"] if b["doc_count"]}
            if buckets != want["aggs"][name]:
                wrong.append(f"{name} buckets {buckets} != "
                             f"{want['aggs'][name]}")
    hits, top = record["hits"], want.get("top", [])
    if len(hits) != len(top):
        wrong.append(f"{len(hits)} hits returned, reference has {len(top)}")
    elif shape.get("sort"):
        got_ids = [hit["_id"] for hit in hits]
        if got_ids != [hit_id for hit_id, _, _ in top]:
            wrong.append(f"sorted ids {got_ids} != {top}")
        if [hit["sort"][0] for hit in hits] != [ts * 1000 for _, _, ts in top]:
            wrong.append("sort values differ from the reference")
    elif top:
        by_split = {c.split_id: i for i, c in enumerate(reference.corpora)}
        score_err = 0.0
        for hit, (_, ref_kth, _) in zip(hits, top):
            split, _, doc = hit["_id"].rpartition(":")
            i, doc = by_split.get(split), int(doc)
            if i is None or not want["masks"][i][doc]:
                wrong.append(f"hit {hit['_id']} does not match the query")
                continue
            # the doc's own score, and its place in the ordered score list
            for ref in (float(want["scores"][i][doc]), ref_kth):
                score_err = max(score_err, abs(hit["_score"] - ref) / ref)
        kth = top[-1][1]
        above = {hit_id for hit_id, score, _ in top
                 if score > kth * (1 + 2 * score_tol)}
        missing = above - {hit["_id"] for hit in hits}
        if missing:
            wrong.append(f"docs above the k-th score are missing: {missing}")
    for hit in hits:
        source = hit.get("_source") or {}
        for field, text in query.get("must") or ():
            if field in source and source[field] != text:
                wrong.append(f"returned doc {hit['_id']} is not "
                             f"{field}:{text}")
    return {"wrong": wrong, "score_rel_err": score_err,
            "pct_rel_err": pct_err}
