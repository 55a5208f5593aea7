"""Bytes of split arrays a request needs from HBM, reckoned from the split's
footer and the query alone: what the plain reference touches for it.

- ids and tfs of each term's postings (the padded run the split stores),
- the field norms of a text field where the request scores,
- values and presence of every fast column named by the range, the sort or
  an aggregation.

It reads the same work whatever implements it. A stacked dispatch reads a
shared column once for several requests, so a group-aware count has to
replace this one before the share nears 100 %.
"""

from __future__ import annotations


def array_bytes(footer: dict, name: str) -> int:
    for array in footer["arrays"]:
        if array["name"] == name:
            return array["nbytes"]
    return 0


def column_bytes(footer: dict, field: str) -> int:
    """A fast column as the split stores it: dictionary ordinals, or values
    (packed where the split packs them) and presence."""
    ordinals = array_bytes(footer, f"col.{field}.ordinals")
    if ordinals:
        return ordinals
    values = (array_bytes(footer, f"col.{field}.packed")
              or array_bytes(footer, f"col.{field}.values"))
    return values + array_bytes(footer, f"col.{field}.present")


def posting_bytes(footer: dict, split, field: str, term: str) -> int:
    """ids + tfs of one term's padded posting run. `split` gives the term
    dictionary's arrays (`array(name)`)."""
    if field == "body":     # the generator's vocabulary: term<number>
        ordinal = int(term[len("term"):])
    else:
        ordinal = split.strings(f"col.{field}.dict").index(term)
    length = int(split.array(f"inv.{field}.terms.post_len")[ordinal])
    ids = split.array(f"inv.{field}.postings.ids").dtype.itemsize
    tfs = split.array(f"inv.{field}.postings.tfs").dtype.itemsize
    return length * (ids + tfs)


def request_bytes(shape: dict, splits: list) -> int:
    """Bytes one request of `shape` needs, over all splits of the index."""
    total = 0
    scores = shape["size"] > 0 and not shape.get("sort")
    for split in splits:
        footer = split.footer
        fields = set()
        for field, term in list(shape["must"]) + list(shape["should"]):
            total += posting_bytes(footer, split, field, term)
            if scores and field not in fields:
                total += array_bytes(footer, f"inv.{field}.fieldnorm")
            fields.add(field)
        columns = set()
        if shape["range"] or shape.get("sort") == "timestamp_desc":
            columns.add("timestamp")
        for agg in (shape.get("aggs") or {}).values():
            (_, spec), = agg.items()
            columns.add(spec["field"])
        total += sum(column_bytes(footer, column) for column in columns)
    return total
