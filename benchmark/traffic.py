"""The one traffic generator: reads a mix (`traffic/<name>.json`) and its
shapes (`shapes/<shape>.json`) and draws requests from a seed.

A mix names its shapes with weights, the number of closed-loop clients, and
the time-range parameters. Each client owns one shape, as a dashboard's
panel does: the clients are shared out by the weights, and a client takes
the stream's next request of its shape. Requests come in blocks of `block`:
inside a block every shape appears in exact proportion to its weight and
takes its range widths from a fixed, evenly spaced grid, so that every seed
sends the same set of sizes in another order; the seed draws the order and
where each range starts (whole seconds). No two requests of a stream are
equal: a repeated request is a leaf-cache hit and never reaches the device.
"""

from __future__ import annotations

import json
import os
import threading

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load_json(*parts: str) -> dict:
    with open(os.path.join(HERE, *parts)) as fh:
        return json.load(fh)


def load_mix(name: str) -> dict:
    mix = load_json("traffic", f"{name}.json")
    mix["shape_files"] = {shape: load_json("shapes", f"{shape}.json")
                          for shape in mix["shapes"]}
    return mix


def block_slots(mix: dict) -> list:
    """[(shape, width_s)] of one block, before shuffling."""
    block, spec = mix["block"], mix["range"]
    slots = []
    for shape, weight in mix["shapes"].items():
        count = round(weight * block)
        if count < 1 or abs(count - weight * block) > 1e-9:
            raise ValueError(f"weight {weight} of {shape} is not a whole "
                             f"number of a block's {block} requests")
        span = spec["width_max_s"] - spec["width_min_s"]
        slots += [(shape, int(spec["width_min_s"] + span * (j + 0.5) / count))
                  for j in range(count)]
    if len(slots) != block:
        raise ValueError(f"the weights of {mix['name']} do not fill a block")
    return slots


class RequestStream:
    """Requests number 0, 1, 2, ... of one seeded stream, made block by
    block on demand; `take(shape)` hands a client the next of its shape."""

    def __init__(self, mix: dict, seed: int, stream: int):
        self.mix = mix
        self._slots = block_slots(mix)
        self._rng = np.random.default_rng([int(seed), int(stream)])
        self._lock = threading.Lock()
        self._seen: set = set()
        self.requests: list = []
        self._next_of = {shape: 0 for shape in mix["shapes"]}
        self._of: dict = {shape: [] for shape in mix["shapes"]}

    def _grow(self) -> None:
        spec = self.mix["range"]
        for i in self._rng.permutation(len(self._slots)):
            shape, width = self._slots[i]
            while True:
                lo = spec["origin_s"] + int(
                    self._rng.integers(0, spec["lo_max_s"] + 1))
                if (shape, lo, width) not in self._seen:
                    break
            self._seen.add((shape, lo, width))
            self.requests.append({"index": len(self.requests),
                                  "shape": shape, "lo": lo, "hi": lo + width})
            self._of[shape].append(self.requests[-1])

    def take(self, shape: str) -> dict:
        """The stream's next request of `shape`."""
        with self._lock:
            while self._next_of[shape] >= len(self._of[shape]):
                self._grow()
            self._next_of[shape] += 1
            return self._of[shape][self._next_of[shape] - 1]


def client_shapes(mix: dict) -> list:
    """The shape each client owns: the clients shared out by weight."""
    clients = mix["clients"]
    owners = []
    for shape, weight in mix["shapes"].items():
        count = round(weight * clients)
        if count < 1 or abs(count - weight * clients) > 1e-9:
            raise ValueError(f"weight {weight} of {shape} is not a whole "
                             f"number of the {clients} clients")
        owners += [shape] * count
    return owners


def shape_query(shape: dict, lo: int, hi: int) -> dict:
    """The query description the reference evaluates (see reference.py)."""
    return {"must": [tuple(t) for t in shape["must"]],
            "should": [tuple(t) for t in shape["should"]],
            "range": (lo, hi) if shape["range"] else None}


def es_body(shape: dict, lo: int, hi: int, profile: bool = False,
            timeout_s: int = None) -> dict:
    """The ES DSL request of a shape over [lo, hi) (unix seconds: bare
    numbers on a datetime field). Without `timeout_s` the request carries no
    deadline of its own and the product's default applies."""
    def term(field, text):
        return {"term": {field: {"value": text}}}
    must = [term(*t) for t in shape["must"]]
    should = [term(*t) for t in shape["should"]]
    filters = ([{"range": {"timestamp": {"gte": lo, "lt": hi}}}]
               if shape["range"] else [])
    if len(must) == 1 and not should and not filters:
        query = must[0]
    elif not must and not should and len(filters) == 1:
        query = filters[0]
    elif not must and not should and not filters:
        query = {"match_all": {}}
    else:
        query = {"bool": {**({"must": must} if must else {}),
                          **({"should": should} if should else {}),
                          **({"filter": filters} if filters else {})}}
    body = {"query": query, "size": shape["size"]}
    if shape.get("aggs"):
        body["aggs"] = shape["aggs"]
    if shape.get("sort") == "timestamp_desc":
        body["sort"] = [{"timestamp": {"order": "desc"}}]
    if profile:
        body["profile"] = True
    if timeout_s is not None:
        body["timeout"] = f"{timeout_s}s"
    return body
