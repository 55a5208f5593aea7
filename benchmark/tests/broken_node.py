"""A node whose answers are altered where they are produced: every fifth ES
search response reports one hit too many. For the test that a broken timed
path makes `correct` come out false; never used by a benchmark run."""

import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.dirname(BENCH))

import node_main
from quickwit_tpu.serve.rest import RestServer

_original = RestServer._es_search_response
_count = [0]


def _altered(response, request, params=None):
    out = _original(response, request, params)
    _count[0] += 1
    if _count[0] % 5 == 0:
        out["hits"]["total"]["value"] += 1
    return out


RestServer._es_search_response = staticmethod(_altered)

if __name__ == "__main__":
    sys.exit(node_main.main(sys.argv[1:]))
