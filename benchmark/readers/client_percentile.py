"""A percentile of the client-side latency (ms) of one shape's requests in
the window, by the load generator's clock. args: shape, percent."""

import numpy as np


def read(run, shape: str, percent: float):
    values = [r["latency_ms"] for r in run.records
              if r["shape"] == shape and r["ok"]]
    return float(np.percentile(values, percent)) if values else None
