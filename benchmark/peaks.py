"""Peaks of the chips the benchmark may run on, keyed by `device_kind` as JAX
reports it. Copied from `bench.py::_PEAK_HBM` (the yardstick may not depend
on a file later PRs can change). A kind that is not here is an error, never
a default.

Sources: Google Cloud documentation, "TPU v5e" (16 GB of HBM at 819 GB/s per
chip), and the "TPU v4", "TPU v5p" and "TPU v6e" system-architecture pages
for the others.
"""

from __future__ import annotations

PEAKS = {
    "TPU v4": {"hbm_bytes_per_s": 1228e9},
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9},    # v5e
    "TPU v5": {"hbm_bytes_per_s": 2765e9},        # v5p
    "TPU v6 lite": {"hbm_bytes_per_s": 1640e9},   # v6e / Trillium
}


class UnknownDevice(Exception):
    """The device kind has no entry in the peaks table."""


def peak(device_kind: str, what: str) -> float:
    try:
        return PEAKS[device_kind][what]
    except KeyError:
        raise UnknownDevice(
            f"no {what} peak for device kind {device_kind!r}; known kinds: "
            f"{sorted(PEAKS)}") from None
