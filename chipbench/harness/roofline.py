"""The chip's peaks, and the least work a frame of an app needs.

Peaks come from ``chipbench/peaks.json``, keyed by JAX's
``device_kind``; a kind that is not there is an error, never a default.

The least work is computed from shapes, whatever implements the app:
every graph input read from HBM once and every output written once, and
the arithmetic the app's plain reference states (its ``min_ops``).  A
later change that removes a pad, splits a kernel or merges two leaves
these numbers alone, so a share of the roofline cannot pass 100% on a
stale count.
"""
from __future__ import annotations

import json
import os

from harness.spec import BENCH_DIR

PEAKS_JSON = os.path.join(BENCH_DIR, "peaks.json")


class UnknownDevice(KeyError):
    pass


def peaks(device_kind: str, path: str = PEAKS_JSON) -> dict:
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table:
        raise UnknownDevice(
            f"no peaks for device kind {device_kind!r} in {path}; "
            f"known: {sorted(table)}")
    return table[device_kind]


def min_bytes(ref, h: int, w: int, itemsize: int = 4) -> int:
    """HBM bytes of one frame: each input read once, each output
    written once."""
    return (len(ref.INPUTS) + len(ref.OUTPUTS)) * h * w * itemsize


def min_seconds(ref, h: int, w: int, peak: dict, itemsize: int = 4) -> float:
    """The least device time for one frame: its bytes at the HBM peak or
    its operations at the compute peak, whichever takes longer."""
    return max(min_bytes(ref, h, w, itemsize) / peak["hbm_bytes_per_s"],
               ref.min_ops(h, w) / peak["flops_per_s"])
