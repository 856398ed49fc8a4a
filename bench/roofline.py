"""Peaks of a chip, and the work a vet launch needs, from window lengths.

``bench/peaks.json`` holds the published peaks of each chip by the
``device_kind`` JAX reports, with their source.  A chip that is not in the
table is an error, not a default.

The vet launch's work is counted from the windows it vets, not from the
padded shapes that carry them, so that a launch that pads less, or gathers
otherwise, is judged on the same yardstick:

- bytes: each window's records read once as float32, and its results
  (vet, EI, OC, PR, cut) written once as 4-byte values;
- operations: per window of ``n`` records, ``n * ceil(log2 n)``
  comparisons for a sort and ``SCAN_OPS`` element operations for the log,
  the three prefix sums, the two-segment landscape, the extrapolation and
  the sums.  The vector unit's peak is not published, so the roofline is
  taken on bytes alone.
"""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np

PEAKS = Path(__file__).resolve().parent / "peaks.json"
RECORD_BYTES = 4
RESULT_VALUES = 5
SCAN_OPS = 30


def peaks_for(kind: str, path: Path = PEAKS) -> dict:
    table = json.loads(path.read_text())["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in {path.name}; "
                       f"it has {sorted(table)}")
    return table[kind]


def vet_launch_bytes(lengths) -> int:
    lengths = np.asarray(lengths, np.int64)
    return int(lengths.sum() * RECORD_BYTES
               + lengths.size * RESULT_VALUES * 4)


def vet_launch_ops(lengths) -> int:
    n = np.asarray(lengths, np.int64)
    return int((n * np.ceil(np.log2(np.maximum(n, 2))) + SCAN_OPS * n).sum())


def hbm_seconds(nbytes: float, peaks: dict) -> float:
    return float(nbytes) / float(peaks["hbm_bytes_per_s"])
