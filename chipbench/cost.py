"""The least bytes an ingest of one block has to move, and the chip's
published peaks.

The ingest of a block changes only the rows its items reach: each such
row's ids, counts and errors (three int32 arrays of ``k`` counters,
padded to the 128-lane tile) are read once and written once, and the
block itself (an int32 key and an int32 weight per slot) is read once.
That is the same work whichever engine or kernel runs the ingest, so a
kernel's share of its roofline is these bytes over its device time at
the chip's HBM bandwidth.
"""
from __future__ import annotations

import json
import os

import numpy as np

LANES = 128
_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "peaks.json")


def rows_reached(keys: np.ndarray, weights: np.ndarray,
                 item_bits: int) -> int:
    """Distinct tenant rows holding a nonzero weight of the block."""
    live = weights != 0
    return len(np.unique(keys[live].astype(np.int64) >> item_bits))


def block_least_bytes(keys: np.ndarray, weights: np.ndarray,
                      item_bits: int, k: int) -> int:
    k_pad = -(-k // LANES) * LANES
    rows = rows_reached(keys, weights, item_bits)
    return rows * k_pad * 3 * 4 * 2 + len(keys) * 8


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; an unknown kind is an
    error, not a default."""
    with open(_PEAKS) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(k for k in table if k[0] != '_')}")
    return table[device_kind]
