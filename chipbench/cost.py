"""The chip's published peaks, and the lane tile a row pads to.

A kernel's share of its roofline is the least bytes (or operations) its
call has to move, which each kind computes from its own layout
(``least_bytes`` of ``chipbench/kinds/<kind>.py``), over its device
time at the chip's peak.
"""
from __future__ import annotations

import json
import os

LANES = 128
_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "peaks.json")


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; an unknown kind is an
    error, not a default."""
    with open(_PEAKS) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(k for k in table if k[0] != '_')}")
    return table[device_kind]
