"""The chip: its published peaks, and the bytes a coding kernel must move.

``peaks.json`` holds each device kind's published peaks with their source;
a kind that is not in it is an error, never a default. The byte counts
are worked out from shapes alone, so they read the same whatever kernel a
later change puts behind an operation.
"""
from __future__ import annotations

import json
import os

PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peak(kind: str, what: str) -> float:
    with open(PEAKS) as f:
        table = json.load(f)
    if kind not in table:
        raise KeyError(f"no published peaks for device kind {kind!r} in "
                       f"{PEAKS}; add them with their source")
    return float(table[kind][what])


def gf_apply_bytes(in_rows: int, out_rows: int, block_bytes: int) -> int:
    """HBM bytes of one GF(2^l) matrix applied across a block: every input
    row read once and every output row written once. Packing l-bit words
    into 32-bit lanes keeps the byte count of the words."""
    return (in_rows + out_rows) * block_bytes
