"""Bytes an operation needs, and the table of peaks.

needed_bytes is the least an implementation of the operation could move,
whatever implements it: 4 B for each edge the plain reference has to read
and 8 B for each node it visits (a uid in, a mark or a distance out)."""

from __future__ import annotations

import json
import os

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "peaks.json")


def needed_bytes(stats: dict) -> int:
    return 4 * int(stats["edges"]) + 8 * int(stats["nodes"])


def peaks(device_kind: str) -> dict:
    with open(_PEAKS) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device_kind {device_kind!r} in "
                       f"{_PEAKS}: add the device with its source")
    return table[device_kind]
