"""The table of chip peaks, keyed by ``device_kind``.  A device that is
not in the table is an error, never a default."""

from __future__ import annotations

import json
import os

_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "peaks.json")


def peaks(device_kind: str) -> dict:
    with open(_PATH) as f:
        table = json.load(f)
    row = table.get(device_kind)
    if not isinstance(row, dict):
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{_PATH}; add a row with its public source")
    return row
