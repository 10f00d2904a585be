"""The table of peaks (``peaks.json``), keyed by ``device_kind``."""

from __future__ import annotations

import json
import os

_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "peaks.json")


def peaks_for(device_kind: str) -> dict:
    """The row of ``device_kind``, matched exactly; an unknown kind raises,
    so that a utilization is never computed against a guess."""
    with open(_PATH) as f:
        table = json.load(f)
    row = table.get(device_kind)
    if not isinstance(row, dict):
        known = sorted(k for k, v in table.items() if isinstance(v, dict))
        raise KeyError(
            f"no peaks on record for device_kind {device_kind!r}; add a "
            f"row with its source to perfbench/peaks.json (known: {known})")
    return row


def peak(row: dict, key: str) -> float:
    """``row[key]``, or an error where the table holds no such figure."""
    value = row.get(key)
    if value is None:
        raise KeyError(f"perfbench/peaks.json has no {key!r} for this "
                       f"device kind (source: {row.get('source')})")
    return float(value)
