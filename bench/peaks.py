"""Per-chip peaks, keyed by the ``device_kind`` JAX reports (``peaks.json``)."""
from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """The peaks of one chip of ``device_kind``; a kind that is not in
    the table is an error, never a default."""
    with open(PEAKS_FILE) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE.name}; known: {', '.join(sorted(table))}")
    return table[device_kind]
