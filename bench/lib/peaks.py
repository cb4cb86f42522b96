"""Published per-chip peaks, keyed by ``device_kind`` (``bench/peaks.json``).
A device that is not in the table is an error, never a default."""
from __future__ import annotations

import json
import os

PEAKS_FILE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "peaks.json")


class UnknownDevice(KeyError):
    pass


def peaks_for(device_kind: str, path: str = PEAKS_FILE) -> dict:
    with open(path) as f:
        table = json.load(f)
    try:
        return table["devices"][device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no published peaks for device kind {device_kind!r} in "
            f"{path}; known: {sorted(table['devices'])}") from None
