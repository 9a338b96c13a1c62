"""The chip's published peaks, keyed by `device_kind` (peaks.json, with its
source). A device that is not in the table is an error, never a default."""

from __future__ import annotations

import json
import os

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def lookup(device_kind: str) -> dict:
    with open(PEAKS_FILE) as f:
        table = json.load(f)["by_device_kind"]
    if device_kind not in table:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r} in "
            f"{PEAKS_FILE}; add them with their source, do not guess"
        )
    return table[device_kind]
