"""The table of device peaks (``peaks.json``), keyed by ``device_kind``."""

from __future__ import annotations

import json
import os

_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks_for(device_kind: str) -> dict:
    """The published peaks of one chip of this kind. A kind that is not in
    the table is an error, never a default: a made-up peak would turn every
    utilisation computed from it into a made-up number."""
    with open(_PATH) as f:
        table = json.load(f)
    entry = table.get(device_kind)
    if not isinstance(entry, dict):
        known = sorted(k for k in table if not k.startswith("_"))
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{_PATH}; known: {known}")
    return entry
