"""Published per-chip peaks, keyed by ``device_kind``.  A device that is
not in ``peaks.json`` is an error, never a default: a utilization against a
made-up peak is worse than none.  A later PR adds a chip by adding an entry.
"""

from __future__ import annotations

import json
from pathlib import Path

_TABLE = Path(__file__).with_name("peaks.json")


def peaks_for(device_kind: str) -> dict:
    table = json.loads(_TABLE.read_text())
    if device_kind not in table:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r} in {_TABLE}; "
            "add an entry with its source before reporting a utilization"
        )
    return table[device_kind]
