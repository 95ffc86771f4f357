"""Published peaks of each chip, keyed by JAX's ``device_kind``.

Both roofline shares use the bf16 peak for every storage dtype: no fp32
matrix peak is published for the v5e, and an fp32 contraction at HIGHEST
precision runs as several bf16 passes on the same units."""
from __future__ import annotations

import json
import os

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")


def peaks_for(device_kind: str, path: str = PEAKS_FILE) -> dict:
    """The table's row for ``device_kind``; a device missing from the
    table is an error, never a default."""
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r} in {path}; known: {sorted(table)}")
    return table[device_kind]
