"""The table of peaks, keyed by ``device_kind``.  A device that is not in it
is an error, never a default."""

import json
import os


def peaks_for(device_kind: str) -> dict:
    with open(os.path.join(os.path.dirname(__file__), "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peak is recorded for device kind {device_kind!r}; known: "
                       f"{sorted(table)}")
    return table[device_kind]
