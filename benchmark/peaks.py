"""The table of device peaks (``peaks.json``), keyed by ``device_kind``.
A device that is not in the table is an error, not a default."""

from __future__ import annotations

import json
import os


def of(device_kind: str) -> dict:
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "peaks.json")
    with open(path) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(
            f"no peaks for device_kind {device_kind!r} in peaks.json "
            f"(known: {sorted(table)}); a share of a peak needs one")
    return table[device_kind]
