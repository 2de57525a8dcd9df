"""The one table of device peaks, keyed by ``device_kind``. A kind that is not
in the table is an error, never a default."""

from __future__ import annotations

import json
import os


def peaks_for(device_kind: str) -> dict:
    with open(os.path.join(os.path.dirname(__file__), "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind == "source":
        raise KeyError(
            f"device kind {device_kind!r} is not in benchmark/peaks.json: add its "
            "published peaks with their source before reporting a share of them"
        )
    return table[device_kind]
