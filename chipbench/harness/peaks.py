"""Published peaks of the chips the benchmark runs on, keyed by JAX's
``device_kind``.  A chip that is not in the table is an error, never a
default: a share of a guessed peak is no measurement."""
from __future__ import annotations

import json
import os

TABLE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peak(device_kind: str, key: str) -> float:
    with open(TABLE) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r} in {TABLE}; "
            "add them with their source"
        )
    return float(table[device_kind][key])
