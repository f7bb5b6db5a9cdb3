"""The result line a run ends with, and the per-layer readings.

Standard output's last line is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, in a traced run
``breakdown``, then ``info`` (window accounting) and, last, ``checks``:
every number the correctness comparison used, beside its limit.  The
same checks are the last lines on standard error.
"""
from __future__ import annotations

import dataclasses
import json
import sys

from harness import env, spec


@dataclasses.dataclass
class Run:
    """What a traced run hands the per-layer readers
    (``chipbench/metrics/<name>.py``, each ``read(run) -> float | None``)."""

    cell: "spec.Cell"
    device_kind: str
    trace: dict                  # harness.trace.reduce_trace of the window
    counters: dict               # counts taken over the window


def per_layer(run: Run) -> dict:
    out = {}
    for m in run.cell.per_layer:
        value = spec.reader(m["name"])(run)
        if value is not None:
            out[m["name"]] = (float(value), m["unit"])
    return out


def emit(*, correct: bool, attempted: int, failed: int, metrics: dict,
         device: dict, checks: dict, info: dict,
         breakdown: "dict | None" = None) -> None:
    """Print the result: info, then the checks as the last stderr lines,
    then the JSON line as the last stdout line."""
    for key, value in info.items():
        env.log(f"{key} = {value}")
    line = {
        "correct": bool(correct),
        "attempted": int(attempted),
        "failed": int(failed),
        "metrics": {k: {"value": v, "unit": u} for k, (v, u) in metrics.items()},
        "device": device,
    }
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["info"] = info
    line["checks"] = {k: {"value": v, "limit": lim}
                      for k, (v, lim) in checks.items()}
    for k, (v, lim) in checks.items():
        env.log(f"check {k} = {v} (limit {lim})")
    sys.stderr.flush()
    print(json.dumps(line, default=float), flush=True)


def passes(checks: dict) -> bool:
    return all(v <= lim for v, lim in checks.values())
