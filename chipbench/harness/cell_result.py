"""Run one cell by its kind and print its result line."""
from __future__ import annotations

from harness import result, search

RUNNERS = {"search": search.run}
LISTED = 20  # long gaps and long host events kept in a traced run's info


def run_and_emit(cell, seed: int, seconds: float, traced: bool, *,
                 require_chip: bool = True, control=None) -> dict:
    """``control`` (see ``harness/control.py``) puts the reference in the
    program's place; the benchmark's own runs never pass one."""
    out = RUNNERS[cell.kind](cell, seed, seconds, traced,
                             require_chip=require_chip, control=control)
    device = dict(out["device"])
    breakdown = None
    info = dict(out["info"])
    if traced:
        run = out["run"]
        metrics = result.per_layer(run)
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        breakdown = {"device_ops": run.trace["device_ops"],
                     "idle_gaps": run.trace["idle_gaps"]}
        info["long_idle_gaps"] = run.trace["long_gaps"][:LISTED]
        info["long_host_events"] = sorted(
            run.trace["host_events_over"],
            key=lambda e: -e["length_s"])[:LISTED]
    else:
        names = {m["name"] for m in cell.end_to_end}
        metrics = {"setup_s": (out["setup_s"], "s")}
        metrics.update({k: v for k, v in out["metrics"].items() if k in names})
    correct = result.passes(out["checks"])
    result.emit(correct=correct, attempted=out["attempted"],
                failed=out["failed"], metrics=metrics, device=device,
                checks=out["checks"], info=info, breakdown=breakdown)
    return {"correct": correct, "metrics": metrics, "info": info,
            "checks": out["checks"]}
