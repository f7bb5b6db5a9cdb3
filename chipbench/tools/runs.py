#!/usr/bin/env python3
"""Run one cell several times, each run a process of its own, and
summarise the spread of every metric.

    python3 chipbench/tools/runs.py --workload search-higgs \
        --seeds 2147483701,2147483702,2147483703 [--seconds 30] [--trace 0] \
        [--out runs.jsonl] [--timeout 400]

Each run's result line (with its seed, exit code and wall time) is
appended to ``--out``.  The spread of a metric is the distance between
its first and third quartile (`statistics.quantiles`) over its median.
This process never imports JAX, so each child has the chips to itself.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
INFO = ("gc_max_ms", "window_compiles", "window_cache_loads", "fits",
        "generations", "window_s", "window_compile_s", "seed_fit_compiles",
        "window_fit_generations", "seed_fit_generations")


def spread(values):
    if len(values) < 2:
        return float("nan")
    q = statistics.quantiles(values, n=4)
    med = statistics.median(values)
    return (q[2] - q[0]) / med if med else float("nan")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--out")
    ap.add_argument("--timeout", type=float, default=400)
    args = ap.parse_args(argv)
    if args.seconds is None:
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            args.seconds = json.load(f)["run_seconds"]
    rows = []
    for seed in args.seeds.split(","):
        cmd = [sys.executable, os.path.join(BENCH, "run.py"),
               "--workload", args.workload, "--seed", seed,
               "--seconds", str(args.seconds), "--trace", str(args.trace)]
        t0 = time.perf_counter()
        try:
            p = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True,
                               timeout=args.timeout)
            rc, out, err = p.returncode, p.stdout, p.stderr
        except subprocess.TimeoutExpired as e:
            rc, out, err = 124, e.stdout or "", e.stderr or ""
            out = out if isinstance(out, str) else out.decode()
            err = err if isinstance(err, str) else err.decode()
        wall = time.perf_counter() - t0
        try:
            res = json.loads(out.strip().splitlines()[-1])
        except (IndexError, ValueError):
            res = None
        row = {"workload": args.workload, "seed": int(seed),
               "trace": args.trace, "seconds": args.seconds, "rc": rc,
               "wall_s": wall, "result": res,
               "stderr_tail": err[-3000:] if res is None or rc else ""}
        rows.append(row)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(row) + "\n")
        info = (res or {}).get("info", {})
        brief = {k: info[k] for k in INFO if k in info}
        print(f"== seed {seed} rc={rc} wall={wall:.1f}s "
              f"correct={None if res is None else res['correct']} "
              f"failed={None if res is None else res['failed']} "
              f"metrics={ {k: v['value'] for k, v in (res or {}).get('metrics', {}).items()} } "
              f"info={brief}", flush=True)
        if res is None or rc:
            print(err[-3000:], flush=True)
    metrics = {}
    for row in rows:
        for k, v in ((row["result"] or {}).get("metrics") or {}).items():
            metrics.setdefault(k, []).append(v["value"])
    for k, vals in sorted(metrics.items()):
        print(f"   {args.workload} {k}: median {statistics.median(vals)} "
              f"spread {spread(vals)} values {vals}", flush=True)
    print(f"   {args.workload} correct: "
          f"{[(r['result'] or {}).get('correct') for r in rows]}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
