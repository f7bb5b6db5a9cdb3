#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

``--trace 0`` measures the cell's end-to-end metrics; ``--trace 1`` runs
the same window under the profiler and reports its per-layer metrics,
the device's busy time and a breakdown.  Cells, configurations, traffic
mixes and metrics are found by name from ``BENCHMARK.json``.  Exits 2,
printing no result, where JAX finds no TPU or too few chips.
"""
from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from harness import env  # noqa: E402

env.prepare()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from harness import spec

    cell = spec.load_cell(args.workload)
    env.configure_jax()
    from harness import cell_result

    try:
        cell_result.run_and_emit(cell, args.seed, args.seconds,
                                 bool(args.trace))
    except env.NoChip as err:
        env.log(f"no chip for {cell.name}: {err}")
        return 2
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # skip interpreter and runtime teardown: the result is out, and no
    # process or file is left open by the run
    os._exit(code)
