#!/usr/bin/env python3
"""Run a cell with its control in the program's place (see
``harness/control.py``), once for each seed, in one process, at the
cell's own size.

    python3 chipbench/tools/control.py --workload search-higgs \
        --seeds 2147483901,2147483902,2147483903 [--seconds 30]

Each run prints the cell's own result line, ``correct`` and the checks
with their limits; a sound control reads ``correct`` false.  The
benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from harness import env  # noqa: E402

env.prepare()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float)
    args = ap.parse_args(argv)

    from harness import spec

    cell = spec.load_cell(args.workload)
    if args.seconds is None:
        with open(spec.BENCHMARK) as f:
            args.seconds = json.load(f)["run_seconds"]
    env.configure_jax()
    from harness import cell_result, control

    for seed in (int(s) for s in args.seeds.split(",")):
        cell_result.run_and_emit(cell, seed, args.seconds, False,
                                 control=control.bfloat16_fitness)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
