"""Benchmark harness entry point — one function per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run            # quick panel
    BENCH_FULL=1 PYTHONPATH=src python -m benchmarks.run  # full Table-1 sweep

Prints ``name,us_per_call,derived`` CSV; JSON artifacts land in
experiments/results/.
"""
from __future__ import annotations

import os
import sys
import time
import traceback


def main() -> None:
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
    quick = os.environ.get("BENCH_FULL", "0") != "1"

    from repro.utils.compile_cache import use_compile_cache

    use_compile_cache()

    from benchmarks import (
        autotc_scaling,
        fig8_design_space,
        fig9_11_baselines,
        fig10_crossval,
        fig12_400gates,
        hw_costs,
        roofline,
        throughput,
    )

    suites = [
        ("fig8a", lambda: fig8_design_space.fig8a(quick)),
        ("fig8bc", lambda: fig8_design_space.fig8bc(quick)),
        ("fig9_11", lambda: fig9_11_baselines.run(quick)),
        ("fig10", lambda: fig10_crossval.run(quick)),
        ("fig12", lambda: fig12_400gates.run(quick)),
        ("hw_costs", lambda: hw_costs.run(quick)),
        ("throughput", lambda: throughput.run(quick)),
        ("autotc_scaling", lambda: autotc_scaling.run(quick)),
        ("roofline", lambda: roofline.run(quick)),
    ]
    print("name,us_per_call,derived")
    failures = 0
    for name, fn in suites:
        t0 = time.time()
        try:
            for line in fn():
                print(line, flush=True)
        except Exception:  # noqa: BLE001
            failures += 1
            print(f"{name},0,ERROR", flush=True)
            traceback.print_exc()
        print(f"# {name} done in {time.time()-t0:.1f}s", flush=True)
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
