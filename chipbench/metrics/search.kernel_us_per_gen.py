"""Kernel (`eval_population_kernel`): microseconds of its device events
per generation of the window."""

KERNEL = "eval_population_kernel"


def read(run):
    k = run.trace["kernels"].get(KERNEL)
    gens = run.counters.get("generations")
    return k["seconds"] * 1e6 / gens if k and gens else None
