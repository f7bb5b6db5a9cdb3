"""Evolve loop (`core/evolve.py`): microseconds the chip was busy, from
the device trace, per generation of the window."""


def read(run):
    gens = run.counters.get("generations")
    return run.trace["busy_s"] * 1e6 / gens if gens else None
