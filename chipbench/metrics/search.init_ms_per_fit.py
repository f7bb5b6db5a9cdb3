"""Evolve loop, host (`core/evolve.py` `init_state`): milliseconds per
fit of the window in the program's span ``evolve.init``, the eager ops
that build each search's first parent and evaluate it.

The program keeps its spans' totals while the profiler records
(`repro.observability.trace.captured`); None where it keeps none, or
recorded no such span."""

SPAN = "evolve.init"


def read(run):
    try:
        from repro.observability.trace import captured
    except ImportError:
        return None
    span = captured().get(SPAN)
    fits = run.counters.get("fits")
    return span["seconds"] * 1e3 / fits if span and fits else None
