"""Evolve loop, host (`core/evolve.py` `evolve`): milliseconds per fit
of the window in the program's span ``evolve.loop``, the
`lax.while_loop` call of each search: trace, lower, compile or cache
load, and enqueue.

The program keeps its spans' totals while the profiler records
(`repro.observability.trace.captured`); None where it keeps none, or
recorded no such span."""

SPAN = "evolve.loop"


def read(run):
    try:
        from repro.observability.trace import captured
    except ImportError:
        return None
    span = captured().get(SPAN)
    fits = run.counters.get("fits")
    return span["seconds"] * 1e3 / fits if span and fits else None
