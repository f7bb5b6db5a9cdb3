"""Evolve loop (`core/evolve.py` `evolve`): traces of the search loop's
body per fit of the window, from the program's count
``evolve.loop_traces``.

The program keeps its counts while the profiler records
(`repro.observability.trace.captured`); None where it keeps none, or
recorded no such count."""

COUNT = "evolve.loop_traces"


def read(run):
    try:
        from repro.observability.trace import captured
    except ImportError:
        return None
    entry = captured().get(COUNT)
    fits = run.counters.get("fits")
    return entry["count"] / fits if entry and fits else None
