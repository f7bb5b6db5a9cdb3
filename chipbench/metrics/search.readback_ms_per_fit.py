"""Fit (`core/api.py`): milliseconds per fit of the window in the
program's span ``fit.readback``, the three scalar reads of each
search's result, where the host waits for the device loop.

The program keeps its spans' totals while the profiler records
(`repro.observability.trace.captured`); None where it keeps none, or
recorded no such span."""

SPAN = "fit.readback"


def read(run):
    try:
        from repro.observability.trace import captured
    except ImportError:
        return None
    span = captured().get(SPAN)
    fits = run.counters.get("fits")
    return span["seconds"] * 1e3 / fits if span and fits else None
