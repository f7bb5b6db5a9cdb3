"""Encoding (`core/encoding.py`): milliseconds per fit of the window in
the program's span ``fit.encode.bits``, one step of ``fit.encode``: each
encoding's input bits (`encode`: every value against its column's edges,
written as its bucket's code).

The program keeps its spans' totals while the profiler records
(`repro.observability.trace.captured`); None where it keeps none, or
recorded no such span."""

SPAN = "fit.encode.bits"


def read(run):
    try:
        from repro.observability.trace import captured
    except ImportError:
        return None
    span = captured().get(SPAN)
    fits = run.counters.get("fits")
    return span["seconds"] * 1e3 / fits if span and fits else None
