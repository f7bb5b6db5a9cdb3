"""Encoding (`core/encoding.py`): milliseconds per fit of the window in
the program's span ``fit.encode.edges``, one step of ``fit.encode``: the
bucket edges of each encoding (`fit_encoder`: each column's min and max,
or its quantiles, a sort of the column).

The program keeps its spans' totals while the profiler records
(`repro.observability.trace.captured`); None where it keeps none, or
recorded no such span."""

SPAN = "fit.encode.edges"


def read(run):
    try:
        from repro.observability.trace import captured
    except ImportError:
        return None
    span = captured().get(SPAN)
    fits = run.counters.get("fits")
    return span["seconds"] * 1e3 / fits if span and fits else None
