"""Fit (`core/api.py`): encodings per fit of the window whose
``fit.encode`` ran while an earlier search of the same fit was
dispatched and not yet read back, from the program's count
``fit.encode_overlapped`` (3 for a fit of four encodings).

The program keeps its counts while the profiler records
(`repro.observability.trace.captured`); None where it keeps none, or
recorded no such count."""

COUNT = "fit.encode_overlapped"


def read(run):
    try:
        from repro.observability.trace import captured
    except ImportError:
        return None
    entry = captured().get(COUNT)
    fits = run.counters.get("fits")
    return entry["count"] / fits if entry and fits else None
