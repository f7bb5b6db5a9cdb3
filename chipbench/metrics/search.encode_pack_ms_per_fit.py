"""Encoding (`core/encoding.py`): milliseconds per fit of the window in
the program's span ``fit.encode.pack``, one step of ``fit.encode``: each
encoding's packed table, label and class words (`pack_dataset`: rows
into 32-bit words, and their transfer to the device).

The program keeps its spans' totals while the profiler records
(`repro.observability.trace.captured`); None where it keeps none, or
recorded no such span."""

SPAN = "fit.encode.pack"


def read(run):
    try:
        from repro.observability.trace import captured
    except ImportError:
        return None
    span = captured().get(SPAN)
    fits = run.counters.get("fits")
    return span["seconds"] * 1e3 / fits if span and fits else None
