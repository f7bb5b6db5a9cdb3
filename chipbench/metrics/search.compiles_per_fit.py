"""Fit layer (`AutoTinyClassifier.fit`): JAX compile requests in the
window, persistent-cache loads included, per fit.  The program re-traces
each encoding's search loop on every fit."""


def read(run):
    fits = run.counters.get("fits")
    return run.counters["compile_requests"] / fits if fits else None
