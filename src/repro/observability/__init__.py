from repro.observability.trace import NULL_TRACER, TraceRecorder, captured, reset_captured  # noqa: F401
