"""End-to-end tracing & telemetry for the serving fleet.

The serving stack's aggregate stats (`ServerStats`/`FrontendStats`) say
*how fast*; this package says *where the time went*.  A `TraceRecorder`
(`repro.observability.trace`: bounded ring buffer, injected clock,
zero-cost when disabled) collects one timeline across every layer:

  * request-lifecycle async spans from the front-end — submit → queue
    wait → scheduler fire (with trigger reason) → launch → resolve,
    correlated by trace id;
  * per-tick phase spans from `CircuitServer.tick()` — encode / pack /
    device_put / launch / readback / decode, per shard;
  * kernel-launch spans from the execution backend (via
    `EvalBackend.instrument`);
  * scheduler fires, autoscale decisions, and plan swaps as instants.

Exporters turn the timeline into a Chrome-trace/Perfetto JSON file
(`export_chrome` — open at https://ui.perfetto.dev), a JSONL event log
(`export_jsonl`), or a Prometheus text snapshot of the aggregate stats
(`prometheus_text`).

Attach a recorder at construction (``CircuitServer(..., tracer=...)``);
everything downstream (front-end, autoscale controller, backend proxy)
inherits the server's timeline.  The default is the shared disabled
`NULL_TRACER`, which costs one branch per instrumentation point.
"""
from repro.serve.observability.export import (
    export_chrome,
    export_jsonl,
    prometheus_text,
    to_chrome,
)
from repro.observability.trace import (
    NULL_TRACER,
    TraceEvent,
    TraceRecorder,
)

__all__ = [
    "NULL_TRACER",
    "TraceEvent",
    "TraceRecorder",
    "export_chrome",
    "export_jsonl",
    "prometheus_text",
    "to_chrome",
]
