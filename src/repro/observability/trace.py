"""TraceRecorder: a bounded, fake-clock-testable event timeline.

The measurement core of the program's observability.  One recorder holds
one timeline: every layer that participates (the micro-batching server's
tick phases, the async front-end's request lifecycle, the deadline
scheduler's fires, the autoscale controller's swaps, the execution
backend's kernel launches) appends typed events — span begin/end,
instants, counters, and cross-thread async spans — into one bounded ring
buffer, so a single export shows *where a request's time went* across
every layer at once.

The search (`AutoTinyClassifier.fit`, `evolve`) records through the
shared `NULL_TRACER`: its spans and counter cost one branch each until a
JAX profiler capture is recording.  While one is, every scoped `span()`
— on any recorder, enabled or not — is also a
`jax.profiler.TraceAnnotation` on the profiler's host plane, on the same
clock as the device's operations, and adds its count and seconds to a
process-wide capture table (`captured()`); `count()` adds to the same
table.  Only the scoped `span()` is mirrored: `begin`/`end` and async
spans may cross functions and threads, which a profiler annotation
cannot.

Design constraints, in order:

  * **Zero-cost when disabled.**  Production servers construct against
    the shared `NULL_TRACER`; every record method is one attribute load
    and one branch, and `span()` returns a single shared no-op context
    manager — no event object, no deque append, no per-call allocation —
    while no profiler capture is recording (one more call: the
    profiler's own `TraceMe.is_enabled` gate).
    The serving benchmarks measure this (``trace_overhead_pct`` in
    BENCH_serve.json) and `check_bench.py` gates it.
  * **Bounded.**  Events live in a ``deque(maxlen=capacity)`` ring: a
    long-running server can trace forever in constant memory, dropping
    the *oldest* events.  ``dropped`` counts evictions — exports never
    pretend the window was complete when it was not.
  * **Fake-clock-testable.**  Time enters only through the injected
    ``clock`` callable (default `time.perf_counter`), exactly like the
    `DeadlineScheduler` — the trace tests drive a fake clock and assert
    on exact timestamps.
  * **Thread-tolerant.**  Appends from the caller thread, the background
    driver thread, and a control loop interleave freely: each append is
    a single C-level ``deque.append`` under the GIL, and snapshots copy
    the ring before iterating.  Duration (B/E) spans nest per *track*
    (one per thread by default), so stack discipline holds per track.

Event phases follow the Chrome trace-event vocabulary so the exporter
(`repro.serve.observability.export`) is a straight mapping:

  ``B``/``E``  span begin/end (same-thread duration, stack-nested)
  ``i``        instant
  ``C``        counter sample
  ``b``/``n``/``e``  async span begin / instant / end, correlated by
               ``id`` — how one request's lifecycle threads through the
               submit thread, the scheduler thread, and the launch.
"""
from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import Callable, NamedTuple

from jax._src.lib import _profiler
from jax.profiler import TraceAnnotation

# the profiler's own gate: true while a capture would record an annotation
_capturing = _profiler.TraceMe.is_enabled

# name -> [count, seconds] over every capture since `reset_captured`
_captured: dict = {}
_captured_lock = threading.Lock()


def _add_captured(name: str, n: int, seconds: float) -> None:
    with _captured_lock:
        entry = _captured.setdefault(name, [0, 0.0])
        entry[0] += n
        entry[1] += seconds


def captured() -> dict:
    """``{name: {"count", "seconds"}}``: the spans and counts recorded
    while a profiler capture was on, since the last `reset_captured`."""
    with _captured_lock:
        return {name: {"count": n, "seconds": sec}
                for name, (n, sec) in _captured.items()}


def reset_captured() -> None:
    with _captured_lock:
        _captured.clear()


class TraceEvent(NamedTuple):
    """One timeline event (timestamps in the recorder's clock domain)."""

    ts: float           # seconds, recorder clock
    phase: str          # "B" | "E" | "i" | "C" | "b" | "n" | "e"
    name: str
    cat: str            # category (export filter; required for async)
    track: str          # logical lane — exported as a thread id
    args: "dict | None"
    id: "int | None"    # async-span correlation id (b/n/e only)


class _NoopSpan:
    """Shared do-nothing context manager — the disabled `span()` path."""

    __slots__ = ()

    def __enter__(self) -> "_NoopSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False


_NOOP_SPAN = _NoopSpan()


class _Span:
    """Context manager emitting a matched B/E pair on one track and,
    while a profiler capture records, a profiler annotation and a row of
    the capture table."""

    __slots__ = ("_rec", "_name", "_cat", "_track", "_args", "_annotation",
                 "_t0")

    def __init__(self, rec, name, cat, track, args, capture):
        self._rec = rec
        self._name = name
        self._cat = cat
        self._track = track
        self._args = args
        self._annotation = TraceAnnotation(name) if capture else None

    def __enter__(self) -> "_Span":
        self._rec.begin(
            self._name, cat=self._cat, track=self._track,
            **(self._args or {}),
        )
        if self._annotation is not None:
            self._annotation.__enter__()
            self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        if self._annotation is not None:
            seconds = time.perf_counter() - self._t0
            self._annotation.__exit__(*exc)
            _add_captured(self._name, 1, seconds)
        self._rec.end(self._name, cat=self._cat, track=self._track)
        return False


class TraceRecorder:
    """Bounded ring buffer of typed trace events.

    ``capacity`` bounds memory (oldest events are evicted; ``dropped``
    counts them).  ``clock`` is the timestamp source — inject a fake for
    deterministic tests.  ``enabled`` can be toggled live; a disabled
    recorder costs one branch per record call.
    """

    def __init__(
        self,
        capacity: int = 65536,
        *,
        clock: Callable[[], float] = time.perf_counter,
        enabled: bool = True,
    ):
        self.enabled = bool(enabled)
        self.clock = clock
        self.capacity = int(capacity)
        self._events: collections.deque = collections.deque(
            maxlen=self.capacity
        )
        self._recorded = 0
        self._ids = itertools.count(1)
        self._totals: dict = {}   # running totals of `count`
        self._totals_lock = threading.Lock()

    # -- lifecycle ------------------------------------------------------
    def enable(self) -> "TraceRecorder":
        self.enabled = True
        return self

    def disable(self) -> "TraceRecorder":
        self.enabled = False
        return self

    def clear(self) -> None:
        self._events.clear()
        self._recorded = 0

    def __len__(self) -> int:
        return len(self._events)

    @property
    def dropped(self) -> int:
        """Events evicted by the ring bound (0 = the window is complete)."""
        return self._recorded - len(self._events)

    def events(self) -> list[TraceEvent]:
        """Snapshot of the ring, oldest first (C-level copy: safe against
        concurrent appends)."""
        return list(self._events)

    def next_id(self) -> int:
        """Fresh async-span correlation id (itertools.count: one C-level
        step, safe under the GIL)."""
        return next(self._ids)

    # -- recording ------------------------------------------------------
    def _record(self, phase, name, cat, track, args, id=None) -> None:
        # the one hot branch: a disabled recorder does nothing else
        if not self.enabled:
            return
        self._recorded += 1
        self._events.append(TraceEvent(
            self.clock(), phase, name, cat,
            track if track is not None
            else threading.current_thread().name,
            args or None, id,
        ))

    def begin(self, name: str, *, cat: str = "", track: "str | None" = None,
              **args) -> None:
        """Open a duration span on ``track`` (must be closed by `end`)."""
        self._record("B", name, cat, track, args)

    def end(self, name: str, *, cat: str = "", track: "str | None" = None,
            **args) -> None:
        """Close the innermost open span on ``track``."""
        self._record("E", name, cat, track, args)

    def span(self, name: str, *, cat: str = "", track: "str | None" = None,
             **args):
        """``with tracer.span("tick.encode", tenant=t): ...`` — emits a
        matched B/E pair.  While a profiler capture records, the span is
        also a profiler annotation of ``name`` and adds to `captured()`,
        on a disabled recorder too.  Otherwise a disabled recorder returns
        one shared no-op context manager: no allocation on the hot path."""
        capture = _capturing()
        if not (self.enabled or capture):
            return _NOOP_SPAN
        return _Span(self, name, cat, track, args, capture)

    def instant(self, name: str, *, cat: str = "",
                track: "str | None" = None, **args) -> None:
        """A point-in-time marker (scheduler fire, plan swap, ...)."""
        self._record("i", name, cat, track, args)

    def counter(self, name: str, value: float, *, cat: str = "",
                track: "str | None" = None) -> None:
        """One sample of a named counter series (queue depth, ...)."""
        self._record("C", name, cat, track, {"value": value})

    def count(self, name: str, n: int = 1) -> None:
        """Add ``n`` to a named count: to `captured()` while a profiler
        capture records, and as a counter sample of the recorder's running
        total when enabled."""
        if _capturing():
            _add_captured(name, n, 0.0)
        if self.enabled:
            with self._totals_lock:
                total = self._totals[name] = self._totals.get(name, 0) + n
            self._record("C", name, "", None, {"value": total})

    # -- async (cross-thread) spans ------------------------------------
    def async_begin(self, name: str, id: int, *, cat: str = "request",
                    track: "str | None" = None, **args) -> None:
        """Open a correlated span that may end on another thread —
        the request-lifecycle primitive."""
        self._record("b", name, cat, track, args, id=id)

    def async_instant(self, name: str, id: int, *, cat: str = "request",
                      track: "str | None" = None, **args) -> None:
        self._record("n", name, cat, track, args, id=id)

    def async_end(self, name: str, id: int, *, cat: str = "request",
                  track: "str | None" = None, **args) -> None:
        self._record("e", name, cat, track, args, id=id)

    # -- export conveniences (full API in .export) ----------------------
    def export_chrome(self, path: str) -> dict:
        """Write the timeline as Chrome-trace/Perfetto JSON (open it at
        https://ui.perfetto.dev or chrome://tracing)."""
        from repro.serve.observability.export import export_chrome

        return export_chrome(self, path)

    def export_jsonl(self, path: str) -> int:
        """Write the timeline as one JSON object per line."""
        from repro.serve.observability.export import export_jsonl

        return export_jsonl(self, path)

    def __repr__(self) -> str:
        state = "on" if self.enabled else "off"
        return (f"<TraceRecorder {state} {len(self._events)}"
                f"/{self.capacity} events, {self.dropped} dropped>")


#: Shared disabled recorder — what every serving layer defaults to, and
#: what the search records through.  Recording through it is a single
#: branch; `span()` through it is a single shared no-op object outside a
#: profiler capture.  Never enable this instance (it is shared
#: process-wide); construct a fresh `TraceRecorder` to actually trace.
NULL_TRACER = TraceRecorder(capacity=1, enabled=False)
