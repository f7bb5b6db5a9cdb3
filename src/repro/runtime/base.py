"""EvalBackend abstraction: the execution seam of the toolflow.

A backend owns *how* a population of sea-of-gates circuits is evaluated
on bit-packed data — which kernel, which block/VMEM policy, which device
kinds — behind three entry points whose contracts are fixed:

  * ``eval_population(opcodes, edge_src, out_src, x_words)``
      i32[P, n], i32[P, n, 2], i32[P, O], u32[I, W] → u32[P, O, W]
  * ``eval_population_spans(..., word_off, in_width, *, span_words)``
      multi-tenant serving path: circuit p reads only words
      [word_off[p], word_off[p]+span_words) with input rows ≥ in_width[p]
      masked to zero → u32[P, O, span_words]
  * ``eval_circuit(...)`` single-circuit convenience → u32[O, W]

All backends must be bit-identical on these contracts (the parity test
matrix in tests/ enforces it); they may differ only in performance and
in which devices they can run on, which `capabilities()` describes.
"""
from __future__ import annotations

import abc
import dataclasses

import jax


class BackendCapabilityError(NotImplementedError):
    """Raised when a registered backend cannot serve a request on this
    host/device (e.g. the reserved ``pallas-gpu`` slot before its lowering
    lands, or a spans call on a backend without span support)."""


@dataclasses.dataclass(frozen=True)
class BackendCapabilities:
    """Static descriptor of what an execution backend can do.

    ``word_alignment`` is the word-axis granularity the backend pads or
    blocks to internally (1 = none).  ``span_offset_contract`` documents
    the alignment constraint on ``word_off`` entries for the spans entry
    point.  ``implemented`` is False for reserved registry slots whose
    eval entry points raise `BackendCapabilityError`.

    ``supports_aot`` declares whether `compile_spans` can produce a
    serializable ahead-of-time executable for the fused span launch;
    ``aot_format``/``aot_format_version`` name the serialization format
    so artifact stores can reject payloads they cannot load.  AOT
    availability is a *declared* capability, not something callers probe
    with try/except — a backend that says False (e.g. ``"ref"``, kept
    eager so it stays the readable oracle) is served via the traced
    fallback path, with the reason logged.
    """

    name: str
    device_kinds: tuple[str, ...]   # e.g. ("cpu", "tpu")
    supports_spans: bool
    word_alignment: int
    span_offset_contract: str = "none"
    implemented: bool = True
    supports_aot: bool = False
    aot_format: str = ""
    aot_format_version: int = 0


class EvalBackend(abc.ABC):
    """One execution strategy for circuit evaluation.

    Implementations are stateless w.r.t. the data they evaluate (safe to
    share across threads / jit traces); configuration such as a forced
    interpret mode lives in the instance.
    """

    name: str = "abstract"

    @abc.abstractmethod
    def capabilities(self) -> BackendCapabilities:
        """Static descriptor: spans support, alignment, device kinds."""

    @abc.abstractmethod
    def eval_population(
        self,
        opcodes: jax.Array,   # i32[P, n]
        edge_src: jax.Array,  # i32[P, n, 2]
        out_src: jax.Array,   # i32[P, O]
        x_words: jax.Array,   # u32[I, W]
    ) -> jax.Array:           # u32[P, O, W]
        """Evaluate a population of circuits on a shared packed dataset."""

    @abc.abstractmethod
    def eval_population_spans(
        self,
        opcodes: jax.Array,    # i32[P, n]
        edge_src: jax.Array,   # i32[P, n, 2]
        out_src: jax.Array,    # i32[P, O]
        x_words: jax.Array,    # u32[I_max, W_total] fused multi-tenant buffer
        word_off: jax.Array,   # i32[P] word offset of circuit p's span
        in_width: jax.Array,   # i32[P] live input rows of circuit p
        *,
        span_words: int,
    ) -> jax.Array:            # u32[P, O, span_words]
        """Multi-tenant population eval over per-circuit word spans."""

    def eval_circuit(
        self,
        opcodes: jax.Array,   # i32[n]
        edge_src: jax.Array,  # i32[n, 2]
        out_src: jax.Array,   # i32[O]
        x_words: jax.Array,   # u32[I, W]
    ) -> jax.Array:           # u32[O, W]
        """Single-circuit convenience wrapper (default: population of 1)."""
        out = self.eval_population(
            opcodes[None], edge_src[None], out_src[None], x_words
        )
        return out[0]

    def compile_spans(self, spec, *, device=None):
        """Ahead-of-time compile the fused span launch for one shard shape.

        ``spec`` is a `repro.runtime.aot.SpanLaunchSpec` (the shard's
        static shape tuple plus the span bucket); the returned
        `jax.stages.Compiled` executes the complete per-tick device
        program — slot gather, liveness mask, span kernel — with zero
        further tracing, and round-trips through
        `repro.runtime.aot.serialize_executable`.

        Availability is declared by ``capabilities().supports_aot``;
        backends that declare False raise `BackendCapabilityError` here
        and are served via the traced fallback path instead.
        """
        caps = self.capabilities()
        if not caps.supports_aot:
            raise BackendCapabilityError(
                f"backend {self.name!r} declares supports_aot=False: the "
                "fused span launch cannot be compiled ahead of time; serve "
                "it via the traced path (trace-on-boot fallback)."
            )
        from repro.runtime import aot

        return aot.compile_span_launch(self, spec, device=device)

    def instrument(self, hook) -> "EvalBackend":
        """Wrap this backend so every ``eval_*`` launch runs inside a
        caller-supplied context.

        ``hook(kind, **meta)`` is called per launch with the entry-point
        name (``"eval_population"``, ``"eval_population_spans"``,
        ``"eval_circuit"``) and cheap launch metadata (population size,
        span words); it must return a context manager, and the launch
        executes inside it.  A `TraceRecorder.span` fits directly::

            traced = backend.instrument(
                lambda kind, **meta: tracer.span(
                    "backend." + kind, cat="kernel", **meta)
            )

        The proxy delegates ``capabilities``/``span_alignment`` and keeps
        the backend ``name``, so it is substitutable anywhere an
        `EvalBackend` is — the serving engine launches through the proxy
        while plan compilation keeps using the raw backend.
        """
        return _InstrumentedBackend(self, hook)

    def span_alignment(self, requested: int | None = None) -> int:
        """Resolve a requested word-span alignment against this backend.

        ``None`` means "whatever this backend wants" and returns
        ``capabilities().word_alignment`` (e.g. 128 so spans stay
        lane-aligned on native TPU kernels); an explicit int is honoured
        as given — backends that tolerate unaligned spans (interpret
        mode, the jnp oracle) serve them, ones that cannot reject the
        launch.  Plan compilers call this once so every `LaunchPlan`
        carries an alignment the backend agreed to."""
        if requested is None:
            return max(int(self.capabilities().word_alignment), 1)
        return max(int(requested), 1)

    def max_launch_slots(self, n_nodes: int, n_outputs: int) -> "int | None":
        """Most circuits of this size one launch can hold, or None when
        the backend has no such bound.  `PlanCompiler` refuses a shard
        above it, so the limit surfaces when a plan is built, not as a
        compiler error in the middle of a tick."""
        return None

    def __repr__(self) -> str:
        return f"<{type(self).__name__} {self.name!r}>"


class _InstrumentedBackend(EvalBackend):
    """Delegating proxy reporting every launch through a hook context.

    Stateless beyond the pair (inner backend, hook): safe to share
    across threads exactly like the backend it wraps.  The hook runs on
    the *dispatching* thread around the launch call, so with an async
    dispatch (jax on device) it measures submit cost, and the readback
    wait shows up wherever the caller blocks — which is exactly how the
    serving tick's phase breakdown wants it split.
    """

    def __init__(self, inner: EvalBackend, hook):
        self._inner = inner
        self._hook = hook
        self.name = inner.name

    def capabilities(self) -> BackendCapabilities:
        return self._inner.capabilities()

    def span_alignment(self, requested: int | None = None) -> int:
        return self._inner.span_alignment(requested)

    def max_launch_slots(self, n_nodes: int, n_outputs: int) -> "int | None":
        return self._inner.max_launch_slots(n_nodes, n_outputs)

    def compile_spans(self, spec, *, device=None):
        # compilation is a control-plane step, not a launch: delegate
        # uninstrumented; the serving tick wraps *execution* of the
        # compiled launch in its own span.
        return self._inner.compile_spans(spec, device=device)

    def eval_population(self, opcodes, edge_src, out_src, x_words):
        with self._hook("eval_population", population=int(opcodes.shape[0]),
                        words=int(x_words.shape[-1])):
            return self._inner.eval_population(
                opcodes, edge_src, out_src, x_words
            )

    def eval_population_spans(self, opcodes, edge_src, out_src, x_words,
                              word_off, in_width, *, span_words: int):
        with self._hook("eval_population_spans",
                        population=int(opcodes.shape[0]),
                        span_words=int(span_words)):
            return self._inner.eval_population_spans(
                opcodes, edge_src, out_src, x_words, word_off, in_width,
                span_words=span_words,
            )

    def eval_circuit(self, opcodes, edge_src, out_src, x_words):
        with self._hook("eval_circuit", words=int(x_words.shape[-1])):
            return self._inner.eval_circuit(
                opcodes, edge_src, out_src, x_words
            )

    def __repr__(self) -> str:
        return f"<_InstrumentedBackend over {self._inner!r}>"
