"""Micro-batching inference engine over compiled launch plans.

Request flow (one `tick()`):

  1. snapshot every tenant's pending float-feature rows;
  2. refresh the compiled plan (the `PlanCompiler` recompiles only when
     the registry generation moved; device-side tensor copies are cached
     by shard content hash, so an unchanged shard never re-uploads);
  3. per tenant, run the encode→bit-pack pipeline once per ensemble
     member over all its pending requests;
  4. fuse each plan shard's work into its own padded
     ``u32[I_max, S·span]`` word buffer — slot k owns the word span
     ``[k·span, (k+1)·span)`` — and dispatch **one fused
     `eval_population_spans` launch per shard**, each placed on its own
     device when the host has several (shards overlap: all launches are
     dispatched before any output is read back);
  5. decode each member's live output bits to class ids, majority-vote
     ensemble members, and scatter results to the originating requests.

Placement is policy, not code: pass a `PlacementPolicy` to shard the
slot population, align spans to the backend's lane width, or rebalance
slot assignment — the engine just executes whatever plan the compiler
produced.
"""
from __future__ import annotations

import dataclasses
import logging
import threading
import time
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro import runtime
from repro.core import encoding as E
from repro.observability.trace import NULL_TRACER, TraceRecorder
from repro.runtime import aot as runtime_aot
from repro.core.api import decode_predictions
from repro.serve.circuits.metrics import (
    TICK_PHASES,
    RebalanceEvent,
    ServerStats,
    TickReport,
)
from repro.serve.circuits.registry import CircuitRegistry
from repro.serve.planning import (
    CompiledPlan,
    PlacementPolicy,
    PlanCompiler,
    ensemble_vote,
)
from repro.sharding import specs

_log = logging.getLogger("repro.serve.aot")


class StalePlanError(RuntimeError):
    """A plan offered to `CircuitServer.swap_plan` was compiled from a
    catalog generation the registry has since moved past — the caller
    must re-snapshot the catalog and recompile."""


@dataclasses.dataclass
class _Pending:
    ticket: int
    x: np.ndarray  # float32[r, F_tenant]


class CircuitServer:
    """Synchronous micro-batching server over a `CircuitRegistry`.

    ``submit()`` enqueues rows and returns a ticket; ``tick()`` serves every
    pending row in one fused launch per plan shard; ``result()`` collects
    predictions.  ``backend`` names the execution backend from the
    `repro.runtime` registry (or is an `EvalBackend` instance); it is
    resolved once here and every tick dispatches through it.  ``policy``
    is the declarative placement: shard count, slot assignment, and span
    alignment (``PlacementPolicy(span_align=None)`` derives lane alignment
    from ``backend.capabilities().word_alignment`` — use on real TPUs).
    ``span_align`` is the legacy scalar knob, honoured when no policy is
    passed.  ``device`` pins every launch of this server to one device —
    how several one-chip replicas share a multi-chip host in one process;
    without it shard *s* runs on local device ``s % n`` when the policy
    shards, and on the default device otherwise.
    """

    def __init__(
        self,
        registry: CircuitRegistry,
        *,
        backend: "str | runtime.EvalBackend" = "ref",
        policy: PlacementPolicy | None = None,
        span_align: int | None = None,
        stable_shapes: bool = True,
        tracer: TraceRecorder | None = None,
        device: "jax.Device | None" = None,
    ):
        if policy is not None and span_align is not None:
            raise ValueError(
                "pass span_align via the policy when using one: "
                "PlacementPolicy(span_align=...)"
            )
        if policy is None:
            policy = PlacementPolicy(
                span_align=1 if span_align is None else span_align
            )
        self.registry = registry
        self.backend = runtime.resolve_backend(backend)
        self.policy = policy
        self.compiler = PlanCompiler(self.backend, policy)
        self.span_align = self.compiler.span_align
        # pad every launch to its shard's full slot count (idle slots are
        # masked off with in_width=0) so the jitted launch shape depends
        # only on the span bucket and the plan content — not on which
        # subset of tenants happens to be busy.  Without this, a deadline
        # scheduler driving launches hits a fresh XLA compile (seconds)
        # whenever a new active-slot count shows up, which is exactly when
        # requests are queued against a deadline.
        self.stable_shapes = bool(stable_shapes)
        # one timeline for the whole stack: the front-end, the autoscale
        # controller, and the backend launch hooks all record into the
        # server's tracer.  NULL_TRACER (the default) is permanently
        # disabled — every instrumentation point costs one branch.
        self.tracer = NULL_TRACER if tracer is None else tracer
        # launches dispatch through the instrumented proxy so each
        # kernel-level eval carries its own trace span; plan compilation
        # keeps using the raw backend
        self._exec = self.backend.instrument(self._launch_span)
        self.stats = ServerStats(backend=self.backend.name)
        self._lock = threading.Lock()
        # serializes whole launches: a step() must observe its own tick
        # serving its tickets, not race a concurrent tick()/predict()
        # that snapshots them first (RLock: step's tick nests inside)
        self._serve_lock = threading.RLock()
        self._pending: dict[str, list[_Pending]] = {}
        self._results: dict[int, np.ndarray] = {}
        self._next_ticket = 0
        # shadow slots: tenant → (expected member count, trailing shadow
        # count).  When a tenant's launched member count matches the
        # expectation, the trailing members are excluded from the decode
        # vote and handed to `shadow_hook` instead — how an online-
        # evolution candidate scores against live traffic inside the
        # fused launch without touching served output.  Keying on the
        # expected count makes the exclusion race-free across the
        # registry mutation that installs/removes the shadow member: a
        # stale plan simply doesn't match and votes normally.
        self._shadow: dict[str, tuple[int, int]] = {}
        self.shadow_hook: "Callable | None" = None
        # compiled-plan cache (generation-tagged) + device-side tensor
        # copies keyed by shard content hash
        self._plan_lock = threading.Lock()
        self._compiled: CompiledPlan | None = None
        self._dev: dict[str, tuple] = {}
        # shard s launches on device s % n (only when the policy shards
        # and the host actually has multiple devices), or on the pinned
        # device
        self._device = device
        self._devices = self._shard_devices(policy)
        # -- AOT executables -------------------------------------------
        # compiled span launches keyed by (shard content hash, span
        # bucket, device ordinal); populated by prewarm_plan /
        # preload_executables, or compiled on first tick miss.  Only
        # meaningful on supports_aot backends with stable shapes — the
        # launch shape must be a pure function of (shard, span bucket)
        # for a compiled executable to be reusable across ticks.
        self._aot_lock = threading.Lock()
        self._aot: dict[tuple[str, int, int], object] = {}
        # uploads staged by prewarm_plan, consumed (and counted as
        # rebuilt) by the next swap_plan fence — staging keeps the
        # reused/rebuilt accounting honest while moving the transfer
        # off the swap's critical path
        self._staged_dev: dict[str, tuple] = {}
        # launch-shape signatures the eager jit cache is already hot
        # for (no-AOT backends): a repeat prewarm of the same shapes is
        # a no-op, so churny swap loops don't pay a dead launch each
        self._warm_shapes: set[tuple] = set()
        self._spans_seen: set[int] = set()   # span buckets ticks produced
        self._aot_capable = bool(
            self.backend.capabilities().supports_aot
        ) and self.stable_shapes
        self.aot_stats = {
            "exec_hits": 0, "compiles": 0, "loads": 0,
            "load_failures": 0, "trace_warms": 0, "exec_warms": 0,
        }

    def _launch_span(self, kind: str, **meta):
        """Launch hook handed to `EvalBackend.instrument` — one trace
        span per kernel-level eval call (no-op while tracing is off)."""
        return self.tracer.span(f"backend.{kind}", cat="kernel", **meta)

    def _shard_devices(self, policy: PlacementPolicy) -> "tuple | None":
        if self._device is not None:
            return (self._device,)
        if policy.n_shards > 1:
            mesh = specs.population_mesh(policy.n_shards)
            if mesh.devices.size > 1:
                return tuple(mesh.devices.flat)
        return None

    def reset_stats(self) -> None:
        """Fresh stats window (keeps the resolved backend tag)."""
        self.stats = ServerStats(backend=self.backend.name)

    # -- request interface ---------------------------------------------
    def submit(self, tenant: str, x: np.ndarray) -> int:
        """Enqueue rows for one tenant; returns a result ticket."""
        if tenant not in self.registry:
            raise KeyError(f"unknown tenant {tenant!r}")
        x = np.atleast_2d(np.asarray(x, np.float32))
        want = self.registry.get(tenant).encoder.n_features
        if x.shape[1] != want:
            raise ValueError(
                f"tenant {tenant!r} expects {want} features, got {x.shape[1]}"
            )
        with self._lock:
            ticket = self._next_ticket
            self._next_ticket += 1
            self._pending.setdefault(tenant, []).append(_Pending(ticket, x))
        return ticket

    def result(self, ticket: int) -> np.ndarray:
        """Class ids for a served ticket (KeyError if not yet ticked).

        Re-raises per-request serving errors (e.g. the tenant was removed
        or hot-swapped incompatibly between submit and tick)."""
        out = self._results.pop(ticket)
        if isinstance(out, Exception):
            raise out
        return out

    def predict(self, tenant: str, x: np.ndarray) -> np.ndarray:
        """submit + tick + result in one call (single-tenant convenience)."""
        ticket = self.submit(tenant, x)
        self.tick()
        return self.result(ticket)

    def step(
        self, work: "list[tuple[str, np.ndarray]]"
    ) -> "list[np.ndarray | Exception]":
        """Single-launch hook for external schedulers (the async front-end).

        Submits the given ``(tenant, rows)`` work items, runs exactly one
        fused tick, and returns each item's class ids — or its per-request
        serving error (bad tenant, hot remove, width mismatch) as an
        Exception instance instead of raising — in input order.  The caller
        owns *when* this fires; the server still owns *how* (encode → fuse
        → one `eval_population_spans` launch per plan shard).

        Atomic against concurrent `tick()`/`predict()` on the same server:
        the whole submit→tick→collect sequence holds the serve lock, so
        another thread's tick cannot steal this step's tickets mid-flight.
        """
        with self._serve_lock:
            tickets: list = []
            for tenant, x in work:
                try:
                    tickets.append(self.submit(tenant, x))
                except Exception as err:  # noqa: BLE001 — per-item isolation
                    tickets.append(err)
            self.tick()
            return [
                t if isinstance(t, Exception) else self._results.pop(t)
                for t in tickets
            ]

    def pending_rows(self) -> int:
        with self._lock:
            return sum(
                p.x.shape[0] for reqs in self._pending.values() for p in reqs
            )

    # -- the compiled plan ---------------------------------------------
    def _device_for(self, shard: int):
        if self._devices is None:
            return None
        return self._devices[shard % len(self._devices)]

    def _refresh_plan(self) -> tuple[CompiledPlan, dict, "tuple | None"]:
        """Compiled plan for the current registry generation plus its
        device-side tensors and the device list it was placed on;
        uploads are cached by shard content hash, so hot-swapping one
        tenant re-uploads only the shards it actually changed.  Returns
        the plan with its own tensor dict and device snapshot (not the
        live attributes) so a concurrent recompile *or plan swap* cannot
        pull tensors — or re-point device placement — out from under a
        tick in flight.

        The fast path is one int comparison — schedulers call this per
        poll, so a cache hit must not build a `Catalog` (or take the
        registry lock).  The snapshot is taken *inside* the plan lock so
        two racing refreshes cannot install an older catalog's plan over
        a newer one."""
        with self._plan_lock:
            if (self._compiled is not None
                    and self._compiled.generation
                    == self.registry.generation):
                return self._compiled, self._dev, self._devices
            cat = self.registry.catalog()
            # incremental once a plan exists: unchanged tenants keep their
            # shard and slot order, so only the shards a mutation actually
            # touched change content hash (and re-upload / re-jit)
            compiled = self.compiler.recompile(cat, self._compiled)
            dev: dict[str, tuple] = {}
            for shard in compiled.shards:
                dev[shard.content_hash] = (
                    self._dev.get(shard.content_hash)
                    or self._staged_dev.pop(shard.content_hash, None)
                    or self._upload_shard(shard)
                )
            self._compiled = compiled
            self._dev = dev  # stale shard tensors are dropped here
            return compiled, dev, self._devices

    def _upload_shard(self, shard) -> tuple:
        device = self._device_for(shard.shard)
        host = (shard.opcodes, shard.edge_src,
                shard.out_src, shard.in_width)
        # device_put straight from host numpy: one transfer, not an
        # upload-to-default + device-to-device copy
        return tuple(
            jnp.asarray(t) if device is None
            else jax.device_put(t, device)
            for t in host
        )

    # -- ahead-of-time executables --------------------------------------
    @staticmethod
    def _dev_key(device) -> int:
        return -1 if device is None else int(device.id)

    def span_bucket(self, words: int) -> int:
        """The launch bucket a tick would round ``words`` up to: next
        power of two, then padded to the plan's span alignment — the
        exact quantization `tick()` applies, so prewarm/export and the
        live launch agree on shapes."""
        span = 1 << (max(int(words), 1) - 1).bit_length()
        return -(-span // self.span_align) * self.span_align

    def spans_seen(self) -> tuple[int, ...]:
        """Span buckets ticks have actually launched (ascending) — the
        shapes worth prewarming or exporting."""
        return tuple(sorted(self._spans_seen))

    def _span_spec(self, shard, span: int) -> runtime_aot.SpanLaunchSpec:
        return runtime_aot.SpanLaunchSpec(
            n_slots=shard.n_slots,
            k_pad=shard.n_slots,  # stable_shapes pads the launch to S
            n_nodes=int(shard.opcodes.shape[1]),
            n_outputs=int(shard.out_src.shape[1]),
            n_inputs=int(shard.n_inputs_max),
            span_words=int(span),
        )

    def _aot_executable(self, shard, span: int, device):
        """Compiled launch for (shard, span bucket) — cache hit, or
        compile-and-cache on a supports_aot backend; None means "use the
        eager traced path"."""
        if not self._aot_capable:
            return None
        key = (shard.content_hash, int(span), self._dev_key(device))
        with self._aot_lock:
            fn = self._aot.get(key)
        if fn is not None:
            self.aot_stats["exec_hits"] += 1
            return fn
        fn = self.backend.compile_spans(
            self._span_spec(shard, span), device=device
        )
        self.aot_stats["compiles"] += 1
        with self._aot_lock:
            return self._aot.setdefault(key, fn)

    def _prewarm_shard(self, shard, spans, store, summary: dict) -> None:
        """Make every (shard, span) launch hot before it serves: load a
        stored executable, else AOT-compile, else (no-AOT backend) trace
        the eager jit path once with the exact launch shapes."""
        device = self._device_for(shard.shard)
        # device tensors: upload now (staged) so the swap fence — and any
        # warm launch below — reuses the transfer instead of doing it
        # with the plan lock held
        with self._plan_lock:
            cached = self._dev.get(shard.content_hash)
            if cached is None:
                cached = self._staged_dev.get(shard.content_hash)
        if cached is None:
            cached = self._upload_shard(shard)
            with self._plan_lock:
                cached = self._staged_dev.setdefault(
                    shard.content_hash, cached
                )
        for span in spans:
            span = int(span)
            if self._aot_capable:
                key = (shard.content_hash, span, self._dev_key(device))
                with self._aot_lock:
                    if key in self._aot:
                        continue
                fn = None
                if store is not None and device is None:
                    # persisted executables are compiled for the default
                    # device; a multi-device placement recompiles instead
                    kstr = runtime_aot.executable_key(
                        self.backend.name, shard.content_hash, span
                    )
                    try:
                        fn = runtime_aot.deserialize_executable(
                            store.get_executable(kstr)
                        )
                        summary["loaded"] += 1
                        self.aot_stats["loads"] += 1
                    except KeyError:
                        pass  # not exported for this shape — compile
                    except Exception as err:  # noqa: BLE001 — any broken
                        # artifact (corrupt bytes, missing object file,
                        # incompatible runtime) falls back to compiling
                        summary["load_failures"] += 1
                        self.aot_stats["load_failures"] += 1
                        _log.warning(
                            "stored executable %s unusable (%s: %s); "
                            "falling back to compile", kstr,
                            type(err).__name__, err,
                        )
                if fn is None:
                    fn = self.backend.compile_spans(
                        self._span_spec(shard, span), device=device
                    )
                    summary["compiled"] += 1
                    self.aot_stats["compiles"] += 1
                with self._aot_lock:
                    fn = self._aot.setdefault(key, fn)
                # an executable's first call pays one-time runtime
                # binding (comparable to a whole steady tick) — spend it
                # on dead zero inputs now, off the serving path, so the
                # first real launch runs at steady latency.  Args mirror
                # the tick's exactly (staged device tensors + uploaded
                # buffers), not host zeros: binding is per argument
                # placement
                k_pad = shard.n_slots
                x = np.zeros(
                    (shard.n_inputs_max, k_pad * span), np.uint32
                )
                woff = np.arange(k_pad, dtype=np.int32) * span
                live = np.zeros(k_pad, np.int32)
                if device is None:
                    x_dev, woff_dev, live_dev = (
                        jnp.asarray(x), jnp.asarray(woff), jnp.asarray(live)
                    )
                else:
                    x_dev, woff_dev, live_dev = (
                        jax.device_put(x, device),
                        jax.device_put(woff, device),
                        jax.device_put(live, device),
                    )
                out = fn(
                    *cached, np.zeros(k_pad, np.int32),
                    x_dev, woff_dev, live_dev,
                )
                jax.block_until_ready(out)
                summary["exec_warmed"] += 1
                self.aot_stats["exec_warms"] += 1
            else:
                # no-AOT backend (e.g. "ref"): warm its jit cache with a
                # dead launch of the exact shapes the tick will use, so
                # the first post-swap tick is a cache hit, not a trace
                sig = (shard.n_slots, int(shard.opcodes.shape[1]),
                       int(shard.out_src.shape[1]),
                       int(shard.n_inputs_max), span, self._dev_key(device))
                if sig in self._warm_shapes:
                    continue  # jit cache already hot for these shapes
                opc, edge, outs, in_w = cached
                k_pad = shard.n_slots
                slots = np.zeros(k_pad, np.int64)
                x = np.zeros(
                    (shard.n_inputs_max, k_pad * span), np.uint32
                )
                woff = np.arange(k_pad, dtype=np.int32) * span
                live = np.zeros(k_pad, np.int32)
                if device is None:
                    x_dev, woff_dev, live_dev = (
                        jnp.asarray(x), jnp.asarray(woff), jnp.asarray(live)
                    )
                else:
                    x_dev, woff_dev, live_dev = (
                        jax.device_put(x, device),
                        jax.device_put(woff, device),
                        jax.device_put(live, device),
                    )
                out = self.backend.eval_population_spans(
                    opc[slots], edge[slots], outs[slots],
                    x_dev, woff_dev, in_w[slots] * live_dev,
                    span_words=span,
                )
                jax.block_until_ready(out)
                self._warm_shapes.add(sig)
                summary["trace_warmed"] += 1
                self.aot_stats["trace_warms"] += 1

    def prewarm_plan(
        self, compiled: CompiledPlan, *, spans=None, store=None,
    ) -> dict:
        """Make an incoming plan's launch shapes hot *before* it is
        installed — the anti-dip half of a plan swap.

        For every shard × span bucket: load the serialized executable
        from ``store`` when one is keyed for it, else compile ahead of
        time (supports_aot backends), else trace-warm the eager jit path
        (no-AOT backends like ``"ref"``).  ``spans`` defaults to the
        buckets this server's ticks have actually produced, so a server
        that has never ticked prewarms nothing.  Runs outside the plan
        lock: serving continues on the old plan while the new one warms.
        Returns a summary dict (loaded/compiled/trace_warmed/...).
        """
        summary = {"loaded": 0, "compiled": 0, "trace_warmed": 0,
                   "exec_warmed": 0, "load_failures": 0, "skipped": 0}
        if not self.stable_shapes:
            # launch shapes depend on live tenant count — nothing to warm
            summary["skipped"] = len(compiled.shards)
            _log.info(
                "prewarm skipped: stable_shapes=False makes launch shapes "
                "traffic-dependent"
            )
            return summary
        use = sorted(
            {int(s) for s in (self._spans_seen if spans is None else spans)}
        )
        for shard in compiled.shards:
            self._prewarm_shard(shard, use, store, summary)
        return summary

    def export_executables(self, store, *, spans=None) -> list[str]:
        """Persist the current plan's compiled launches into an
        `ArtifactStore`: one serialized executable per shard × span
        bucket, keyed by ``(backend, shard content hash, span bucket)``.
        Executables are compiled for the default device (a booting host's
        placement), and each entry records that device's platform and
        kind so a host of another kind skips it by name.  On a backend
        that declares no AOT support this stores nothing and logs why —
        boot from such an artifact falls back to trace-on-boot.  Returns
        the stored keys."""
        caps = self.backend.capabilities()
        if not caps.supports_aot:
            _log.info(
                "backend %r declares supports_aot=False: no executables "
                "exported, artifact boot will trace", self.backend.name,
            )
            return []
        if not self.stable_shapes:
            _log.info(
                "stable_shapes=False: launch shapes are traffic-dependent, "
                "no executables exported"
            )
            return []
        plan, _, _ = self._refresh_plan()
        use = sorted(
            {int(s) for s in (self._spans_seen if spans is None else spans)}
        ) or [self.span_bucket(1)]
        target = jax.devices()[0]
        keys = []
        for shard in plan.shards:
            for span in use:
                key = (shard.content_hash, span, -1)
                with self._aot_lock:
                    fn = self._aot.get(key)
                if fn is None:
                    fn = self.backend.compile_spans(
                        self._span_spec(shard, span)
                    )
                    self.aot_stats["compiles"] += 1
                    with self._aot_lock:
                        fn = self._aot.setdefault(key, fn)
                kstr = runtime_aot.executable_key(
                    self.backend.name, shard.content_hash, span
                )
                store.put_executable(
                    kstr, runtime_aot.serialize_executable(fn),
                    backend=self.backend.name,
                    aot_format=caps.aot_format,
                    aot_format_version=caps.aot_format_version,
                    spec=tuple(self._span_spec(shard, span)),
                    platform=target.platform,
                    device_kind=target.device_kind,
                )
                keys.append(kstr)
        return keys

    def preload_executables(self, store) -> dict:
        """Boot-time half of `export_executables`: bind every stored
        executable that matches the current plan's shard hashes (and this
        backend/format) into the launch cache — **zero tracing** when the
        artifact covers the plan.  Entries built for another platform or
        device kind are skipped by name (counted in ``skipped``); other
        mismatched or broken entries fall back to compiling, with the
        reason logged.  Returns the prewarm summary."""
        plan, _, _ = self._refresh_plan()
        caps = self.backend.capabilities()
        here = jax.devices()[0]
        spans_by_hash: dict[str, set[int]] = {}
        prefix = f"{self.backend.name}--"
        skipped = 0
        for kstr, entry in store.executable_entries().items():
            if entry.get("backend") != self.backend.name:
                continue
            built_for = (entry.get("platform", ""),
                         entry.get("device_kind", ""))
            if built_for != (here.platform, here.device_kind):
                _log.warning(
                    "stored executable %s was built for platform %r device "
                    "%r; this host is %r %r — skipped (will compile)",
                    kstr, *built_for, here.platform, here.device_kind,
                )
                skipped += 1
                continue
            if (entry.get("format") != caps.aot_format
                    or int(entry.get("format_version", 0))
                    > caps.aot_format_version):
                _log.warning(
                    "stored executable %s has format %s v%s; this backend "
                    "reads %s v<=%s — skipped (will compile)",
                    kstr, entry.get("format"), entry.get("format_version"),
                    caps.aot_format, caps.aot_format_version,
                )
                continue
            if not kstr.startswith(prefix) or "--s" not in kstr:
                continue
            body, span_s = kstr[len(prefix):].rsplit("--s", 1)
            spans_by_hash.setdefault(body, set()).add(int(span_s))
        summary = {"loaded": 0, "compiled": 0, "trace_warmed": 0,
                   "exec_warmed": 0, "load_failures": 0, "skipped": skipped}
        for shard in plan.shards:
            spans = sorted(spans_by_hash.get(shard.content_hash, ()))
            if not spans:
                continue
            self._spans_seen.update(spans)
            self._prewarm_shard(shard, spans, store, summary)
        return summary

    def swap_plan(
        self,
        compiled: CompiledPlan,
        *,
        compiler: PlanCompiler | None = None,
        action: str = "swap",
        reason: str = "",
        prewarm: bool = True,
        store=None,
    ) -> RebalanceEvent:
        """Generation-fenced atomic plan swap — the autoscaling hook.

        Installs an externally compiled plan (e.g. a rebalanced or
        grown/shrunk one from `PlanCompiler.recompile`) in place of the
        server's own.  The fence: the plan must have been compiled from
        the registry's *current* generation, else `StalePlanError` —
        the caller re-snapshots the catalog and recompiles, so a swap
        can never roll back a concurrent registry mutation.

        The swap is atomic against serving: a tick in flight keeps its
        own immutable plan snapshot and device-tensor dict to the end;
        requests queued across the swap land on the new plan at their
        next tick.  Device uploads are satisfied from the content-hash
        cache, so unchanged shards are never re-uploaded (`RebalanceEvent
        .shards_reused` counts them).  ``compiler`` (when given) becomes
        the server's compiler, so the swapped policy — shard count,
        assignment — also governs future generation-triggered refreshes.

        ``prewarm`` (default on) makes the incoming plan's launch shapes
        hot *before* the fence: executables load from ``store`` or
        compile ahead of time (AOT backends), or the eager jit cache is
        trace-warmed (no-AOT backends) — all while serving continues on
        the old plan, so the first post-swap tick launches without a
        compile in its critical path.
        """
        # fast-fail the fence before spending prewarm work on a plan
        # that is already stale (the lock re-checks authoritatively)
        if compiled.generation != self.registry.generation:
            raise StalePlanError(
                f"plan compiled at generation {compiled.generation}, "
                f"registry is at {self.registry.generation}"
            )
        prewarm_summary = None
        if prewarm and self._aot_capable:
            # swap-integrated prewarm is AOT-only: compiled executables
            # are keyed by shard content hash so the work is reusable,
            # and cache hits make repeat swaps near-free.  On no-AOT
            # backends a trace-warm would hold the recompile→fence
            # window open for whole jit traces under churn — those
            # servers warm on first tick (or via an explicit
            # `prewarm_plan` call at boot) instead.
            prewarm_summary = self.prewarm_plan(compiled, store=store)
        t0 = time.perf_counter()
        with self._plan_lock:
            if compiled.generation != self.registry.generation:
                raise StalePlanError(
                    f"plan compiled at generation {compiled.generation}, "
                    f"registry is at {self.registry.generation}"
                )
            prev = self._compiled
            if compiler is not None:
                self.compiler = compiler
                self.policy = compiler.policy
                self.span_align = compiler.span_align
                self._devices = self._shard_devices(compiler.policy)
            reused = rebuilt = 0
            dev: dict[str, tuple] = {}
            for shard in compiled.shards:
                cached = self._dev.get(shard.content_hash)
                if cached is None:
                    # a prewarm-staged upload still counts as rebuilt —
                    # the transfer happened for this swap, just earlier
                    rebuilt += 1
                    cached = self._staged_dev.pop(shard.content_hash, None)
                    if cached is None:
                        cached = self._upload_shard(shard)
                else:
                    reused += 1
                dev[shard.content_hash] = cached
            self._compiled = compiled
            self._dev = dev
            self._staged_dev.clear()
            with self._lock:
                inflight = sum(
                    len(reqs) for reqs in self._pending.values()
                )
        event = RebalanceEvent(
            action=action,
            reason=reason,
            generation=compiled.generation,
            from_shards=prev.n_shards if prev is not None else 0,
            to_shards=compiled.n_shards,
            shards_reused=reused,
            shards_rebuilt=rebuilt,
            inflight_requests=inflight,
            swap_ms=(time.perf_counter() - t0) * 1e3,
            prev_hash=prev.content_hash if prev is not None else "",
            plan_hash=compiled.content_hash,
        )
        self.stats.record_rebalance(event)
        # plan swaps land as instants on the shared timeline, next to the
        # request spans and tick phases they interleave with
        self.tracer.instant(
            "plan.swap", cat="autoscale", track="autoscale",
            action=action, reason=reason,
            from_shards=event.from_shards, to_shards=event.to_shards,
            shards_reused=reused, shards_rebuilt=rebuilt,
            inflight=inflight, swap_ms=round(event.swap_ms, 3),
            generation=event.generation,
            **(
                {"prewarm_" + k: v for k, v in prewarm_summary.items() if v}
                if prewarm_summary else {}
            ),
        )
        return event

    # -- shadow slots (online evolution) -------------------------------
    def set_shadow(self, tenant: str, n_members: int, n_shadow: int) -> None:
        """Mark the trailing ``n_shadow`` of the tenant's ``n_members``
        ensemble members as hidden shadow slots: they launch and decode
        like any member, but are excluded from the served vote and
        delivered to ``shadow_hook(tenant, shadow_ids, served_ids)``
        instead.  The exclusion only applies to launches whose member
        count equals ``n_members``, so the caller can set this *before*
        the registry mutation that adds the shadow member — a tick on
        the pre-mutation plan votes normally."""
        if not (0 < n_shadow < n_members):
            raise ValueError(
                f"need 0 < n_shadow < n_members, got "
                f"({n_shadow}, {n_members})"
            )
        self._shadow[tenant] = (int(n_members), int(n_shadow))

    def clear_shadow(self, tenant: str) -> None:
        self._shadow.pop(tenant, None)

    def shadow_of(self, tenant: str) -> "tuple[int, int] | None":
        return self._shadow.get(tenant)

    def shard_of(self, tenant: str) -> int:
        """Home shard of a tenant under the current compiled plan (what a
        deadline scheduler keys its per-shard fire times on)."""
        plan, _, _ = self._refresh_plan()
        return plan.shard_of(tenant)

    def plan(self) -> CompiledPlan:
        """The current compiled plan (compiling if stale) — inspectable:
        shards, placement, content hashes, span alignment."""
        plan, _, _ = self._refresh_plan()
        return plan

    def peek_plan(self) -> CompiledPlan | None:
        """The last installed plan without compiling — possibly stale,
        possibly None on a never-ticked server.  What an autoscaler
        feeds `PlanCompiler.recompile` as the stickiness hint: a stale
        previous plan only costs placement quality, never correctness,
        and peeking avoids compiling a plan that is about to be
        replaced anyway."""
        with self._plan_lock:
            return self._compiled

    # -- the fused tick ------------------------------------------------
    def tick(self) -> TickReport:
        """Serve every pending request in one launch per active shard."""
        with self._serve_lock:
            return self._tick_locked()

    def _tick_locked(self) -> TickReport:
        perf = time.perf_counter
        t0 = perf()
        # wall time per phase this tick (encode / pack / device_put /
        # launch / readback / decode) — always measured: a handful of
        # perf_counter reads against ms-scale ticks, and the breakdown is
        # the BENCH before-picture the device-resident hot path must beat
        phase = dict.fromkeys(TICK_PHASES, 0.0)
        tracer = self.tracer
        # Snapshot pending BEFORE the plan: any tenant that reached the
        # queue was registered at submit time, so a plan refreshed now can
        # only be missing it if a concurrent remove won — and everything
        # below reads the immutable plan snapshot, never the live registry.
        with self._lock:
            batch = [(t, reqs) for t, reqs in self._pending.items() if reqs]
            self._pending = {}
        tracer.begin("tick", cat="tick")
        try:
            report = self._tick_traced(t0, perf, phase, batch)
        finally:
            tracer.end("tick", cat="tick")
        self.stats.record(report)
        return report

    def _tick_traced(self, t0, perf, phase, batch) -> TickReport:
        tracer = self.tracer
        # plan, tensors, devices and span alignment are one consistent
        # snapshot: a concurrent swap_plan re-points the live attributes,
        # but this tick launches entirely on what it read here
        plan, dev, devices = self._refresh_plan()
        span_align = plan.span_align if plan.shards else self.span_align

        def device_for(shard: int):
            if devices is None:
                return None
            return devices[shard % len(devices)]

        # Encode each tenant's pending rows once per ensemble member.
        # entries: one logical tenant's tick state; member_ids[m] is filled
        # in as member m's shard launch decodes.
        entries = []   # (tenant, reqs, offsets, refs, n_classes, member_ids)
        shard_work: dict[int, list] = {}  # shard → [(slot, packed, entry, m)]
        n_requests = 0
        for tenant, reqs in batch:
            n_requests += len(reqs)
            refs = plan.placement.get(tenant)
            # The tenant may have been removed (or hot-swapped to a
            # different feature width) between submit and tick; fail those
            # requests individually instead of poisoning the whole tick.
            members = plan.members(tenant) if refs else ()
            if not refs or any(
                p.x.shape[1] != members[0].encoder.n_features for p in reqs
            ):
                why = ("removed" if not refs
                       else "hot-swapped to a different feature width")
                err = KeyError(
                    f"tenant {tenant!r} was {why} with requests pending"
                )
                for p in reqs:
                    self._results[p.ticket] = err
                continue
            xs = [p.x for p in reqs]
            n_rows = sum(x.shape[0] for x in xs)
            if n_rows == 0:  # zero-row requests complete immediately
                for p in reqs:
                    self._results[p.ticket] = np.zeros(0, np.int64)
                continue
            entry = {
                "tenant": tenant,
                "reqs": reqs, "rows": n_rows, "offsets": None,
                "n_classes": int(members[0].n_classes),
                "member_ids": [None] * len(refs),
            }
            w_t = E.n_words(n_rows)
            with tracer.span("tick.encode_pack", cat="tick",
                             tenant=tenant, rows=n_rows):
                for m, (ref, sc) in enumerate(zip(refs, members)):
                    t1 = perf()
                    bits, offsets = E.encode_batched(sc.encoder, xs)
                    t2 = perf()
                    entry["offsets"] = offsets
                    packed = E.pack_bits_rows(bits, w_t)
                    phase["encode"] += t2 - t1
                    phase["pack"] += perf() - t2
                    shard_work.setdefault(ref.shard, []).append(
                        (ref.slot, packed, entry, m)
                    )
            entries.append(entry)

        if not shard_work:
            return TickReport(
                generation=plan.generation, tenants=0, requests=n_requests,
                rows=0, launches=0, span_words=0,
                latency_s=perf() - t0, occupancy=0.0,
                plan_shards=plan.n_shards,
                phase_s=phase,
            )

        # Fuse per shard: slot k owns words [k*span, (k+1)*span) of that
        # shard's buffer.  Spans are bucketed to powers of two (then padded
        # to the plan's span alignment) so jit sees a bounded set of shapes
        # across ticks instead of recompiling per traffic level.  With
        # stable_shapes the slot axis is padded to the shard's full slot
        # count: pad slots gather slot 0's genome but carry in_width=0, so
        # their rows are fully masked and their outputs never read.
        # All shard launches are dispatched before any output is read back
        # — with per-shard device placement they overlap on the hardware.
        launches = []  # (shard_idx, span, items, out_device_array)
        max_span = 0
        pad_cells = 0
        shard_stats = []  # per launch: (shard, slot-rows, padded bit-lanes)
        for shard_idx in sorted(shard_work):
            shard = plan.shards[shard_idx]
            items = shard_work[shard_idx]
            span = max(E.n_words(e["rows"]) for _, _, e, _ in items)
            span = 1 << (span - 1).bit_length()
            span = -(-span // span_align) * span_align
            k_active = len(items)
            k_pad = shard.n_slots if self.stable_shapes else k_active
            i_max = shard.n_inputs_max
            t1 = perf()
            x_buf = np.zeros((i_max, k_pad * span), np.uint32)
            for k, (slot, packed, _, _) in enumerate(items):
                x_buf[: packed.shape[0],
                      k * span: k * span + packed.shape[1]] = packed

            slots = np.zeros(k_pad, np.int64)
            slots[:k_active] = [it[0] for it in items]
            live = (np.arange(k_pad) < k_active).astype(np.int32)
            opc, edge, outs, in_w = dev[shard.content_hash]
            device = device_for(shard_idx)
            woff_host = np.arange(k_pad, dtype=np.int32) * span
            phase["pack"] += perf() - t1  # fused-buffer fill
            t1 = perf()
            with tracer.span("tick.device_put", cat="tick",
                             shard=shard_idx):
                if device is None:
                    x_dev = jnp.asarray(x_buf)
                    live_dev = jnp.asarray(live)
                    woff = jnp.asarray(woff_host)
                else:  # one transfer per buffer, straight to shard device
                    x_dev = jax.device_put(x_buf, device)
                    live_dev = jax.device_put(live, device)
                    woff = jax.device_put(woff_host, device)
            self._spans_seen.add(span)
            # a failed compile raises: the traced path would hit the same
            # compiler, and a silent fallback would hide it
            aot_fn = self._aot_executable(shard, span, device)
            t2 = perf()
            with tracer.span("tick.launch", cat="tick", shard=shard_idx,
                             span_words=span, slots=k_active):
                if aot_fn is not None:
                    # pre-compiled executable: gather + mask fused inside,
                    # so the call never traces — same span name as the
                    # instrumented eager path keeps the timeline uniform
                    with self._launch_span(
                        "eval_population_spans",
                        population=int(k_pad), span_words=int(span),
                        aot=True,
                    ):
                        out = aot_fn(
                            opc, edge, outs, in_w,
                            slots.astype(np.int32), x_dev, woff, live_dev,
                        )
                else:
                    out = self._exec.eval_population_spans(
                        opc[slots], edge[slots], outs[slots],
                        x_dev, woff, in_w[slots] * live_dev,
                        span_words=span,
                    )
                    if self.stable_shapes:
                        # this launch just warmed the eager jit cache for
                        # these shapes — prewarm can skip them
                        self._warm_shapes.add((
                            shard.n_slots, int(shard.opcodes.shape[1]),
                            int(shard.out_src.shape[1]),
                            int(shard.n_inputs_max), span,
                            self._dev_key(device),
                        ))
            phase["device_put"] += t2 - t1
            phase["launch"] += perf() - t2
            launches.append((shard_idx, span, items, out))
            max_span = max(max_span, span)
            pad_cells += k_pad * span
            shard_stats.append((
                shard_idx,
                sum(it[2]["rows"] for it in items),
                k_pad * span * E.WORD,
            ))

        # Read back and decode: member class ids first, then the vote.
        for shard_idx, span, items, out in launches:
            shard = plan.shards[shard_idx]
            t1 = perf()
            with tracer.span("tick.readback", cat="tick", shard=shard_idx):
                out = np.asarray(out)  # u32[K_pad, O_max, span]
            t2 = perf()
            for k, (slot, _, entry, m) in enumerate(items):
                o_t = int(shard.out_width[slot])
                entry["member_ids"][m] = decode_predictions(
                    out[k, :o_t], entry["rows"], entry["n_classes"]
                )
            phase["readback"] += t2 - t1
            phase["decode"] += perf() - t2

        t1 = perf()
        with tracer.span("tick.decode", cat="tick"):
            for entry in entries:
                member_ids = entry["member_ids"]
                shadow = self._shadow.get(entry["tenant"])
                n_sh = 0
                if shadow is not None and shadow[0] == len(member_ids):
                    n_sh = shadow[1]
                voted = member_ids[:len(member_ids) - n_sh]
                ids = ensemble_vote(np.stack(voted), entry["n_classes"])
                if n_sh and self.shadow_hook is not None:
                    try:
                        self.shadow_hook(
                            entry["tenant"],
                            member_ids[len(member_ids) - n_sh:], ids,
                        )
                    except Exception:  # noqa: BLE001 — a scoring bug
                        # must never fail the serving path
                        pass
                offsets = entry["offsets"]
                for p, lo, hi in zip(
                        entry["reqs"], offsets[:-1], offsets[1:]):
                    self._results[p.ticket] = ids[lo:hi]
        phase["decode"] += perf() - t1

        total_rows = sum(e["rows"] for e in entries)
        tracer.counter("tick.rows", total_rows, cat="tick")
        return TickReport(
            generation=plan.generation,
            tenants=len(entries),
            requests=n_requests,
            rows=total_rows,
            launches=len(launches),
            span_words=max_span,
            latency_s=perf() - t0,
            occupancy=total_rows / (pad_cells * E.WORD),
            plan_shards=plan.n_shards,
            max_slots_per_launch=max(
                len(items) for _, _, items, _ in launches
            ),
            shard_stats=tuple(shard_stats),
            tenant_rows=tuple(
                (e["tenant"], e["rows"]) for e in entries
            ),
            phase_s=phase,
        )
