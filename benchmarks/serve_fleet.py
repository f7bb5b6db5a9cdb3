"""Multi-host fleet serving: trace-driven cluster load harness.

Builds an in-process fleet (≥ 2 `ServingHost`s behind a `FleetRouter`),
registers a heterogeneous tenant set, and replays a seeded workload
trace through the chunked fused-`step` path — the configuration the
acceptance criteria name: a 10⁵-request skewed trace across two hosts
with **zero lost requests**, **at least one cross-host migration**
mid-replay, and per-request results **bitwise identical** to the same
trace replayed against a single host.

The migration is organic where possible: a `RebalanceCadence` ticks on
a virtual clock driven by the trace's own event times (interval = a
third of the trace duration), so the planner's LPT override acts on the
observed (Zipf-skewed) per-tenant row loads exactly when an operational
deployment's periodic rebalancer would — deterministically, because the
clock is the trace's, not the wall's.  If consistent hashing already
balanced the hot tenants — possible for small tenant sets — a single
scripted `migrate` of the hottest tenant keeps the migration path
measured (counted separately as ``forced``).

Traces are replayable artifacts: ``--workload PATH`` replays a
committed file (CI's fleet-smoke leg does this), ``--write-trace PATH``
generates-and-saves one and exits — the tooling that produced
``benchmarks/workloads/fleet_smoke.jsonl.gz``.

    PYTHONPATH=src python benchmarks/serve_fleet.py [--events N]
        [--hosts N] [--tenants N] [--shape skew|diurnal|spike]
        [--workload PATH] [--backend ref] [--trace PATH]
"""
from __future__ import annotations

import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.common import save_json, trace_dest
from benchmarks.serve_circuits import make_fleet
from repro import runtime
from repro.serve.circuits import CircuitRegistry
from repro.serve.fleet import (
    FleetRouter,
    InProcTransport,
    RebalanceCadence,
    ServingHost,
    Workload,
    generate,
    load_trace,
    save_trace,
)
from repro.serve.observability import TraceRecorder, export_chrome
from repro.utils.compile_cache import use_compile_cache


def build_fleet(n_hosts: int, backend: str, tracer) -> FleetRouter:
    """Router + ``n_hosts`` in-process hosts on one shared trace
    timeline (router and host spans interleave on their own tracks)."""
    router = FleetRouter(tracer=tracer)
    for i in range(n_hosts):
        host = ServingHost(f"host{i}", CircuitRegistry(),
                           backend=backend, tracer=tracer)
        host.start()
        router.add_host(f"host{i}", InProcTransport(host))
    return router


def register_tenants(router: FleetRouter, n_tenants: int, seed: int):
    """Register the benchmark tenant fleet; returns {tenant: circuit}
    for the parity leg.  Seeded so a second call builds bit-identical
    circuits — the single-host replay must serve the *same* models."""
    reg = make_fleet(n_tenants, np.random.RandomState(seed))
    circuits = {t: reg.get(t) for t in reg}
    for t, sc in sorted(circuits.items()):
        router.register(t, [sc])
    return circuits


def warm(router: FleetRouter, workload: Workload,
         warm_events: int) -> None:
    """Replay a small prefix to compile the fused launch shapes, then
    zero every counter — cold jit must not be charged to the timed
    window (migration-triggered recompiles mid-run stay in, they are
    part of what the benchmark measures)."""
    router.replay(workload.events[:warm_events], chunk_size=warm_events)
    router.reset_stats()


def replay_single_host(workload: Workload, circuits: dict,
                       backend: str, n_tenants: int,
                       seed: int, chunk_size: int) -> list:
    """The parity oracle: the same trace against one host."""
    solo = build_fleet(1, backend, TraceRecorder(enabled=False))
    try:
        register_tenants(solo, n_tenants, seed)
        return solo.replay(workload.events, chunk_size=chunk_size)
    finally:
        solo.close()


def run(backend: str = "ref", n_hosts: int = 2, n_tenants: int = 8,
        n_events: int = 100_000, shape: str = "skew",
        chunk_size: int = 2048, seed: int = 0,
        workload_path: "str | None" = None,
        trace_path: "str | None" = None) -> dict:
    if workload_path:
        workload = load_trace(workload_path)
        n_events = workload.n_events
    else:
        workload = generate(shape, n_events=n_events,
                            tenants=[f"tenant{i}" for i in range(n_tenants)],
                            seed=seed)
    missing = set(workload.tenants()) - {f"tenant{i}"
                                         for i in range(n_tenants)}
    if missing:
        raise SystemExit(
            f"trace names tenants the fleet does not build: "
            f"{sorted(missing)} — raise --tenants"
        )

    tracer = TraceRecorder(enabled=bool(trace_path))
    router = build_fleet(n_hosts, backend, tracer)
    try:
        circuits = register_tenants(router, n_tenants, seed)
        warm_events = min(4 * len(circuits) * 8, max(n_events // 10, 1))
        warm(router, workload, warm_events)
        tracer.clear()  # trace covers the timed window only

        # periodic rebalancing on the trace's own clock: the cadence
        # first comes due a third of the way in, by which point
        # observed_loads has a real window of the skewed traffic
        duration = workload.events[-1].t if workload.events else 0.0
        virtual_now = [0.0]
        cadence = RebalanceCadence(
            router, interval_s=max(duration / 3.0, 1e-9),
            min_rows=chunk_size, clock=lambda: virtual_now[0],
        )
        forced = 0

        def on_chunk(ci: int, r: FleetRouter) -> None:
            nonlocal forced
            last = min((ci + 1) * chunk_size, len(workload.events)) - 1
            virtual_now[0] = workload.events[last].t
            moved = cadence.tick()
            if moved is not None and not moved and not r.migrations:
                # hashing already balanced the hot tenants; script one
                # move so the migration path is always measured
                loads = r.observed_loads()
                hot = max(sorted(loads), key=lambda t: loads[t])
                away = min(h for h in r.hosts if h != r.owner_of(hot))
                r.migrate(hot, away, reason="bench-forced")
                forced += 1

        t0 = time.monotonic()
        results = router.replay(workload.events,
                                chunk_size=chunk_size,
                                on_chunk=on_chunk)
        wall = time.monotonic() - t0

        lost = sum(1 for y in results if not isinstance(y, np.ndarray))
        rep_fleet = router.report()
        migrations = [
            {"tenant": m.tenant, "from": m.from_host, "to": m.to_host,
             "reason": m.reason, "drained": m.drained,
             "buffered": m.buffered,
             "duration_ms": round(m.duration_s * 1e3, 3)}
            for m in router.migrations
        ]
    finally:
        router.close()

    # parity oracle after the fleet is down: peak memory stays one
    # cluster's worth, and the oracle's jit cache can't warm the fleet
    oracle = replay_single_host(workload, circuits, backend,
                                n_tenants, seed, chunk_size)
    parity_mismatches = sum(
        1 for y, want in zip(results, oracle)
        if not (isinstance(y, np.ndarray) and isinstance(want, np.ndarray)
                and np.array_equal(y, want))
    )

    rep = {
        "backend": backend,
        "qps": round(n_events / max(wall, 1e-9), 1),
        "rows_per_s": round(workload.total_rows / max(wall, 1e-9), 1),
        "n_hosts": n_hosts,
        "n_tenants": n_tenants,
        "n_events": n_events,
        "total_rows": workload.total_rows,
        "shape": workload.meta.get("shape", shape),
        "chunk_size": chunk_size,
        "workload_path": workload_path,
        "migrations": len(migrations),
        "cadence_fires": cadence.fires,
        "forced_migrations": forced,
        "migration_events": migrations,
        "lost_requests": lost,
        "parity_mismatches": parity_mismatches,
        "wall_s": round(wall, 3),
        "router": rep_fleet["router"],
        "hosts": rep_fleet["hosts"],
    }
    if trace_path:
        export_chrome(tracer, trace_path)
        rep.update({
            "trace_path": trace_path, "trace_events": len(tracer),
        })
    # acceptance invariants: the trace crossed a real cluster, at least
    # one tenant moved hosts mid-replay, nothing was lost, and every
    # result matches the single-host oracle bit for bit
    assert rep["n_hosts"] >= 2, "fleet benchmark needs >= 2 hosts"
    assert rep["migrations"] >= 1, "no cross-host migration happened"
    assert rep["lost_requests"] == 0, f"{lost} requests lost in replay"
    assert rep["parity_mismatches"] == 0, (
        "fleet replay diverged from the single-host oracle"
    )
    assert rep["router"]["requests_routed"] == n_events, (
        "router accounting leaked across the migration"
    )
    return rep


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--hosts", type=int, default=2)
    ap.add_argument("--tenants", type=int, default=8)
    ap.add_argument("--events", type=int, default=100_000,
                    help="trace length when generating (ignored with "
                         "--workload)")
    ap.add_argument("--shape", default="skew",
                    choices=["skew", "diurnal", "spike"])
    ap.add_argument("--chunk-size", type=int, default=2048)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--workload", default=None, metavar="PATH",
                    help="replay a committed trace file instead of "
                         "generating one")
    ap.add_argument("--write-trace", default=None, metavar="PATH",
                    help="generate the workload, save it to PATH "
                         "(.gz → gzip), and exit without benchmarking")
    implemented = [
        n for n in runtime.available_backends()
        if runtime.get_backend(n).capabilities().implemented
    ]
    ap.add_argument("--backend", action="append", default=None,
                    choices=implemented,
                    help="execution backend(s) to bench (repeatable; "
                         "default: ref)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="record the run and write a Chrome-trace/Perfetto "
                         "JSON (with several --backend flags, each gets "
                         "PATH with '.<backend>' before the extension)")
    args = ap.parse_args()

    if args.write_trace:
        wl = generate(args.shape, n_events=args.events,
                      tenants=[f"tenant{i}" for i in range(args.tenants)],
                      seed=args.seed)
        n = save_trace(wl, args.write_trace)
        print(f"wrote {wl.n_events} events ({wl.total_rows} rows, "
              f"shape={args.shape}, seed={args.seed}) -> "
              f"{args.write_trace} ({n} bytes)")
        return

    backends = args.backend or ["ref"]
    results = []
    for backend in backends:
        rep = run(backend=backend, n_hosts=args.hosts,
                  n_tenants=args.tenants, n_events=args.events,
                  shape=args.shape, chunk_size=args.chunk_size,
                  seed=args.seed, workload_path=args.workload,
                  trace_path=trace_dest(args.trace, backend, backends))
        results.append(rep)
        print(f"--- backend={rep['backend']} ({rep['n_hosts']} hosts, "
              f"{rep['n_tenants']} tenants, {rep['n_events']} events, "
              f"shape={rep['shape']}) ---")
        for k in ("qps", "rows_per_s", "migrations", "cadence_fires",
                  "forced_migrations", "lost_requests",
                  "parity_mismatches", "wall_s"):
            print(f"  {k:22s} {rep[k]}")
        for m in rep["migration_events"]:
            print(f"  migrate {m['tenant']:10s} {m['from']}→{m['to']} "
                  f"drained={m['drained']} buffered={m['buffered']} "
                  f"{m['duration_ms']:.1f} ms ({m['reason']})")
        for h, hs in sorted(rep["hosts"].items()):
            print(f"  {h:8s} routed={hs['requests_routed']:7d} "
                  f"tenants={hs['tenants']} in/out="
                  f"{hs['migrations_in']}/{hs['migrations_out']}")
        if rep.get("trace_path"):
            print(f"  trace                  {rep['trace_path']} "
                  f"({rep['trace_events']} events)")
    save_json("serve_fleet", results)


if __name__ == "__main__":
    use_compile_cache()
    main()
