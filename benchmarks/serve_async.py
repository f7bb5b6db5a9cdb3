"""Async deadline-aware serving under open-loop Poisson load.

Drives the `AsyncCircuitServer` front-end with open-loop arrivals (the
request schedule is drawn up front and replayed on the wall clock, so a
slow server cannot slow the offered load — the honest way to measure a
serving system) across tenants with mixed deadline tiers, and reports the
numbers the BENCH trajectory tracks: p50/p99 request latency, deadline
miss rate, and mean batch fill of the deadline scheduler's coalesced
launches.

    PYTHONPATH=src python benchmarks/serve_async.py [--backend ref]
        [--backend pallas] [--duration-s 2.0] [--qps 120]
        [--deadline-scale 1.0] [--expect-no-miss]

Tenants cycle through three QoS tiers (tight / standard / relaxed
deadlines).  With ``--expect-no-miss`` (the CI configuration: modest load,
generous deadlines) the run fails if any admitted request misses its
deadline.  On CPU the ``pallas`` backend runs in interpret mode —
plumbing validation, not speed.
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
from repro.serve.async_frontend import AsyncCircuitServer
from repro.serve.circuits import CircuitServer, TenantQoS
from repro.serve.observability import TraceRecorder, export_chrome
from repro.utils.compile_cache import use_compile_cache

# deadline tiers cycled across tenants (seconds, scaled by --deadline-scale)
TIERS = (
    ("tight", 0.150),
    ("standard", 0.400),
    ("relaxed", 1.500),
)


def build_schedule(tenants, registry, *, qps: float, duration_s: float,
                   mean_rows: int, rng) -> list:
    """Open-loop arrival schedule: (t_arrival, tenant, rows) sorted by time.
    Poisson process per tenant at qps/len(tenants) each."""
    events = []
    rate = qps / max(len(tenants), 1)
    for tenant in tenants:
        n_feats = registry.get(tenant).encoder.n_features
        t = 0.0
        while True:
            t += rng.exponential(1.0 / rate)
            if t >= duration_s:
                break
            rows = 1 + rng.poisson(mean_rows)
            events.append(
                (t, tenant, rng.randn(rows, n_feats).astype(np.float32))
            )
    events.sort(key=lambda e: e[0])
    return events


def run(backend: str = "ref", n_tenants: int = 6, qps: float = 120.0,
        duration_s: float = 2.0, mean_rows: int = 8,
        deadline_scale: float = 1.0, seed: int = 0,
        trace_path: "str | None" = None) -> dict:
    rng = np.random.RandomState(seed)
    registry = make_fleet(n_tenants, rng)
    tenants = list(registry)
    tiers = {}
    for i, tenant in enumerate(tenants):
        name, deadline_s = TIERS[i % len(TIERS)]
        tiers[tenant] = name
        registry.set_qos(tenant, TenantQoS(
            max_batch=256,
            max_wait_s=0.25 * deadline_s * deadline_scale,
            default_deadline_s=deadline_s * deadline_scale,
        ))
    # tracing on only when a trace was asked for: the recorder's append
    # cost is µs-scale against ms ticks, but the benchmark's default
    # configuration stays the production one (instrumented, disabled)
    tracer = TraceRecorder(enabled=bool(trace_path))
    server = CircuitServer(registry, backend=backend, tracer=tracer)

    # Warm up the fused launch (jit compile) outside the measured window —
    # a cold fire would charge multi-second compile time to whichever
    # requests ride it.  With stable_shapes the launch shape depends only
    # on the span bucket, so warming a few row levels covers the run.
    for rows in (1, 33, 4 * mean_rows + 65):
        server.step([
            (t, rng.randn(rows, registry.get(t).encoder.n_features)
             .astype(np.float32))
            for t in tenants
        ])
    server.reset_stats()
    tracer.clear()  # drop warmup events: the trace covers the timed window

    schedule = build_schedule(tenants, registry, qps=qps,
                              duration_s=duration_s, mean_rows=mean_rows,
                              rng=rng)
    frontend = AsyncCircuitServer(server)
    results = []  # (tenant, future, x)
    rejected = 0
    with frontend:
        t0 = time.monotonic()
        for t_arr, tenant, x in schedule:
            delay = t0 + t_arr - time.monotonic()
            if delay > 0:
                time.sleep(delay)
            try:
                results.append((tenant, frontend.enqueue(tenant, x), x))
            except Exception:  # noqa: BLE001 — admission reject
                rejected += 1
        wall = time.monotonic() - t0
    # context exit stops + drains: every future is resolved now

    failed = 0
    parity_mismatches = 0
    for i, (tenant, fut, x) in enumerate(results):
        err = fut.exception()
        if err is not None:
            failed += 1
            continue
        if i % 20 == 0:  # spot-check parity vs the per-model path
            want = registry.get(tenant).predict(x)
            parity_mismatches += int(not np.array_equal(fut.result(), want))

    rep = frontend.stats.report()
    rep.update({
        "n_tenants": n_tenants,
        "tenant_tiers": tiers,
        "deadline_tiers": {
            name: round(s * deadline_scale, 4) for name, s in TIERS
        },
        "offered_qps": round(len(schedule) / max(duration_s, 1e-9), 1),
        "offered_requests": len(schedule),
        "wall_s": round(wall, 3),
        "mean_rows": mean_rows,
        "parity_mismatches": parity_mismatches,
        "server": server.stats.report(),
    })
    if trace_path:
        export_chrome(tracer, trace_path)
        rep.update({
            "trace_path": trace_path, "trace_events": len(tracer),
        })
    assert rep["parity_mismatches"] == 0
    assert rep["completed"] + rep["shed"] + rejected == len(schedule)
    # independently-counted failed futures must agree with the stats'
    # shed count (the only failure mode here — no hot removes in-bench)
    assert failed == rep["shed"], (failed, rep["shed"])
    return rep


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tenants", type=int, default=6)
    ap.add_argument("--qps", type=float, default=120.0)
    ap.add_argument("--duration-s", type=float, default=2.0)
    ap.add_argument("--mean-rows", type=int, default=8)
    ap.add_argument("--deadline-scale", type=float, default=1.0,
                    help="multiply every tier's deadline (CI uses > 1 so "
                         "interpret-mode backends stay feasible)")
    ap.add_argument("--expect-no-miss", action="store_true",
                    help="fail if any admitted request misses its deadline "
                         "(CI gate: load within capacity, feasible deadlines)")
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

    backends = args.backend or ["ref"]
    results = []
    for backend in backends:
        rep = run(backend=backend, n_tenants=args.tenants, qps=args.qps,
                  duration_s=args.duration_s, mean_rows=args.mean_rows,
                  deadline_scale=args.deadline_scale,
                  trace_path=trace_dest(args.trace, backend, backends))
        results.append(rep)
        print(f"--- backend={rep['backend']} ({rep['n_tenants']} tenants, "
              f"{rep['offered_qps']} req/s offered) ---")
        for k in ("completed", "rejected", "shed", "served_late",
                  "miss_rate", "p50_latency_ms", "p99_latency_ms",
                  "mean_batch_fill", "fires", "fire_reasons",
                  "max_queue_depth_rows"):
            print(f"  {k:23s} {rep[k]}")
        pb = rep["server"]["phase_breakdown"]
        print(f"  host/kernel share      {pb['host_share']} / "
              f"{pb['kernel_share']}")
        if rep.get("trace_path"):
            print(f"  trace                  {rep['trace_path']} "
                  f"({rep['trace_events']} events)")
        if args.expect_no_miss:
            assert rep["deadline_misses"] == 0 and rep["rejected"] == 0, (
                f"backend {backend}: {rep['deadline_misses']} deadline "
                f"misses / {rep['rejected']} rejects under the CI "
                "configuration (load within capacity, feasible deadlines)"
            )
    save_json("serve_async", results)


if __name__ == "__main__":
    use_compile_cache()
    main()
