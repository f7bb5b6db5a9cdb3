"""Multi-tenant circuit serving throughput / latency.

Builds a fleet of heterogeneous tenants (random genomes — serving cost does
not depend on how a circuit was found), drives Poisson-ish request traffic
through the `CircuitServer` micro-batcher, and reports QPS, p50/p99 tick
latency, and fused-launch occupancy.  The headline property the acceptance
criteria ask for is printed per config: every tick that had ≥ 2 pending
tenants served them with exactly one kernel launch, and results stay
bit-identical to the per-model `ServableCircuit.predict` path.

Each run is tagged with the resolved execution-backend name (from the
`repro.runtime` registry) in its results JSON, so BENCH trajectories stay
comparable across backends.

    PYTHONPATH=src python benchmarks/serve_circuits.py [--ticks N]
        [--tenants N] [--backend ref] [--backend pallas]

On CPU the ``pallas`` backend runs in interpret mode (plumbing validation,
not speed).
"""
from __future__ import annotations

import argparse
import os
import sys
import time

import jax
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.common import save_json, trace_dest
from repro import runtime
from repro.core import encoding as E
from repro.core import gates
from repro.core.api import ServableCircuit
from repro.core.genome import CircuitSpec, init_genome
from repro.serve.circuits import CircuitRegistry, CircuitServer
from repro.serve.observability import TraceRecorder, export_chrome
from repro.serve.planning import PlacementPolicy
from repro.utils.compile_cache import use_compile_cache

# (features, bits/input, gates, classes) per tenant, cycled
SHAPES = [(4, 2, 60, 2), (7, 4, 120, 3), (3, 2, 40, 4), (10, 4, 200, 5),
          (6, 2, 80, 2), (12, 4, 300, 8)]


def make_fleet(n_tenants: int, rng) -> CircuitRegistry:
    reg = CircuitRegistry()
    for i in range(n_tenants):
        f, b, n, c = SHAPES[i % len(SHAPES)]
        enc = E.fit_encoder(rng.randn(256, f).astype(np.float32),
                            E.EncodingConfig("quantile", b))
        n_out = max(1, int(np.ceil(np.log2(max(c, 2)))))
        spec = CircuitSpec(enc.n_bits_total, n, n_out, gates.FULL_FS)
        reg.add(
            f"tenant{i}",
            ServableCircuit(spec, init_genome(jax.random.key(i), spec),
                            enc, c),
        )
    return reg


def drive(server: CircuitServer, registry: CircuitRegistry, *, ticks: int,
          mean_rows: int, rng, verify_every: int = 0) -> tuple:
    """Submit traffic and tick; returns (parity mismatches, the largest
    number of tenants any single tick fused across its launches)."""
    mismatches = 0
    max_tick_tenants = 0
    tenants = list(registry)
    for t in range(ticks):
        tickets = []
        for name in tenants:
            if rng.rand() < 0.2:  # tenant idle this tick
                continue
            n_feats = registry.get(name).encoder.n_features
            rows = 1 + rng.poisson(mean_rows)
            x = rng.randn(rows, n_feats).astype(np.float32)
            tickets.append((name, server.submit(name, x), x))
        report = server.tick()
        assert report.launches <= server.policy.n_shards
        max_tick_tenants = max(max_tick_tenants, report.tenants)
        for name, ticket, x in tickets:
            got = server.result(ticket)
            if verify_every and t % verify_every == 0:
                want = registry.get(name).predict(x)
                mismatches += int(not np.array_equal(got, want))
            else:
                assert got.shape == (x.shape[0],)
    return mismatches, max_tick_tenants


def measure_trace_overhead(server, registry, *, ticks: int, mean_rows: int,
                           seed: int) -> float:
    """QPS cost of *enabling* tracing, in percent: two back-to-back drives
    over identical traffic (same RNG seed), recorder off then on.  Both
    legs run in-process on warm jit caches, so the delta isolates the
    recorder's append cost from runner noise — the number
    `check_bench.py` gates.  (The cost of the *disabled* instrumentation
    — one branch per site — is the benchmark's normal configuration and
    is gated by the standard QPS-vs-baseline tolerance.)"""
    tracer = server.tracer
    tracer.disable()
    t0 = time.perf_counter()
    drive(server, registry, ticks=ticks, mean_rows=mean_rows,
          rng=np.random.RandomState(seed))
    t_off = time.perf_counter() - t0
    tracer.clear()
    tracer.enable()
    t0 = time.perf_counter()
    drive(server, registry, ticks=ticks, mean_rows=mean_rows,
          rng=np.random.RandomState(seed))
    t_on = time.perf_counter() - t0
    tracer.disable()
    return (t_on - t_off) / max(t_off, 1e-9) * 100.0


def run(ticks: int = 50, n_tenants: int = 8, mean_rows: int = 24,
        backend: str = "ref", seed: int = 0, shards: int = 1,
        trace_path: "str | None" = None) -> dict:
    rng = np.random.RandomState(seed)
    registry = make_fleet(n_tenants, rng)
    tracer = TraceRecorder(enabled=False)
    server = CircuitServer(
        registry, backend=backend,
        policy=PlacementPolicy(n_shards=shards), tracer=tracer,
    )

    # warmup: trigger plan build + jit compile outside the timed window
    drive(server, registry, ticks=2, mean_rows=mean_rows, rng=rng)
    server.reset_stats()

    t0 = time.perf_counter()
    mism, max_tick_tenants = drive(
        server, registry, ticks=ticks, mean_rows=mean_rows,
        rng=rng, verify_every=10,
    )
    wall = time.perf_counter() - t0

    overhead = measure_trace_overhead(
        server, registry, ticks=max(ticks // 2, 8),
        mean_rows=mean_rows, seed=seed + 1,
    )

    rep = server.stats.report()
    rep.update({
        "impl": server.backend.name,  # legacy key, kept for BENCH continuity
        "n_tenants": n_tenants,
        "n_shards": shards,
        "max_tick_tenants": max_tick_tenants,
        "wall_s": round(wall, 3),
        "parity_mismatches": mism,
        "trace_overhead_pct": round(overhead, 2),
    })
    if trace_path:
        # the overhead measurement's enabled leg left a real trace behind
        export_chrome(tracer, trace_path)
        rep.update({
            "trace_path": trace_path, "trace_events": len(tracer),
        })
    return rep


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--ticks", type=int, default=50)
    ap.add_argument("--tenants", type=int, default=8)
    ap.add_argument("--mean-rows", type=int, default=24)
    implemented = [
        n for n in runtime.available_backends()
        if runtime.get_backend(n).capabilities().implemented
    ]
    ap.add_argument("--backend", action="append", default=None,
                    choices=implemented,
                    help="execution backend(s) to bench (repeatable; "
                         "default: ref)")
    ap.add_argument("--shards", type=int, default=1,
                    help="plan shards (one fused launch per shard per "
                         "tick; shards land on distinct devices when the "
                         "host has several)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write a Chrome-trace/Perfetto JSON of the traced "
                         "leg (with several --backend flags, each gets "
                         "PATH with '.<backend>' before the extension)")
    args = ap.parse_args()

    backends = args.backend or ["ref"]
    results = []
    for backend in backends:
        rep = run(ticks=args.ticks, n_tenants=args.tenants,
                  mean_rows=args.mean_rows, backend=backend,
                  shards=args.shards,
                  trace_path=trace_dest(args.trace, backend, backends))
        results.append(rep)
        print(f"--- backend={rep['backend']} ({rep['n_tenants']} tenants) ---")
        for k in ("qps", "rows_per_s", "p50_tick_ms", "p99_tick_ms",
                  "mean_occupancy", "max_tenants_per_launch", "launches",
                  "ticks", "parity_mismatches", "trace_overhead_pct"):
            print(f"  {k:23s} {rep[k]}")
        pb = rep["phase_breakdown"]
        print("  phase ms/tick          " + "  ".join(
            f"{p}={v}" for p, v in pb["per_tick_ms"].items()))
        print(f"  host/kernel share      {pb['host_share']} / "
              f"{pb['kernel_share']}")
        if rep.get("trace_path"):
            print(f"  trace                  {rep['trace_path']} "
                  f"({rep['trace_events']} events)")
        assert rep["parity_mismatches"] == 0
        # fusion guard: some tick must have served >= 4 heterogeneous
        # tenants across at most `shards` launches (drive() asserts the
        # launch bound per tick)
        assert rep["max_tick_tenants"] >= 4, (
            "fused launches must together serve >= 4 heterogeneous tenants"
        )
    save_json("serve_circuits", results)


if __name__ == "__main__":
    use_compile_cache()
    main()
