"""Multi-host fleet serving: two hosts, a router, and a live migration.

Walks the fleet tier end to end on one machine:

  * two `ServingHost`s (each its own registry + `CircuitServer` +
    async front-end) join a `FleetRouter` over the in-process
    transport — the same RPC surface the socket/subprocess transports
    speak, codec and all;
  * tenants register through the router and land on hosts by
    consistent hashing (`FleetPlan`), so membership changes move ~K/n
    tenants instead of reshuffling the world;
  * a short skewed workload trace replays through the chunked fused
    path, then `router.rebalance()` lets the planner's LPT override
    act on the observed per-tenant loads — a cross-host migration
    ships the tenant's npz bundles over the wire with zero lost
    requests;
  * a live `router.submit()` shows the deadline path, and the fleet
    report / Prometheus text shows per-host gauges.

    PYTHONPATH=src python examples/serve_fleet.py
"""
import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)  # for benchmarks.serve_circuits (fleet builder)
sys.path.insert(0, os.path.join(_ROOT, "src"))

import numpy as np

from repro.serve.circuits import CircuitRegistry
from repro.serve.fleet import (
    FleetRouter,
    InProcTransport,
    ServingHost,
    generate,
)
from repro.serve.observability import prometheus_text
from repro.utils.compile_cache import use_compile_cache

N_TENANTS = 6
N_EVENTS = 800


def main():
    from benchmarks.serve_circuits import make_fleet

    print("== build: 2 hosts behind a router ==")
    router = FleetRouter()
    for i in range(2):
        host = ServingHost(f"host{i}", CircuitRegistry())
        host.start()
        router.add_host(f"host{i}", InProcTransport(host))

    print("== register tenants (consistent-hash placement) ==")
    registry = make_fleet(N_TENANTS, np.random.RandomState(0))
    circuits = {t: registry.get(t) for t in registry}
    for tenant, sc in sorted(circuits.items()):
        owner = router.register(tenant, [sc])
        print(f"  {tenant} -> {owner}")

    print(f"== replay a skewed {N_EVENTS}-event trace ==")
    wl = generate("skew", n_events=N_EVENTS,
                  tenants=sorted(circuits), seed=0)
    results = router.replay(wl.events, chunk_size=200)
    lost = sum(1 for y in results if not isinstance(y, np.ndarray))
    print(f"  {wl.n_events} events, {wl.total_rows} rows, {lost} lost")

    print("== rebalance on observed load (LPT override) ==")
    moved = router.rebalance(reason="example")
    if not moved:
        # hashing already balanced this tenant set — move one by hand
        # so the migration path still runs
        tenant = sorted(circuits)[0]
        away = next(h for h in router.hosts
                    if h != router.owner_of(tenant))
        moved = [router.migrate(tenant, away, reason="example")]
    for m in moved:
        print(f"  migrated {m.tenant}: {m.from_host} -> {m.to_host} "
              f"(drained {m.drained} queued, buffered {m.buffered} "
              f"racing submits, {m.duration_s * 1e3:.1f} ms)")

    print("== live submit lands on the new owner ==")
    tenant = moved[0].tenant
    x = np.random.RandomState(1).randn(
        4, circuits[tenant].encoder.n_features).astype(np.float32)
    y = router.submit(tenant, x).result(timeout=30)
    ok = np.array_equal(y, circuits[tenant].predict(x))
    print(f"  {tenant} via {router.owner_of(tenant)}: "
          f"{y.tolist()} (parity {'ok' if ok else 'BROKEN'})")

    print("== fleet report ==")
    rep = router.report()
    r = rep["router"]
    print(f"  routed {r['requests_routed']} requests "
          f"({r['rows_routed']} rows), {r['migrations']} migration(s), "
          f"plan generation {r['plan_generation']}")
    for h, hs in sorted(rep["hosts"].items()):
        print(f"  {h}: tenants={hs['tenants']} "
              f"routed={hs['requests_routed']} "
              f"in/out={hs['migrations_in']}/{hs['migrations_out']}")

    print("== prometheus (fleet section, first lines) ==")
    text = prometheus_text(fleet=rep)
    for line in text.splitlines():
        if "fleet" in line and not line.startswith("#"):
            print(f"  {line}")

    router.close()
    assert lost == 0 and ok and len(moved) >= 1
    print("fleet demo complete: zero lost, parity held across migration")


if __name__ == "__main__":
    use_compile_cache()
    main()
