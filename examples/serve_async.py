"""Async deadline-aware serving: awaitable requests over the fused kernel.

Builds a small heterogeneous fleet (random genomes — serving cost does not
depend on how a circuit was found), pins each tenant a QoS tier, and
drives it from asyncio coroutines through `AsyncCircuitServer`:

  * every ``await frontend.submit(...)`` resolves to class ids once the
    deadline scheduler decides the fused launch should fire;
  * concurrent submits from different tenants coalesce into one
    `eval_population_spans` launch (batch fill / fire reasons printed);
  * admission control turns away a request whose deadline already passed,
    and a deliberately impossible deadline shows queue-side shedding;
  * `ServableCircuit.serve_async` is the one-call single-tenant variant.

    PYTHONPATH=src python examples/serve_async.py
"""
import asyncio
import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)  # for benchmarks.serve_circuits (fleet builder)
sys.path.insert(0, os.path.join(_ROOT, "src"))

import numpy as np

from repro.serve.async_frontend import AdmissionError, AsyncCircuitServer
from repro.serve.circuits import CircuitServer, TenantQoS
from repro.utils.compile_cache import use_compile_cache

TIERS = {
    "tight": TenantQoS(max_batch=128, max_wait_s=0.01,
                       default_deadline_s=0.20),
    "standard": TenantQoS(max_batch=256, max_wait_s=0.05,
                          default_deadline_s=0.60),
    "relaxed": TenantQoS(max_batch=512, max_wait_s=0.20,
                         default_deadline_s=2.00),
}


def build_fleet(n_tenants: int = 6, seed: int = 0):
    from benchmarks.serve_circuits import make_fleet

    rng = np.random.RandomState(seed)
    registry = make_fleet(n_tenants, rng)
    for i, tenant in enumerate(registry):
        tier = list(TIERS)[i % len(TIERS)]
        registry.set_qos(tenant, TIERS[tier])
        print(f"  {tenant}: {tier} "
              f"(deadline {TIERS[tier].default_deadline_s * 1e3:.0f} ms)")
    return registry, rng


async def tenant_traffic(frontend, registry, tenant, rng, n_requests=8):
    """One tenant's request stream: submit, await, verify."""
    n_feats = registry.get(tenant).encoder.n_features
    mismatches = 0
    for _ in range(n_requests):
        x = rng.randn(1 + rng.randint(12), n_feats).astype(np.float32)
        ids = await frontend.submit(tenant, x)
        mismatches += int(
            not np.array_equal(ids, registry.get(tenant).predict(x))
        )
        await asyncio.sleep(rng.uniform(0.0, 0.02))
    return mismatches


async def main():
    print("building fleet ...")
    registry, rng = build_fleet()
    server = CircuitServer(registry)
    # warm the fused launch so the first deadline isn't spent compiling
    server.step([
        (t, rng.randn(8, registry.get(t).encoder.n_features)
         .astype(np.float32))
        for t in registry
    ])
    server.reset_stats()

    async with AsyncCircuitServer(server) as frontend:
        print("\nserving concurrent mixed-deadline traffic ...")
        mism = await asyncio.gather(*[
            tenant_traffic(frontend, registry, t, rng) for t in registry
        ])
        print(f"  round-trip mismatches vs per-model predict: {sum(mism)}")
        assert sum(mism) == 0

        # admission control: a deadline in the past never enters the queue
        try:
            frontend.enqueue("tenant0", np.zeros((1, 4), np.float32),
                             deadline_s=-0.1)
        except AdmissionError as e:
            print(f"  admission reject (expected): {e}")

        print("\nfront-end stats:")
        for k, v in frontend.stats.report().items():
            print(f"  {k:23s} {v}")
        assert frontend.stats.report()["miss_rate"] == 0.0

    # one-call single-tenant variant
    print("\nServableCircuit.serve_async convenience:")
    sc = registry.get("tenant0")
    async with sc.serve_async() as single:
        x = rng.randn(5, 4).astype(np.float32)
        ids = await single.submit("default", x, deadline_s=5.0)
        assert np.array_equal(ids, sc.predict(x))
        print(f"  served {len(ids)} rows through a fresh single-tenant "
              f"front-end (backend={single.server.backend.name})")


if __name__ == "__main__":
    use_compile_cache()
    asyncio.run(main())
