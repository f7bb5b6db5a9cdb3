"""Multi-tenant circuit serving: fit several Tiny Classifiers, persist
them as on-disk artifacts, and boot a server **from the artifacts alone**
— the fleet-restart flow.

The flow mirrors a deployment: each dataset stands in for a customer
scenario (its own feature width, encoding, and class count); the evolved
circuit is exported with `to_servable()` and persisted into a versioned
content-addressed `ArtifactStore` (manifest.json + objects/).  Serving
then starts from `ArtifactStore.load_registry` — no fitted classifier
objects, no `fit()` call — and the `CircuitServer` micro-batches every
tenant's requests into a single `eval_population_spans` launch per tick
through the configured execution backend.  At the end one tenant is
hot-swapped to show generation-tagged recompilation.

    PYTHONPATH=src python examples/serve_circuits.py [--artifacts DIR]

With ``--artifacts DIR`` pointing at a directory that already holds a
store (or a legacy flat directory of ``*.circuit.npz`` bundles from an
older run), fitting is skipped entirely: the server boots straight from
disk.
"""
import argparse
import os
import sys
import tempfile

sys.path.insert(0, "src")

import numpy as np

from repro.core.api import AutoTinyClassifier
from repro.core.encoding import EncodingConfig
from repro.data import load_dataset, train_test_split
from repro.serve.artifacts import (
    ArtifactStore,
    CIRCUIT_SUFFIX,
    load_legacy_registry_dir,
)
from repro.serve.circuits import CircuitRegistry, CircuitServer
from repro.serve.observability import (
    TraceRecorder,
    export_chrome,
    prometheus_text,
)
from repro.serve.planning import PlacementPolicy
from repro.utils.compile_cache import use_compile_cache

# tenant name → dataset (heterogeneous widths and class counts)
TENANTS = ("blood", "iris", "led", "wall-robot")


def fit_tenant(dataset: str, seed: int = 0):
    ds = load_dataset(dataset)
    train, test = train_test_split(ds, test_fraction=0.2, seed=seed)
    clf = AutoTinyClassifier(
        n_gates=60,
        encodings=(EncodingConfig("quantile", 2),),
        kappa=100, max_gens=600, seed=seed,
    )
    clf.fit(train.x, train.y, ds.n_classes)
    print(f"  {dataset:11s}: {ds.n_features} feats, {ds.n_classes} classes, "
          f"test bal-acc {clf.balanced_score(test.x, test.y):.3f}")
    return clf


def build_artifacts(artifact_dir: str):
    """Fit one classifier per tenant and persist the servable bundles."""
    print("fitting one tiny classifier per tenant ...")
    staging = CircuitRegistry()
    for name in TENANTS:
        staging.add(name, fit_tenant(name).to_servable())
    written = ArtifactStore(artifact_dir).put_registry(staging)
    print(f"  wrote {len(written)} artifact bundles to {artifact_dir}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--artifacts", default=None,
                    help="artifact directory; if it already holds a store "
                         f"(or legacy *{CIRCUIT_SUFFIX} bundles), fitting "
                         "is skipped")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="record the serving run and write a Chrome-trace/"
                         "Perfetto JSON (open at https://ui.perfetto.dev)")
    args = ap.parse_args()

    artifact_dir = args.artifacts or tempfile.mkdtemp(prefix="circuits-")
    is_store = ArtifactStore.is_store(artifact_dir)
    legacy = (not is_store and os.path.isdir(artifact_dir) and any(
        f.endswith(CIRCUIT_SUFFIX) for f in os.listdir(artifact_dir)))
    have = is_store or legacy
    if have:
        print(f"reusing artifact bundles in {artifact_dir} (no fitting)")
    else:
        build_artifacts(artifact_dir)

    # --- fleet restart: everything below runs from disk, no fit() ------
    registry = (load_legacy_registry_dir(artifact_dir) if legacy
                else ArtifactStore(artifact_dir).load_registry())
    tracer = TraceRecorder(enabled=bool(args.trace))
    server = CircuitServer(registry, tracer=tracer)
    print(f"\nbooted server from {len(registry)} on-disk artifacts "
          f"(backend={server.backend.name})")

    datasets = {name: load_dataset(name) for name in registry}
    print("serving mixed traffic (40 ticks, every tenant each tick) ...")
    rng = np.random.RandomState(0)
    mismatches = 0
    for _ in range(40):
        tickets = {}
        for name, ds in datasets.items():
            take = rng.randint(1, 48)
            idx = rng.randint(0, ds.x.shape[0], take)
            x = ds.x[idx].astype(np.float32)
            tickets[name] = (server.submit(name, x), x)
        report = server.tick()
        assert report.launches == 1 and report.tenants == len(registry)
        for name, (ticket, x) in tickets.items():
            got = server.result(ticket)
            want = registry.get(name).predict(x)  # per-model reference path
            mismatches += int(not np.array_equal(got, want))
    print(f"  {len(registry)} tenants per fused launch, "
          f"round-trip mismatches vs per-model predict: {mismatches}")

    for k, v in server.stats.report().items():
        print(f"  {k:23s} {v}")

    if args.trace:
        export_chrome(tracer, args.trace)
        print(f"\nwrote {len(tracer)} trace events to {args.trace} — "
              "open at https://ui.perfetto.dev")
        print("Prometheus snapshot of the same run:")
        print(prometheus_text(server_stats=server.stats))

    # --- declarative placement: same catalog, sharded plan -------------
    print("\nsharded serving (same catalog, PlacementPolicy(n_shards=2)) ...")
    sharded = CircuitServer(registry, policy=PlacementPolicy(n_shards=2))
    plan = sharded.plan()
    print(f"  {plan.n_shards} plan shards, hash {plan.content_hash[:12]}…; "
          "placement: "
          + ", ".join(f"{t}→s{plan.shard_of(t)}" for t in plan.tenants))
    sharded_mismatches = 0
    for name, ds in datasets.items():
        x = ds.x[:16].astype(np.float32)
        want = registry.get(name).predict(x)
        sharded_mismatches += int(
            not np.array_equal(sharded.predict(name, x), want)
        )
    print(f"  sharded vs per-model predict mismatches: {sharded_mismatches}")
    assert sharded_mismatches == 0

    if have:
        return  # pure-restart run: nothing to hot-swap against
    print("\nhot-swapping tenant 'blood' (generation-tagged recompile) ...")
    clf2 = fit_tenant("blood", seed=1)
    sc2 = clf2.to_servable()
    gen = registry.add("blood", sc2, replace=True)
    x2 = datasets["blood"].x[:10].astype(np.float32)
    got = server.predict("blood", x2)
    assert np.array_equal(got, sc2.predict(x2))
    print(f"  registry generation {gen}; new circuit served correctly")


if __name__ == "__main__":
    use_compile_cache()
    main()
