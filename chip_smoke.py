"""Bring-up smoke test: the search and the serving path on a TPU.

    python chip_smoke.py              # one chip: device, search, serve
    python chip_smoke.py --chips 4    # only the paths that span 4 chips

One chip runs three phases through the entry points a user calls:

  * device — JAX's first device must be a TPU; nothing falls back to
    the CPU or to Pallas interpret mode;
  * search — `AutoTinyClassifier.fit` at the paper's width (300 gates,
    the "full" gate set, λ=4, κ=300, four encodings) on the higgs shape,
    once on the "pallas" backend and once on "ref"; the fit records and
    the chosen genome must agree bit for bit.  A few generations on the
    christine shape (quantize-4) cover the widest VMEM table;
  * serve — a one-shard `CircuitServer` with the fitted circuit and the
    `benchmarks/serve_circuits.py` shape mix answers a few ticks, bitwise
    equal to a "ref" server; the fleet is then exported and booted with
    `ServingHost.boot_from_artifact`, which must trace nothing, compile
    nothing and answer the same.

``--chips 4`` runs only a four-shard server (one shard per chip) and
four one-chip `ServingHost`s behind a `FleetRouter`, each against
single-chip "ref" answers.

Data comes from the seeded Table-1 generators; nothing is downloaded.
Phase lines go to stdout; the last stdout line is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``.  Any failure
exits non-zero without that line.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
SEED = 0
SEARCH_DATASET, SEARCH_GENS = "higgs", 400   # paper: 8000 generations
WIDE_DATASET, WIDE_GENS = "christine", 20    # the widest VMEM table
TICKS = 5


class SmokeFailure(RuntimeError):
    """A phase produced a wrong or missing result."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def log(phase: str, **fields) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


# ------------------------------------------------------------------ device

def phase_device(n_chips: int):
    import jax

    devices = jax.devices()
    first = devices[0]
    check(first.platform == "tpu",
          f"JAX's platform is {first.platform!r}, not 'tpu'")
    check(len(devices) >= n_chips,
          f"{n_chips} chips asked for, JAX sees {len(devices)}")
    log("device", platform=first.platform, kind=repr(first.device_kind),
        count=len(devices))
    return devices


# ------------------------------------------------------------------ search

def fit_both(x, y, n_classes, encodings, gens):
    """Fit with "pallas" then "ref" from the same seed; returns the two
    fitted classifiers, after checking they agree bit for bit."""
    import jax

    from repro.configs import tiny_classifier as paper
    from repro.core.api import AutoTinyClassifier

    fitted = {}
    for backend in ("pallas", "ref"):
        clf = AutoTinyClassifier(
            n_gates=paper.N_GATES, fn_set=paper.FN_SET, encodings=encodings,
            lam=paper.PAPER_EVOLVE.lam, gamma=paper.PAPER_EVOLVE.gamma,
            kappa=paper.PAPER_EVOLVE.kappa, max_gens=gens,
            backend=backend, seed=SEED,
        )
        t0 = time.perf_counter()
        clf.fit(x, y, n_classes)
        jax.block_until_ready(clf.genome_)
        fitted[backend] = (clf, time.perf_counter() - t0)
    (pal, _), (ref, _) = fitted["pallas"], fitted["ref"]
    check(pal.records_ == ref.records_,
          f"fit records differ: pallas {pal.records_} ref {ref.records_}")
    for field in ("gate_fn", "edge_src", "out_src"):
        check(np.array_equal(np.asarray(getattr(pal.genome_, field)),
                             np.asarray(getattr(ref.genome_, field))),
              f"chosen genome differs in {field}")
    return fitted


def kernel_lowering_check(n_inputs: int, n_words: int, n_out: int) -> None:
    """The "pallas" backend resolved to native kernels, and its search
    launch lowers to a Mosaic custom call."""
    import jax
    import jax.numpy as jnp

    from repro.configs import tiny_classifier as paper
    from repro.runtime import get_backend

    backend = get_backend("pallas")
    check(backend.capabilities().device_kinds == ("tpu",),
          "the 'pallas' backend resolved to interpret mode")
    lam, n = paper.PAPER_EVOLVE.lam, paper.N_GATES
    text = jax.jit(backend.eval_population).lower(
        jax.ShapeDtypeStruct((lam, n), jnp.int32),
        jax.ShapeDtypeStruct((lam, n, 2), jnp.int32),
        jax.ShapeDtypeStruct((lam, n_out), jnp.int32),
        jax.ShapeDtypeStruct((n_inputs, n_words), jnp.uint32),
    ).as_text()
    check("tpu_custom_call" in text,
          "the search launch has no tpu_custom_call")
    log("search", tpu_custom_call="present", interpret=False)


def phase_search():
    """Returns the higgs-shape fit (pallas) and its dataset for the serve
    phase."""
    from repro.configs import tiny_classifier as paper
    from repro.core import encoding as E
    from repro.data import load_dataset

    gens = SEARCH_GENS
    ds = load_dataset(SEARCH_DATASET)
    log("search", dataset=SEARCH_DATASET, rows=ds.n_rows,
        features=ds.n_features, classes=ds.n_classes, gates=paper.N_GATES,
        fn_set=paper.FN_SET, lam=paper.PAPER_EVOLVE.lam, kappa=paper.PAPER_EVOLVE.kappa,
        encodings=len(paper.PAPER_ENCODINGS),
        max_gens=f"{gens} (paper: {paper.PAPER_EVOLVE.max_gens})")
    fitted = fit_both(ds.x, ds.y, ds.n_classes, paper.PAPER_ENCODINGS, gens)
    pal = fitted["pallas"][0]
    log("search", **{f"fit_{b}_s": round(s, 3)
                     for b, (_, s) in fitted.items()},
        generations=[r.generations for r in pal.records_],
        val_fitness=[round(r.val_fitness, 4) for r in pal.records_],
        pallas_eq_ref="records+genome bitwise")

    widest = max(paper.PAPER_ENCODINGS, key=lambda e: e.bits)
    kernel_lowering_check(ds.n_features * widest.bits, E.n_words(ds.n_rows),
                          pal.spec_.n_outputs)

    wd = load_dataset(WIDE_DATASET)
    q4 = (E.EncodingConfig("quantize", 4),)
    wfit = fit_both(wd.x, wd.y, wd.n_classes, q4, WIDE_GENS)
    log("search", dataset=WIDE_DATASET, rows=wd.n_rows,
        features=wd.n_features, input_bits=wd.n_features * 4,
        max_gens=WIDE_GENS,
        **{f"fit_{b}_s": round(s, 3) for b, (_, s) in wfit.items()},
        pallas_eq_ref="records+genome bitwise")
    return pal, ds


# ------------------------------------------------------------------- serve

def shape_mix(n_tenants: int):
    sys.path.insert(0, ROOT)  # benchmarks.serve_circuits: the shape mix
    from benchmarks.serve_circuits import make_fleet

    return make_fleet(n_tenants, np.random.RandomState(SEED))


def traffic(registry, ticks: int, rng, *, own_rows=None):
    """Per tick, a batch of (tenant, rows) for every tenant; rows of the
    tenant named in ``own_rows`` come from its own dataset."""
    out = []
    for _ in range(ticks):
        batch = []
        for t in registry:
            n = int(rng.randint(1, 65))
            if own_rows is not None and t == own_rows[0]:
                x = own_rows[1][rng.randint(0, len(own_rows[1]), n)]
            else:
                f = registry.get(t).encoder.n_features
                x = rng.randn(n, f).astype(np.float32)
            batch.append((t, np.asarray(x, np.float32)))
        out.append(batch)
    return out


def serve_ticks(server, batches):
    """Submit each batch and tick once; returns per-request answers and
    per-tick wall seconds (a tick returns after its readback)."""
    answers, secs = [], []
    for batch in batches:
        tickets = [server.submit(t, x) for t, x in batch]
        t0 = time.perf_counter()
        server.tick()
        secs.append(time.perf_counter() - t0)
        answers.append([server.result(k) for k in tickets])
    return answers, secs


def mismatches(got, want) -> int:
    return sum(not np.array_equal(g, w)
               for gb, wb in zip(got, want) for g, w in zip(gb, wb))


def phase_serve(fitted, fitted_rows, out_dir: str) -> None:
    from repro.runtime import aot
    from repro.serve.circuits import CircuitRegistry, CircuitServer
    from repro.serve.fleet import FleetRouter, InProcTransport, ServingHost

    registry = shape_mix(8)
    registry.add("fitted", fitted)
    batches = traffic(registry, TICKS, np.random.RandomState(SEED + 1),
                      own_rows=("fitted", fitted_rows))
    ref_answers, _ = serve_ticks(CircuitServer(registry, backend="ref"),
                                 batches)

    # a one-host fleet: the host is in-process, so ticks drive its server
    router = FleetRouter()
    host = ServingHost("host0", CircuitRegistry(), backend="pallas").start()
    router.add_host("host0", InProcTransport(host))
    try:
        for t in registry:
            router.register(t, registry.members(t))
        plan = host.server.plan()
        slots = [s.n_slots for s in plan.shards]
        gates = max(sc.spec.n_nodes for t in registry
                    for sc in registry.members(t))
        check(len(slots) == 1 and slots[0] >= 8,
              f"expected >= 8 slots in one shard, got {slots}")
        answers, secs = serve_ticks(host.server, batches)
        bad = mismatches(answers, ref_answers)
        log("serve", slots=slots[0], shards=len(slots), max_gates=gates,
            span_align=plan.span_align, ticks=TICKS,
            first_tick_s=round(secs[0], 4),
            p50_tick_s=round(float(np.median(secs[1:] or secs)), 4),
            aot_compiles=host.server.aot_stats["compiles"],
            mismatches_vs_ref=bad)
        check(bad == 0, f"{bad} pallas answers differ from ref")
        t0 = time.perf_counter()
        export = router.export_fleet(out_dir)
        export_s = time.perf_counter() - t0
    finally:
        router.close()

    aot.reset_trace_count()
    t0 = time.perf_counter()
    booted = ServingHost.boot_from_artifact("host0", out_dir)
    boot_s = time.perf_counter() - t0
    answers, secs = serve_ticks(booted.server, batches)
    stats = booted.server.aot_stats
    bad = mismatches(answers, ref_answers)
    log("serve", artifact_executables=export["executables"],
        export_s=round(export_s, 3), boot_s=round(boot_s, 3),
        traces=aot.trace_count(), loads=stats["loads"],
        load_failures=stats["load_failures"], compiles=stats["compiles"],
        first_tick_s=round(secs[0], 4), mismatches_vs_ref=bad)
    check(export["executables"] >= 1, "the artifact holds no executable")
    check(aot.trace_count() == 0,
          f"artifact boot traced: {aot.trace_tags()}")
    check(stats["load_failures"] == 0 and stats["compiles"] == 0,
          f"artifact boot loaded {stats['loads']}, failed "
          f"{stats['load_failures']}, compiled {stats['compiles']}")
    check(bad == 0, f"{bad} booted answers differ from ref")


# --------------------------------------------------------------- 4 chips

def tensor_devices(server) -> "dict[int, set[int]]":
    """Device ids holding each plan shard's genome tensors — the inputs,
    and so the outputs, of that shard's launches."""
    plan = server.plan()
    return {
        s.shard: {d.id for t in server._dev[s.content_hash]
                  for d in t.devices()}
        for s in plan.shards
    }


def phase_four_chips(devices) -> None:
    from repro.serve.circuits import CircuitRegistry, CircuitServer
    from repro.serve.fleet import FleetRouter, InProcTransport, ServingHost
    from repro.serve.planning import PlacementPolicy

    registry = shape_mix(16)
    batches = traffic(registry, TICKS, np.random.RandomState(SEED + 2))
    ref_answers, _ = serve_ticks(CircuitServer(registry, backend="ref"),
                                 batches)

    sharded = CircuitServer(registry, backend="pallas",
                            policy=PlacementPolicy(n_shards=4))
    answers, secs = serve_ticks(sharded, batches)
    on = tensor_devices(sharded)
    bad = mismatches(answers, ref_answers)
    log("4chips", path="n_shards=4", shard_devices=on,
        first_tick_s=round(secs[0], 4),
        p50_tick_s=round(float(np.median(secs[1:] or secs)), 4),
        mismatches_vs_ref=bad)
    check(bad == 0, f"{bad} sharded answers differ from ref")
    check(len(on) == 4 and all(len(d) == 1 for d in on.values())
          and len(set().union(*on.values())) == 4,
          f"shards are not one per chip: {on}")

    router = FleetRouter()
    hosts = [ServingHost(f"host{i}", CircuitRegistry(), backend="pallas",
                         device=devices[i]).start() for i in range(4)]
    try:
        for h in hosts:
            router.add_host(h.host_id, InProcTransport(h))
        for t in registry:
            router.register(t, registry.members(t))
        t0 = time.perf_counter()
        answers = [[router.submit(t, x).result(timeout=300)
                    for t, x in batch] for batch in batches]
        wall = time.perf_counter() - t0
        bad = mismatches(answers, ref_answers)
        placed = {h.host_id: tensor_devices(h.server)[0] if len(h.registry)
                  else set() for h in hosts}
        log("4chips", path="4 ServingHosts behind FleetRouter",
            tenants={h.host_id: len(h.registry) for h in hosts},
            host_devices=placed, requests=sum(map(len, batches)),
            wall_s=round(wall, 3), mismatches_vs_ref=bad)
        check(bad == 0, f"{bad} routed answers differ from ref")
        check(all(placed[h.host_id] == {devices[i].id}
                  for i, h in enumerate(hosts)),
              f"hosts are not one per chip: {placed}")
    finally:
        router.close()


# -------------------------------------------------------------------- main

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the paths that span four chips")
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.utils.compile_cache import use_compile_cache

    cache_dir = use_compile_cache()
    t_all = time.perf_counter()
    devices = phase_device(args.chips)
    log("device", compile_cache=cache_dir)
    first = devices[0]
    if args.chips == 4:
        t0 = time.perf_counter()
        phase_four_chips(devices)
        log("4chips", phase_s=round(time.perf_counter() - t0, 3))
    else:
        t0 = time.perf_counter()
        fitted, ds = phase_search()
        log("search", phase_s=round(time.perf_counter() - t0, 3))
        t0 = time.perf_counter()
        with tempfile.TemporaryDirectory(prefix="chip_smoke_") as out_dir:
            phase_serve(fitted.to_servable(), ds.x, out_dir)
        log("serve", phase_s=round(time.perf_counter() - t0, 3))
    log("total", wall_s=round(time.perf_counter() - t_all, 3))
    print(json.dumps({"ok": True, "device": {
        "platform": first.platform, "kind": first.device_kind,
        "count": len(devices),
    }}), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as err:
        print(f"chip_smoke FAILED: {err}", file=sys.stderr, flush=True)
        sys.exit(1)
