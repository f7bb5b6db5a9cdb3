"""AOT artifact cold-start: host-ready time and post-swap first-tick dip.

Measures the two latencies the AOT serving artifacts exist to kill:

1. **Cold boot** — a warm single-host fleet is exported with
   `FleetRouter.export_fleet`, then two fresh processes each bring a
   host to *ready* (boot + first fused tick served) against the same
   circuits: one trace-from-scratch (`CircuitServer` over the stored
   registry, jit traces in the first tick's critical path) and one from
   the artifact (`ServingHost.boot_from_artifact`, serialized
   executables preloaded).  The artifact child must report **zero jit
   traces** (`repro.runtime.aot.trace_count`) and answers bitwise equal
   to both the scratch child and the warm exporter; the headline is
   ``boot_speedup = scratch_ready / artifact_ready``.

2. **Pre-warmed swap** — serve to a steady p50 tick latency, register
   a new tenant, `recompile` + `swap_plan` (prewarm on, the default),
   and time the first post-swap tick.  The executable for the changed
   shard was compiled *and invoked once* before the generation fence,
   so the ratio of that first tick to where the new (one-tenant-larger)
   plan settles stays near 1.  A second swap with ``prewarm=False``
   records the contrast.

`check_bench.py` gates ``cold_traces_artifact == 0``, ``parity_ok``,
``boot_speedup >= CHECK_BENCH_MIN_BOOT_SPEEDUP`` (default 10) and
``postswap_ratio <= CHECK_BENCH_MAX_POSTSWAP_RATIO`` (default 1.5).

    PYTHONPATH=src python benchmarks/serve_coldstart.py [--tenants N]
        [--rows N] [--steady-ticks N] [--backend pallas] [--keep PATH]

Every leg (export, scratch boot, artifact boot, post-swap) runs in a
child process that re-invokes this file with ``--child``, one after
another; the parent runs no JAX computation, so on an accelerator each
child can own the chip in turn.  On CPU the ``pallas`` backend runs in
interpret mode, so absolute times are plumbing numbers — the *ratios*
are what transfer.
"""
from __future__ import annotations

import argparse
import hashlib
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmarks.common import save_json
from benchmarks.serve_circuits import make_fleet
from repro.serve.artifacts import ArtifactStore
from repro.serve.circuits import CircuitRegistry, CircuitServer
from repro.serve.fleet import FleetRouter, InProcTransport, ServingHost
from repro.utils.compile_cache import use_compile_cache

PROBE_SEED = 7  # children and parent must agree on the probe traffic


def row_set(rows: int) -> tuple[int, int]:
    """Two batch sizes landing in two distinct span buckets (``rows``
    stays within one 32-row word; ``rows + 32`` needs a second), so the
    artifact carries more than one executable per shard and *ready*
    means every steady launch shape is hot."""
    return (rows, rows + 32)


def probe_inputs(registry, rows: int) -> dict:
    """Deterministic per-tenant probe batches (constant rows/tenant →
    one span bucket per call)."""
    rng = np.random.RandomState(PROBE_SEED)
    return {
        t: rng.randn(rows, registry.get(t).encoder.n_features)
               .astype(np.float32)
        for t in sorted(registry)
    }


def serve_once(server, xs: dict) -> tuple:
    """One fused tick over every tenant; returns (answers, tick ms)."""
    tickets = {t: server.submit(t, x) for t, x in xs.items()}
    t0 = time.perf_counter()
    server.tick()
    tick_ms = (time.perf_counter() - t0) * 1e3
    outs = {t: server.result(k) for t, k in tickets.items()}
    return outs, tick_ms


def answers_digest(outs: dict) -> str:
    h = hashlib.sha256()
    for t in sorted(outs):
        h.update(t.encode())
        h.update(np.ascontiguousarray(outs[t]).tobytes())
    return h.hexdigest()


# ---------------------------------------------------------------- children

def run_child(mode: str, artifact_dir: str, backend: str, rows: int,
              n_tenants: int, steady_ticks: int, seed: int) -> None:
    """Run one leg and report it as a JSON line on stdout."""
    if mode == "export":
        export, digest = export_warm_fleet(
            artifact_dir, backend, n_tenants, rows, seed)
        print(json.dumps({"export": export, "digest": digest}))
    elif mode == "postswap":
        print(json.dumps(measure_postswap(
            artifact_dir, backend, rows, steady_ticks, seed)))
    else:
        boot_child(mode, artifact_dir, backend, rows)


def boot_child(mode: str, artifact_dir: str, backend: str,
               rows: int) -> None:
    """Bring one host to *ready* — boot + one fused tick served at
    every steady span bucket — and report timings + jit trace count as
    a JSON line on stdout."""
    import jax
    import jax.numpy as jnp

    from repro.runtime import aot

    # generic runtime init (XLA client, platform discovery) is paid once
    # per process by *both* legs and is not something serving artifacts
    # can address — warm it outside the timed window so the ratio
    # measures tracing, not process birth
    jax.block_until_ready(jnp.zeros((), jnp.uint32))
    aot.reset_trace_count()
    t0 = time.perf_counter()
    if mode == "artifact":
        host = ServingHost.boot_from_artifact("host0", artifact_dir)
        server, registry = host.server, host.registry
    else:  # scratch: same circuits, no executables — jit in the tick
        registry = ArtifactStore(artifact_dir).load_registry()
        server = CircuitServer(registry, backend=backend)
        server.plan()
    outs, tick_ms = {}, []
    for r in row_set(rows):
        o, ms = serve_once(server, probe_inputs(registry, r))
        outs.update({f"{t}@{r}": y for t, y in o.items()})
        tick_ms.append(ms)
    host_ready_s = time.perf_counter() - t0
    _, warm_tick_ms = serve_once(server, probe_inputs(registry, rows))
    print(json.dumps({
        "mode": mode,
        "host_ready_s": host_ready_s,
        "first_tick_ms": tick_ms[0],
        "tick_ms": tick_ms,
        "warm_tick_ms": warm_tick_ms,
        "traces": aot.trace_count(),
        "trace_tags": aot.trace_tags(),
        "digest": answers_digest(outs),
    }))


def spawn_child(mode: str, artifact_dir: str, backend: str, rows: int,
                n_tenants: int, steady_ticks: int, seed: int) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--child", mode,
         "--artifacts", artifact_dir, "--backend", backend,
         "--rows", str(rows), "--tenants", str(n_tenants),
         "--steady-ticks", str(steady_ticks), "--seed", str(seed)],
        capture_output=True, text=True, timeout=600, env=env, cwd=REPO,
    )
    if proc.returncode != 0:
        raise SystemExit(
            f"--child {mode} failed:\n{proc.stdout}\n{proc.stderr}"
        )
    return json.loads(proc.stdout.strip().splitlines()[-1])


# ------------------------------------------------------------ child legs

def export_warm_fleet(artifact_dir: str, backend: str, n_tenants: int,
                      rows: int, seed: int) -> tuple:
    """Build + warm a single-host fleet, export it; returns
    (export summary, digest of the warm answers)."""
    router = FleetRouter()
    host = ServingHost("host0", CircuitRegistry(), backend=backend)
    host.start()
    router.add_host("host0", InProcTransport(host))
    try:
        reg = make_fleet(n_tenants, np.random.RandomState(seed))
        for t in sorted(reg):
            router.register(t, [reg.get(t)])
        warm = {}
        for r in row_set(rows):
            for t, x in probe_inputs(reg, r).items():
                warm[f"{t}@{r}"] = router.submit(t, x).result(timeout=120)
        export = router.export_fleet(artifact_dir)
    finally:
        router.close()
    return export, answers_digest(warm)


def measure_postswap(artifact_dir: str, backend: str, rows: int,
                     steady_ticks: int, seed: int) -> dict:
    """Steady p50 tick latency, then a prewarmed swap's first tick
    (and an unwarmed swap's, for contrast)."""
    registry = ArtifactStore(artifact_dir).load_registry()
    server = CircuitServer(registry, backend=backend)
    rows = row_set(rows)[1]  # the heavier batch: steadier tick timings
    xs = probe_inputs(registry, rows)
    serve_once(server, xs)  # warm the launch path
    ticks = [serve_once(server, xs)[1] for _ in range(steady_ticks)]
    steady_p50 = float(np.percentile(ticks, 50))

    rng = np.random.RandomState(PROBE_SEED + 1)

    def grow_and_swap(name: str, extra_seed: int, prewarm: bool) -> float:
        sc = make_fleet(1, np.random.RandomState(extra_seed)).get("tenant0")
        registry.add(name, sc)
        compiled = server.compiler.recompile(registry.catalog(),
                                             server.peek_plan())
        server.swap_plan(compiled, reason="coldstart-bench",
                         prewarm=prewarm)
        xs[name] = rng.randn(rows, sc.encoder.n_features) \
                      .astype(np.float32)
        return serve_once(server, xs)[1]

    # three independent grow→prewarmed-swap rounds, median-aggregated:
    # a single first-tick sample is one scheduler quantum away from a
    # flaky gate.  The dip baseline is where each *new* plan settles —
    # it serves one more tenant than its predecessor, so comparing
    # against the pre-swap p50 would charge the swap for workload growth
    firsts, settles, ratios = [], [], []
    for k in range(3):
        first = grow_and_swap(f"newcomer_{k}", seed + 101 + k, True)
        settled = [serve_once(server, xs)[1] for _ in range(steady_ticks)]
        p50 = float(np.percentile(settled, 50))
        firsts.append(first)
        settles.append(p50)
        ratios.append(first / max(p50, 1e-9))
    unwarmed_ms = grow_and_swap("newcomer_unwarmed", seed + 999, False)
    return {
        "steady_p50_tick_ms": round(steady_p50, 3),
        "postswap_steady_p50_tick_ms": round(
            float(np.median(settles)), 3),
        "postswap_first_tick_ms": round(float(np.median(firsts)), 3),
        "postswap_ratio": round(float(np.median(ratios)), 3),
        "postswap_ratios": [round(r, 3) for r in ratios],
        "unwarmed_swap_first_tick_ms": round(unwarmed_ms, 3),
    }


# ------------------------------------------------------------------ parent

def dir_bytes(path: str) -> int:
    return sum(os.path.getsize(os.path.join(r, f))
               for r, _, fs in os.walk(path) for f in fs)


def run(backend: str = "pallas", n_tenants: int = 6, rows: int = 8,
        steady_ticks: int = 30, seed: int = 0,
        keep: "str | None" = None) -> dict:
    artifact_dir = keep or tempfile.mkdtemp(prefix="coldstart_artifact_")
    legs = {}
    try:
        # one child at a time: each owns the device while it runs
        for mode in ("export", "scratch", "artifact", "postswap"):
            legs[mode] = spawn_child(mode, artifact_dir, backend, rows,
                                     n_tenants, steady_ticks, seed)
        store_bytes = dir_bytes(artifact_dir)
    finally:
        if keep is None:
            shutil.rmtree(artifact_dir, ignore_errors=True)

    export, warm_digest = legs["export"]["export"], legs["export"]["digest"]
    scratch, artifact, post = (
        legs["scratch"], legs["artifact"], legs["postswap"])
    rep = {
        "backend": backend,
        "n_tenants": n_tenants,
        "probe_rows": rows,
        "executables_exported": export["executables"],
        "artifact_bytes": store_bytes,
        "host_ready_scratch_s": round(scratch["host_ready_s"], 3),
        "host_ready_artifact_s": round(artifact["host_ready_s"], 3),
        "boot_speedup": round(
            scratch["host_ready_s"] / max(artifact["host_ready_s"], 1e-9),
            2),
        "first_tick_scratch_ms": round(scratch["first_tick_ms"], 3),
        "first_tick_artifact_ms": round(artifact["first_tick_ms"], 3),
        "cold_traces_scratch": scratch["traces"],
        "cold_traces_artifact": artifact["traces"],
        "artifact_trace_tags": artifact["trace_tags"],
        "parity_ok": (scratch["digest"] == warm_digest
                      and artifact["digest"] == warm_digest),
    }
    rep.update(post)

    # acceptance invariants (check_bench.py re-gates the committed copy)
    assert rep["cold_traces_artifact"] == 0, rep["artifact_trace_tags"]
    assert rep["cold_traces_scratch"] > 0, (
        "scratch leg traced nothing — the comparison is vacuous"
    )
    assert rep["parity_ok"], "cold-boot answers diverged from warm host"
    return rep


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tenants", type=int, default=6)
    ap.add_argument("--rows", type=int, default=8,
                    help="rows per tenant per tick (constant → one "
                         "span bucket)")
    ap.add_argument("--steady-ticks", type=int, default=30)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--backend", default="pallas",
                    help="AOT-capable execution backend to bench")
    ap.add_argument("--keep", default=None, metavar="PATH",
                    help="export the artifact here and keep it "
                         "(default: temp dir, removed)")
    ap.add_argument("--child", default=None,
                    choices=["export", "scratch", "artifact", "postswap"],
                    help=argparse.SUPPRESS)  # internal subprocess mode
    ap.add_argument("--artifacts", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args()

    if args.child:
        run_child(args.child, args.artifacts, args.backend, args.rows,
                  args.tenants, args.steady_ticks, args.seed)
        return

    rep = run(backend=args.backend, n_tenants=args.tenants,
              rows=args.rows, steady_ticks=args.steady_ticks,
              seed=args.seed, keep=args.keep)
    print(f"--- backend={rep['backend']} ({rep['n_tenants']} tenants, "
          f"{rep['executables_exported']} executables, "
          f"{rep['artifact_bytes']} bytes) ---")
    for k in ("host_ready_scratch_s", "host_ready_artifact_s",
              "boot_speedup", "first_tick_scratch_ms",
              "first_tick_artifact_ms", "cold_traces_scratch",
              "cold_traces_artifact", "parity_ok", "steady_p50_tick_ms",
              "postswap_steady_p50_tick_ms", "postswap_first_tick_ms",
              "postswap_ratio", "unwarmed_swap_first_tick_ms"):
        print(f"  {k:28s} {rep[k]}")
    save_json("serve_coldstart", [rep])


if __name__ == "__main__":
    use_compile_cache()
    main()
