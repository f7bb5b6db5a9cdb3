"""Plan-aware autoscaling under ramping, skewed, churning open-loop load.

Three phases of open-loop Poisson traffic (the request schedule is drawn
up front and replayed on the wall clock, so a slow server cannot slow
the offered load) drive an `AsyncCircuitServer` with an
`AutoscaleController` polled on a fixed control cadence:

  * **steady** — balanced traffic across every tenant: the baseline.
  * **skew+churn** — a configurable fraction of the offered load piles
    onto the tenants of one shard while new tenants are hot-added and
    old ones hot-removed; the occupancy-imbalance trigger should fire a
    telemetry-weighted rebalance mid-traffic.
  * **recover** — balanced again (including the churned-in tenants),
    measuring the stack after the swaps.

The report carries the keys the BENCH trajectory gates (qps, miss_rate,
n_rebalances, mean_swap_ms, shards_reused_frac) plus per-phase QPS and
miss rates — throughput before, during, and after rebalances.  If the
hysteresis policy never fired organically by the recovery phase (slow
CI runners can compress the skew window below the policy's patience),
one scripted grow is applied so the swap path is always measured; it is
counted separately as ``forced_rebalances``.

Acceptance invariants asserted on every run: at least one rebalance
under load, zero lost requests (every admitted future resolves exactly
once), a positive reused-shard fraction (unchanged shards were not
re-uploaded), and spot-check parity against the per-model predict path.

    PYTHONPATH=src python benchmarks/serve_autoscale.py [--backend ref]
        [--qps 150] [--phase-s 1.2] [--shards 3] [--skew 0.85]
"""
from __future__ import annotations

import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.common import save_json, trace_dest
from benchmarks.serve_circuits import SHAPES, make_fleet
from repro import runtime
from repro.core import encoding as E
from repro.core import gates
from repro.core.api import ServableCircuit
from repro.core.genome import CircuitSpec, init_genome
from repro.serve.async_frontend import AsyncCircuitServer
from repro.serve.autoscale import (
    AutoscaleController,
    AutoscaleDecision,
    HysteresisPolicy,
)
from repro.serve.circuits import CircuitServer, TenantQoS
from repro.serve.observability import TraceRecorder, export_chrome
from repro.serve.planning import PlacementPolicy
from repro.utils.compile_cache import use_compile_cache


def make_extra(i: int, rng) -> ServableCircuit:
    """A churn-in tenant (same shape family as the base fleet)."""
    import jax

    f, b, n, c = SHAPES[i % len(SHAPES)]
    enc = E.fit_encoder(rng.randn(256, f).astype(np.float32),
                        E.EncodingConfig("quantile", b))
    n_out = max(1, int(np.ceil(np.log2(max(c, 2)))))
    spec = CircuitSpec(enc.n_bits_total, n, n_out, gates.FULL_FS)
    return ServableCircuit(
        spec, init_genome(jax.random.key(1000 + i), spec), enc, c,
    )


def phase_schedule(tenants, weights, registry_circuits, *, t0, duration_s,
                   qps, mean_rows, rng):
    """Open-loop arrivals for one phase: (t, tenant, rows) sorted by time.
    ``weights[tenant]`` splits the offered QPS across tenants."""
    total_w = sum(weights.values())
    events = []
    for tenant in tenants:
        rate = qps * weights[tenant] / total_w
        if rate <= 0:
            continue
        n_feats = registry_circuits[tenant].encoder.n_features
        t = 0.0
        while True:
            t += rng.exponential(1.0 / rate)
            if t >= duration_s:
                break
            rows = 1 + rng.poisson(mean_rows)
            events.append((
                t0 + t, tenant,
                rng.randn(rows, n_feats).astype(np.float32),
            ))
    events.sort(key=lambda e: e[0])
    return events


def run(backend: str = "ref", n_tenants: int = 9, qps: float = 150.0,
        phase_s: float = 1.2, mean_rows: int = 4, shards: int = 3,
        skew: float = 0.85, churn: int = 2, control_interval_s: float = 0.12,
        deadline_s: float = 2.5, seed: int = 0,
        trace_path: "str | None" = None) -> dict:
    rng = np.random.RandomState(seed)
    registry = make_fleet(n_tenants, rng)
    base_tenants = list(registry)
    # staleness-paced launches (small max_wait) instead of riding the
    # deadline edge: a launch that fires at deadline − EWMA is late the
    # moment latency jitters past the estimate
    qos = TenantQoS(
        max_batch=256, max_wait_s=min(0.06, 0.25 * deadline_s),
        default_deadline_s=deadline_s,
    )
    for tenant in base_tenants:
        registry.set_qos(tenant, qos)
    tracer = TraceRecorder(enabled=bool(trace_path))
    server = CircuitServer(
        registry, backend=backend,
        policy=PlacementPolicy(n_shards=shards), tracer=tracer,
    )
    frontend = AsyncCircuitServer(server)
    controller = AutoscaleController(
        frontend,
        HysteresisPolicy(
            patience=2, cooldown_s=4 * control_interval_s,
            imbalance_high=1.5, imbalance_low=1.15,
            # one grow at most: every extra shard re-shapes launches and
            # the resulting jit recompiles stall a CPU CI runner far more
            # than they buy
            max_shards=shards + 1,
            # shards time-share whatever devices the runner exposes (the
            # benchmark measures plan churn, not device parallelism), so
            # the topology cap must not veto the scripted trajectory on
            # a 1-device CI host
            device_cap=shards + 1,
            # CI runners are noisy; leave headroom/miss growth to real
            # deployments and let imbalance drive the organic trigger
            grow_headroom=0.0, miss_rate_high=0.5,
        ),
    )

    # warm the launch path outside the measured window (cold tracing
    # would charge seconds to whichever requests ride the first fire)
    circuits = {t: registry.get(t) for t in registry}
    for rows in (1, 33):
        server.step([
            (t, rng.randn(rows, circuits[t].encoder.n_features)
             .astype(np.float32))
            for t in base_tenants
        ])
    server.reset_stats()
    tracer.clear()  # drop warmup events: the trace covers the timed window

    # phase traffic: steady → skew+churn → recover
    hot = [t for t in base_tenants if server.plan().shard_of(t) == 0]
    churn_in = {f"new{i}": make_extra(i, rng) for i in range(churn)}
    churn_out = [t for t in base_tenants if t not in hot][:churn]
    balanced = {t: 1.0 for t in base_tenants}
    skewed = {
        t: (skew / max(len(hot), 1) if t in hot
            else (1.0 - skew) / max(len(base_tenants) - len(hot), 1))
        for t in base_tenants if t not in churn_out
    }
    recovered = {
        t: 1.0 for t in (set(base_tenants) - set(churn_out))
        | set(churn_in)
    }
    all_circuits = dict(circuits)
    all_circuits.update(churn_in)
    phases = [
        ("steady", 0.0, balanced),
        ("skew+churn", phase_s, skewed),
        ("recover", 2 * phase_s, recovered),
    ]
    schedule = []
    for name, t0, weights in phases:
        schedule.extend(phase_schedule(
            list(weights), weights, all_circuits,
            t0=t0, duration_s=phase_s, qps=qps,
            mean_rows=mean_rows, rng=rng,
        ))
    schedule.sort(key=lambda e: e[0])
    # churn actions land mid-skew-phase: removals only for tenants whose
    # traffic ended with phase one, so no request races its own tenant
    churn_t = phase_s * 1.5
    actions = [(churn_t + 0.02 * i, "add", name)
               for i, name in enumerate(churn_in)]
    actions += [(churn_t + 0.05 + 0.02 * i, "remove", name)
                for i, name in enumerate(churn_out)]
    actions.sort(key=lambda a: a[0])

    results = []   # (tenant, future, x)
    rejected = 0
    phase_marks = []  # (elapsed, submitted, completed, misses) at boundary
    forced = 0

    def mark():
        fs = frontend.stats
        phase_marks.append((
            time.monotonic() - t_start, fs.submitted, fs.completed,
            fs.deadline_misses,
        ))

    next_phase = 1
    next_control = 0.0
    with frontend:
        t_start = time.monotonic()
        for t_arr, tenant, x in schedule:
            while actions and actions[0][0] <= t_arr:
                _, op, name = actions.pop(0)
                if op == "add":
                    registry.add(name, churn_in[name], qos=qos)
                else:
                    registry.remove(name)
            if next_phase < len(phases) and t_arr >= phases[next_phase][1]:
                mark()
                next_phase += 1
            now = time.monotonic() - t_start
            if now >= next_control:
                controller.step()
                next_control = now + control_interval_s
            delay = t_start + t_arr - time.monotonic()
            if delay > 0:
                time.sleep(delay)
            try:
                results.append((tenant, frontend.enqueue(tenant, x), x))
            except Exception:  # noqa: BLE001 — admission reject / churn race
                rejected += 1
            if next_phase == len(phases) and not controller.events:
                # organic trigger never fired (compressed window on a slow
                # runner): script one grow so the swap path is measured
                forced += 1
                controller.apply(AutoscaleDecision(
                    "grow", server.policy.n_shards + 1,
                    "forced fallback (benchmark determinism)",
                ))
        wall = time.monotonic() - t_start
    # context exit stops + drains: every future is resolved now — the
    # final mark lands after the drain so the recover phase counts its
    # own completions
    mark()

    failed = 0
    parity_mismatches = 0
    lost = 0
    for i, (tenant, fut, x) in enumerate(results):
        if not fut.done():
            lost += 1
            continue
        if fut.exception() is not None:
            failed += 1
            continue
        if i % 25 == 0:  # spot-check parity vs the per-model path
            want = all_circuits[tenant].predict(x)
            parity_mismatches += int(not np.array_equal(fut.result(), want))

    srv = server.stats.report()
    fs = frontend.stats.report()
    phase_stats = []
    prev = (0.0, 0, 0, 0)
    for (name, _, _), cur in zip(phases, phase_marks):
        dt = max(cur[0] - prev[0], 1e-9)
        d_sub = cur[1] - prev[1]
        phase_stats.append({
            "phase": name,
            "qps": round((cur[2] - prev[2]) / dt, 1),
            "miss_rate": round((cur[3] - prev[3]) / max(d_sub, 1), 4),
        })
        prev = cur

    rep = {
        "backend": srv["backend"],
        "qps": round(fs["completed"] / max(wall, 1e-9), 1),
        "miss_rate": fs["miss_rate"],
        "n_rebalances": srv["n_rebalances"],
        "mean_swap_ms": srv["mean_swap_ms"],
        "shards_reused_frac": srv["shards_reused_frac"],
        "forced_rebalances": forced,
        "rebalance_events": [
            {"action": e.action, "reason": e.reason,
             "from_shards": e.from_shards, "to_shards": e.to_shards,
             "shards_reused": e.shards_reused,
             "shards_rebuilt": e.shards_rebuilt,
             "inflight_requests": e.inflight_requests,
             "swap_ms": round(e.swap_ms, 3)}
            for e in controller.events
        ],
        "phases": phase_stats,
        "n_tenants": n_tenants,
        "initial_shards": shards,
        "final_shards": server.policy.n_shards,
        "skew": skew,
        "churn_in": len(churn_in),
        "churn_out": len(churn_out),
        "offered_qps": round(len(schedule) / (3 * phase_s), 1),
        "offered_requests": len(schedule),
        "rejected_at_door": rejected,
        "failed_requests": failed,
        "lost_requests": lost,
        "parity_mismatches": parity_mismatches,
        "wall_s": round(wall, 3),
        "frontend": fs,
        "server": srv,
    }
    if trace_path:
        export_chrome(tracer, trace_path)
        rep.update({
            "trace_path": trace_path, "trace_events": len(tracer),
        })
    # acceptance invariants: a rebalance happened under load, no request
    # was lost, unchanged shards were reused, parity held
    assert rep["n_rebalances"] >= 1, "no plan swap was exercised"
    assert rep["lost_requests"] == 0, f"{lost} futures never resolved"
    assert rep["shards_reused_frac"] > 0, (
        "every swap rebuilt every shard — content-hash reuse is broken"
    )
    assert rep["parity_mismatches"] == 0
    assert fs["completed"] + fs["shed"] == fs["submitted"], (
        "request accounting leaked across the swaps"
    )
    return rep


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tenants", type=int, default=9)
    ap.add_argument("--qps", type=float, default=150.0)
    ap.add_argument("--phase-s", type=float, default=1.2)
    ap.add_argument("--mean-rows", type=int, default=4)
    ap.add_argument("--shards", type=int, default=3,
                    help="initial plan shards (autoscaling moves it)")
    ap.add_argument("--skew", type=float, default=0.85,
                    help="fraction of phase-2 load aimed at one shard's "
                         "tenants")
    ap.add_argument("--churn", type=int, default=2,
                    help="tenants hot-added and hot-removed mid-run")
    ap.add_argument("--control-interval-s", type=float, default=0.12)
    ap.add_argument("--deadline-s", type=float, default=2.5,
                    help="per-request deadline (generous: CI measures "
                         "swaps, not deadline pressure)")
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
                  phase_s=args.phase_s, mean_rows=args.mean_rows,
                  shards=args.shards, skew=args.skew, churn=args.churn,
                  control_interval_s=args.control_interval_s,
                  deadline_s=args.deadline_s,
                  trace_path=trace_dest(args.trace, backend, backends))
        results.append(rep)
        print(f"--- backend={rep['backend']} ({rep['n_tenants']} tenants, "
              f"{rep['offered_qps']} req/s offered, shards "
              f"{rep['initial_shards']}→{rep['final_shards']}) ---")
        for k in ("qps", "miss_rate", "n_rebalances", "forced_rebalances",
                  "mean_swap_ms", "shards_reused_frac", "failed_requests",
                  "rejected_at_door", "parity_mismatches"):
            print(f"  {k:22s} {rep[k]}")
        for ph in rep["phases"]:
            print(f"  phase {ph['phase']:12s} qps={ph['qps']:8.1f} "
                  f"miss_rate={ph['miss_rate']}")
        for ev in rep["rebalance_events"]:
            print(f"  swap {ev['action']:9s} {ev['from_shards']}→"
                  f"{ev['to_shards']} shards, reused {ev['shards_reused']}/"
                  f"{ev['shards_reused'] + ev['shards_rebuilt']}, "
                  f"{ev['swap_ms']:.1f} ms, "
                  f"{ev['inflight_requests']} in flight ({ev['reason']})")
        pb = rep["server"]["phase_breakdown"]
        print(f"  host/kernel share      {pb['host_share']} / "
              f"{pb['kernel_share']}")
        if rep.get("trace_path"):
            print(f"  trace                  {rep['trace_path']} "
                  f"({rep['trace_events']} events)")
    save_json("serve_autoscale", results)


if __name__ == "__main__":
    use_compile_cache()
    main()
