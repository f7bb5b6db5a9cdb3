"""End-to-end tracing & telemetry (`repro.observability`,
`repro.serve.observability`).

Four layers of coverage:

  * `TraceRecorder` in isolation — fake clock, ring bounding, the
    zero-allocation disabled path.
  * The profiler mirror — spans on the JAX profiler's host plane and in
    the capture table while a capture records, and nothing outside one.
  * The Chrome-trace exporter's schema invariants — matched B/E pairs,
    proper per-thread nesting, monotonic timestamps, async id matching —
    including on deliberately corrupted windows (evicted opens/closes).
  * The full serving stack on ONE timeline — request async spans from
    the front-end, tick phase spans from the server, kernel spans from
    the instrumented backend, scheduler fires and plan swaps as instants
    — and the phase/QPS telemetry (`phase_breakdown`, window QPS,
    Prometheus snapshot) riding the same run.
"""
import glob
import json

import jax
import numpy as np
import pytest

from repro.serve.async_frontend import AsyncCircuitServer
from repro.serve.circuits import CircuitRegistry, CircuitServer, TenantQoS
from repro.serve.circuits.metrics import (
    DEVICE_PHASES,
    HOST_PHASES,
    STATS_WINDOW,
    TICK_PHASES,
    ServerStats,
    TickReport,
)
from repro.serve.observability import (
    NULL_TRACER,
    TraceEvent,
    TraceRecorder,
    export_chrome,
    export_jsonl,
    prometheus_text,
    to_chrome,
)
from repro.observability.trace import _NOOP_SPAN, captured, reset_captured
from repro.serve.planning import PlacementPolicy
from tests.test_serve_circuits import TENANT_SHAPES, make_servable

RNG = np.random.RandomState(3)


class FakeClock:
    def __init__(self, t: float = 0.0, step: float = 0.0):
        self.t = t
        self.step = step

    def __call__(self) -> float:
        self.t += self.step
        return self.t


def ev(ts, phase, name, cat="test", track="main", args=None, id=None):
    return TraceEvent(ts, phase, name, cat, track, args, id)


# ---------------------------------------------------------------------------
# schema validation helpers (the acceptance-criteria assertions)
# ---------------------------------------------------------------------------

def validate_chrome(doc: dict) -> dict:
    """Assert the Chrome trace-event invariants; returns events by tid."""
    assert set(doc) >= {"traceEvents", "displayTimeUnit"}
    events = doc["traceEvents"]
    by_tid: dict = {}
    last_ts = -1.0
    stacks: dict = {}
    async_open: dict = {}
    for rec in events:
        if rec["ph"] == "M":
            continue  # metadata records carry no timestamp
        assert rec["ts"] >= 0
        # emission order is globally time-sorted (synthetic closes land
        # at the window end, which is >= every real timestamp)
        assert rec["ts"] >= last_ts - 1e-9, (rec, last_ts)
        last_ts = rec["ts"]
        tid = rec["tid"]
        by_tid.setdefault(tid, []).append(rec)
        if rec["ph"] == "B":
            stacks.setdefault(tid, []).append(rec)
        elif rec["ph"] == "E":
            stack = stacks.get(tid)
            assert stack, f"E without open B on tid {tid}: {rec}"
            opened = stack.pop()
            # proper nesting: the close matches the innermost open
            assert rec["name"] == opened["name"], (rec, opened)
            assert rec["ts"] >= opened["ts"]
        elif rec["ph"] == "b":
            key = (rec["cat"], rec["id"])
            async_open[key] = async_open.get(key, 0) + 1
        elif rec["ph"] in ("n", "e"):
            key = (rec["cat"], rec["id"])
            assert async_open.get(key, 0) > 0, f"async {rec} without b"
            if rec["ph"] == "e":
                async_open[key] -= 1
    for tid, stack in stacks.items():
        assert not stack, f"unclosed B spans on tid {tid}: {stack}"
    assert all(n == 0 for n in async_open.values()), async_open
    return by_tid


# ---------------------------------------------------------------------------
# TraceRecorder core
# ---------------------------------------------------------------------------

def test_recorder_records_with_injected_clock():
    clk = FakeClock(10.0)
    tr = TraceRecorder(clock=clk)
    tr.begin("work", cat="tick", track="t")
    clk.t = 10.5
    tr.instant("mark", cat="tick", track="t", detail=3)
    clk.t = 11.0
    tr.end("work", cat="tick", track="t")
    tss = [e.ts for e in tr.events()]
    assert tss == [10.0, 10.5, 11.0]
    phases = [e.phase for e in tr.events()]
    assert phases == ["B", "i", "E"]
    assert tr.events()[1].args == {"detail": 3}


def test_recorder_span_context_manager_emits_matched_pair():
    tr = TraceRecorder(clock=FakeClock(0.0, step=1.0))
    with tr.span("phase", cat="tick", track="t", shard=2):
        tr.counter("rows", 7, cat="tick", track="t")
    b, c, e = tr.events()
    assert (b.phase, b.name, b.args) == ("B", "phase", {"shard": 2})
    assert (c.phase, c.args) == ("C", {"value": 7})
    assert (e.phase, e.name) == ("E", "phase")


def test_recorder_ring_bounds_memory_and_counts_drops():
    tr = TraceRecorder(capacity=8, clock=FakeClock())
    for i in range(20):
        tr.instant(f"e{i}")
    assert len(tr) == 8
    assert tr.dropped == 12
    # oldest evicted: the window holds the 8 newest
    assert [e.name for e in tr.events()] == [f"e{i}" for i in range(12, 20)]


def test_disabled_recorder_is_inert_and_allocation_free():
    tr = TraceRecorder(clock=FakeClock(), enabled=False)
    tr.begin("x")
    tr.instant("y")
    tr.counter("z", 1)
    tr.async_begin("r", 1)
    assert len(tr) == 0 and tr.dropped == 0
    # span() returns the one shared no-op context manager — no per-call
    # allocation on the disabled hot path
    assert tr.span("a") is _NOOP_SPAN
    assert tr.span("b") is tr.span("c")
    assert NULL_TRACER.span("d") is _NOOP_SPAN
    assert not NULL_TRACER.enabled


def test_recorder_enable_disable_toggles_live():
    tr = TraceRecorder(clock=FakeClock())
    tr.disable()
    tr.instant("dropped")
    tr.enable()
    tr.instant("kept")
    assert [e.name for e in tr.events()] == ["kept"]


# ---------------------------------------------------------------------------
# Profiler mirror and capture table
# ---------------------------------------------------------------------------

def host_event_names(trace_dir) -> set:
    """Names of the events on the host planes of a profiler capture."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(str(trace_dir / "**" / "*.xplane.pb"), recursive=True)
    return {ev.name for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host")
            for line in plane.lines for ev in line.events}


def test_disabled_span_outside_a_capture_is_the_noop_and_records_nothing():
    reset_captured()
    tr = TraceRecorder(clock=FakeClock(), enabled=False)
    with tr.span("outside") as sp:
        assert sp is _NOOP_SPAN
    assert NULL_TRACER.span("outside") is _NOOP_SPAN
    assert captured() == {}


def test_disabled_span_under_a_capture_reaches_the_profiler_and_the_table(
        tmp_path):
    reset_captured()
    tr = TraceRecorder(clock=FakeClock(), enabled=False)
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(2):
            with tr.span("mirrored.span") as sp:
                assert sp is not _NOOP_SPAN
                jax.numpy.ones(4).block_until_ready()
        with NULL_TRACER.span("mirrored.null"):
            pass
    assert len(tr) == 0  # the ring of a disabled recorder stays empty
    got = captured()
    assert got["mirrored.span"]["count"] == 2
    assert got["mirrored.span"]["seconds"] > 0
    assert got["mirrored.null"]["count"] == 1
    assert {"mirrored.span", "mirrored.null"} <= host_event_names(tmp_path)
    # after the capture a span is the no-op again and adds nothing
    assert tr.span("after") is _NOOP_SPAN
    assert set(captured()) == {"mirrored.span", "mirrored.null"}


def test_count_adds_only_under_a_capture_and_samples_an_enabled_ring(
        tmp_path):
    reset_captured()
    tr = TraceRecorder(clock=FakeClock())
    tr.count("things")
    NULL_TRACER.count("things", 5)
    assert captured() == {}
    # an enabled recorder samples its own running total either way
    assert [(e.phase, e.args) for e in tr.events()] == [
        ("C", {"value": 1})]
    with jax.profiler.trace(str(tmp_path)):
        tr.count("things", 2)
        NULL_TRACER.count("things")
    assert captured() == {"things": {"count": 3, "seconds": 0.0}}
    assert tr.events()[-1].args == {"value": 3}
    reset_captured()
    assert captured() == {}


def test_capture_table_keeps_every_update_from_many_threads(tmp_path):
    """Spans and counts from more threads than cores, with the switch
    interval shortened: a lost update would leave a total short."""
    import os
    import sys
    import threading

    threads, rounds = 2 * (os.cpu_count() or 4), 200
    tr = TraceRecorder(capacity=16)

    def work():
        for _ in range(rounds):
            NULL_TRACER.count("stress.count")
            tr.count("stress.ring")
            with NULL_TRACER.span("stress.span"):
                pass

    reset_captured()
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        with jax.profiler.trace(str(tmp_path)):
            workers = [threading.Thread(target=work) for _ in range(threads)]
            for w in workers:
                w.start()
            for w in workers:
                w.join(timeout=60)
            assert not any(w.is_alive() for w in workers)
    finally:
        sys.setswitchinterval(interval)
    got = captured()
    reset_captured()
    assert got["stress.count"]["count"] == threads * rounds
    assert got["stress.ring"]["count"] == threads * rounds
    assert got["stress.span"]["count"] == threads * rounds
    assert tr._totals == {"stress.ring": threads * rounds}


# ---------------------------------------------------------------------------
# Chrome exporter: schema invariants, including corrupted windows
# ---------------------------------------------------------------------------

def test_chrome_export_clean_window_validates():
    tr = TraceRecorder(clock=FakeClock(0.0, step=0.25))
    with tr.span("tick", cat="tick", track="driver"):
        with tr.span("encode", cat="tick", track="driver"):
            pass
        tr.instant("fire", cat="scheduler", track="sched")
    rid = tr.next_id()
    tr.async_begin("request", rid, tenant="t0")
    tr.async_instant("request", rid, state="fired")
    tr.async_end("request", rid, outcome="ok")
    doc = to_chrome(tr)
    by_tid = validate_chrome(doc)
    # tracks become named threads
    names = {rec["args"]["name"] for rec in doc["traceEvents"]
             if rec["ph"] == "M"}
    assert {"driver", "sched"} <= names
    assert len(by_tid) >= 2


def test_chrome_export_drops_orphan_close_and_closes_dangling_open():
    events = [
        ev(1.0, "E", "evicted-open"),       # B fell out of the ring
        ev(2.0, "B", "never-closed"),       # disabled before the end
        ev(2.5, "i", "mark"),
    ]
    doc = to_chrome(events)
    validate_chrome(doc)  # still matched + nested after sanitization
    phases = [(r["ph"], r["name"]) for r in doc["traceEvents"]
              if r["ph"] != "M"]
    assert ("E", "evicted-open") not in phases
    assert ("B", "never-closed") in phases
    assert ("E", "never-closed") in phases  # synthetic close at window end


def test_chrome_export_sanitizes_async_orphans():
    events = [
        ev(1.0, "n", "request", id=9),   # b evicted: dropped
        ev(1.5, "e", "request", id=9),   # likewise
        ev(2.0, "b", "request", id=7),   # never ended: truncated close
    ]
    doc = to_chrome(events)
    validate_chrome(doc)
    recs = [r for r in doc["traceEvents"] if r["ph"] in ("b", "n", "e")]
    ids = {(r["ph"], r["id"]) for r in recs}
    assert ("n", format(9, "x")) not in ids
    assert ("b", format(7, "x")) in ids
    assert any(r["ph"] == "e" and r["name"] == "truncated" for r in recs)


def test_chrome_export_reports_ring_drops(tmp_path):
    tr = TraceRecorder(capacity=4, clock=FakeClock(0.0, step=1.0))
    for i in range(10):
        tr.instant(f"e{i}")
    doc = export_chrome(tr, str(tmp_path / "t.json"))
    assert doc["otherData"]["dropped_events"] == 6
    on_disk = json.loads((tmp_path / "t.json").read_text())
    assert on_disk == doc


def test_jsonl_export_round_trips(tmp_path):
    tr = TraceRecorder(clock=FakeClock(0.0, step=1.0))
    tr.begin("a", cat="tick", track="t", k=1)
    tr.end("a", cat="tick", track="t")
    rid = tr.next_id()
    tr.async_begin("r", rid)
    tr.async_end("r", rid)
    path = tmp_path / "t.jsonl"
    assert export_jsonl(tr, str(path)) == 4
    lines = [json.loads(line) for line in path.read_text().splitlines()]
    assert [rec["ph"] for rec in lines] == ["B", "E", "b", "e"]
    assert lines[0]["args"] == {"k": 1}
    assert lines[2]["id"] == rid


# ---------------------------------------------------------------------------
# full stack: one timeline across front-end, server, backend, autoscale
# ---------------------------------------------------------------------------

@pytest.fixture
def traced_stack():
    reg = CircuitRegistry()
    for i, shape in enumerate(TENANT_SHAPES[:2]):
        reg.add(f"t{i}", make_servable(i, *shape))
        reg.set_qos(f"t{i}", TenantQoS(
            max_batch=64, max_wait_s=0.05, default_deadline_s=10.0,
        ))
    tracer = TraceRecorder()
    server = CircuitServer(reg, backend="ref", tracer=tracer)
    clk = FakeClock(100.0)
    frontend = AsyncCircuitServer(server, clock=clk)
    return reg, tracer, server, frontend, clk


def test_full_stack_trace_on_one_timeline(traced_stack, tmp_path):
    reg, tracer, server, frontend, clk = traced_stack
    assert frontend.tracer is tracer  # one shared timeline

    futs = []
    for tenant in reg:
        x = RNG.randn(3, reg.get(tenant).encoder.n_features)
        futs.append(frontend.enqueue(tenant, x.astype(np.float32)))
    clk.t = 100.1  # past max_wait: the scheduler fires
    frontend.pump()
    for fut in futs:
        assert fut.result(timeout=5).shape == (3,)

    # a plan swap lands on the same timeline as an autoscale instant
    compiled = server.plan()
    from repro.serve.planning import PlanCompiler
    plan2 = PlanCompiler(server.backend, PlacementPolicy(n_shards=2)).compile(
        reg.catalog()
    )
    server.swap_plan(plan2, action="grow", reason="test")

    cats = {e.cat for e in tracer.events()}
    assert {"request", "scheduler", "tick", "kernel", "autoscale"} <= cats

    # request lifecycle: per admitted request one b ... n(fired) ... e(ok)
    per_id: dict = {}
    for e in tracer.events():
        if e.cat == "request" and e.id is not None:
            per_id.setdefault(e.id, []).append(e)
    assert len(per_id) == len(futs)
    for chain in per_id.values():
        assert [e.phase for e in chain] == ["b", "n", "e"]
        assert chain[0].args["tenant"] in set(reg)
        assert chain[1].args["state"] == "fired"
        assert chain[2].args["outcome"] == "ok"

    # tick phases appear as spans; kernel launches ride the backend hook
    names = {e.name for e in tracer.events()}
    assert "tick" in names and "tick.launch" in names
    assert "backend.eval_population_spans" in names
    assert "scheduler.fire" in names and "plan.swap" in names
    assert compiled.n_shards != plan2.n_shards  # swap actually happened

    # and the whole window exports as a valid Chrome trace
    doc = export_chrome(tracer, str(tmp_path / "full.json"))
    validate_chrome(doc)
    assert (tmp_path / "full.json").exists()


def test_tick_phase_breakdown_accounts_for_the_tick(traced_stack):
    _, tracer, server, frontend, clk = traced_stack
    reg = server.registry
    for _ in range(3):
        for tenant in reg:
            x = RNG.randn(2, reg.get(tenant).encoder.n_features)
            server.submit(tenant, x.astype(np.float32))
        report = server.tick()
        assert set(report.phase_s) == set(TICK_PHASES)
        assert all(v >= 0.0 for v in report.phase_s.values())
        # the phases partition measured work inside the tick wall time
        assert report.host_s + report.device_s <= report.latency_s + 1e-6
        assert report.host_s == pytest.approx(
            sum(report.phase_s[p] for p in HOST_PHASES))
        assert report.device_s == pytest.approx(
            sum(report.phase_s[p] for p in DEVICE_PHASES))

    pb = server.stats.report()["phase_breakdown"]
    assert set(pb["per_tick_ms"]) == set(TICK_PHASES)
    assert pb["host_share"] + pb["kernel_share"] == pytest.approx(1.0, abs=1e-3)
    assert sum(pb["share"].values()) == pytest.approx(1.0, abs=1e-2)


def test_tracing_disabled_serves_identically(traced_stack):
    """The default NULL_TRACER path must serve bit-identical results."""
    reg, _, traced_server, _, _ = traced_stack
    plain = CircuitServer(reg, backend="ref")
    assert plain.tracer is NULL_TRACER
    for tenant in reg:
        x = RNG.randn(4, reg.get(tenant).encoder.n_features).astype(np.float32)
        np.testing.assert_array_equal(
            plain.predict(tenant, x), traced_server.predict(tenant, x)
        )
    assert len(plain.tracer.events()) == 0


def test_instrumented_backend_delegates_and_hooks():
    from repro.runtime import get_backend

    calls = []

    class Hook:
        def __init__(self, kind, meta):
            calls.append((kind, meta))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    raw = get_backend("ref")
    proxy = raw.instrument(lambda kind, **meta: Hook(kind, meta))
    assert proxy.name == raw.name
    assert proxy.capabilities() == raw.capabilities()
    assert proxy.span_alignment(None) == raw.span_alignment(None)

    sc = make_servable(0, *TENANT_SHAPES[0])
    from repro.core import encoding as E
    x = RNG.randn(8, sc.encoder.n_features).astype(np.float32)
    bits = E.encode_batched(sc.encoder, [x])[0]
    packed = E.pack_bits_rows(bits, E.n_words(8))
    import jax.numpy as jnp
    from repro.core.genome import opcodes
    opc = opcodes(sc.genome, sc.spec)[None]
    edge = sc.genome.edge_src[None]
    outs = sc.genome.out_src[None]
    got = proxy.eval_population_spans(
        jnp.asarray(opc), jnp.asarray(edge), jnp.asarray(outs),
        jnp.asarray(packed), jnp.zeros(1, jnp.int32),
        jnp.full(1, packed.shape[0], jnp.int32),
        span_words=packed.shape[1],
    )
    want = raw.eval_population_spans(
        jnp.asarray(opc), jnp.asarray(edge), jnp.asarray(outs),
        jnp.asarray(packed), jnp.zeros(1, jnp.int32),
        jnp.full(1, packed.shape[0], jnp.int32),
        span_words=packed.shape[1],
    )
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert calls and calls[0][0] == "eval_population_spans"
    assert calls[0][1]["population"] == 1


# ---------------------------------------------------------------------------
# metrics satellites: window QPS, bounded windows, Prometheus snapshot
# ---------------------------------------------------------------------------

def _tick(requests=10, rows=10, latency=0.001):
    return TickReport(
        generation=0, tenants=1, requests=requests, rows=rows, launches=1,
        span_words=1, latency_s=latency, occupancy=0.5,
        phase_s={p: 0.0001 for p in TICK_PHASES},
    )


def test_window_qps_ignores_idle_before_the_window():
    clk = FakeClock(0.0)
    stats = ServerStats(clock=clk)
    clk.t = 1000.0  # idle for 1000 s after construction
    for _ in range(10):
        clk.t += 1.0
        stats.record(_tick(requests=10))
    rep = stats.report()
    # lifetime QPS is diluted by the idle 1000 s; the window is not
    assert rep["qps"] < 1.0
    assert rep["qps_window"] == pytest.approx(10.0, rel=0.15)
    assert rep["window_s"] == pytest.approx(9.0, rel=1e-6)


def test_window_qps_falls_back_to_lifetime_when_underfilled():
    clk = FakeClock(5.0)
    stats = ServerStats(clock=clk)
    clk.t = 7.0
    stats.record(_tick(requests=4))
    rep = stats.report()  # one mark: not enough for a window
    assert rep["qps_window"] == rep["qps"]


def test_stats_windows_stay_bounded_past_stats_window():
    clk = FakeClock(0.0, step=0.001)
    stats = ServerStats(clock=clk)
    n = STATS_WINDOW + 500
    for _ in range(n):
        stats.record(_tick(requests=1))
    assert stats.ticks == n
    assert stats.requests == n
    assert len(stats.tick_latencies_s) == STATS_WINDOW
    assert len(stats.occupancies) == STATS_WINDOW
    assert len(stats.request_marks) == STATS_WINDOW
    stats.report()  # and the report still computes


def test_prometheus_text_snapshot():
    clk = FakeClock(0.0, step=0.5)
    stats = ServerStats(backend="ref", clock=clk)
    stats.record(_tick())
    text = prometheus_text(server_stats=stats)
    assert '# TYPE repro_server_qps gauge' in text
    assert 'repro_server_qps{backend="ref"}' in text
    assert 'repro_server_ticks{backend="ref"} 1' in text
    # nested phase maps flatten to one labelled series per phase
    assert ('repro_server_phase_breakdown_per_tick_ms'
            '{backend="ref",key="encode"}') in text
    # dict + frontend sections coexist
    from repro.serve.circuits.metrics import FrontendStats
    fs = FrontendStats(backend="ref")
    fs.record_submitted()
    both = prometheus_text(server_stats=stats, frontend_stats=fs)
    assert 'repro_frontend_submitted{backend="ref"} 1' in both


def test_prometheus_text_fleet_section():
    """The fleet section: router-level gauges plus per-host labelled
    series, riding the same exposition as server/front-end stats."""
    fleet_report = {
        "router": {"requests_routed": 500, "qps": 76.2, "migrations": 2,
                   "n_hosts": 2, "plan_generation": 7},
        "hosts": {
            "h0": {"requests_routed": 303, "queue_rows": 4, "qps": 46.2,
                   "tenants": 2, "migrations_in": 0, "migrations_out": 1},
            "h1": {"requests_routed": 197, "queue_rows": 0, "qps": 30.0,
                   "tenants": 2, "migrations_in": 1, "migrations_out": 0},
        },
    }
    text = prometheus_text(fleet=fleet_report)
    assert "# TYPE repro_fleet_router_qps gauge" in text
    assert "repro_fleet_router_qps 76.2" in text
    assert "repro_fleet_router_migrations 2" in text
    # per-host series carry a host label, one line per host per metric
    assert 'repro_fleet_host_queue_rows{host="h0"} 4' in text
    assert 'repro_fleet_host_queue_rows{host="h1"} 0' in text
    assert 'repro_fleet_host_requests_routed{host="h0"} 303' in text
    assert 'repro_fleet_host_migrations_in{host="h1"} 1' in text
    # fleet + server sections coexist in one exposition
    stats = ServerStats(backend="ref", clock=FakeClock(0.0, step=0.5))
    stats.record(_tick())
    both = prometheus_text(server_stats=stats, fleet=fleet_report)
    assert 'repro_server_qps{backend="ref"}' in both
    assert "repro_fleet_router_qps 76.2" in both
