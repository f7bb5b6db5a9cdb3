"""Multi-host fleet serving: plan, workload, transports, host, router.

Everything here drives real `ServingHost` stacks — through the
in-process transport for determinism (it still round-trips every
payload through the wire codec), plus a thread-hosted socket server and
one subprocess host to pin the real-runs path.  The migration tests
assert the contract the subsystem exists for: a cross-host tenant move
loses no request and changes no result.
"""
import threading
import time

import jax
import numpy as np
import pytest

from repro.core import encoding as E
from repro.core import gates
from repro.core.api import ServableCircuit
from repro.core.genome import CircuitSpec, init_genome
from repro.observability.trace import TraceRecorder
from repro.serve.circuits import CircuitRegistry
from repro.serve.fleet import (
    FleetPlanner,
    FleetRouter,
    HashRing,
    InProcTransport,
    ServingHost,
    SocketTransport,
    Transport,
    dump_bundle,
    generate,
    load_trace,
    save_trace,
    serve_socket,
    spawn_host_process,
)
from repro.serve.fleet.transport import encode_frame, _dec, _enc
from repro.serve.fleet.workload import chunked

RNG = np.random.RandomState(0)

# (features, bits/input, gates, classes)
SHAPES = [(4, 2, 40, 2), (7, 4, 80, 3), (3, 2, 25, 4), (10, 4, 120, 5)]


def make_servable(seed, n_feats, bits, n_nodes, n_classes,
                  rng) -> ServableCircuit:
    enc = E.fit_encoder(
        rng.randn(200, n_feats).astype(np.float32),
        E.EncodingConfig("quantile", bits),
    )
    n_out = max(1, int(np.ceil(np.log2(max(n_classes, 2)))))
    spec = CircuitSpec(enc.n_bits_total, n_nodes, n_out,
                       gates.FUNCTION_SETS["full"])
    return ServableCircuit(
        spec, init_genome(jax.random.key(seed), spec), enc, n_classes
    )


def make_circuits(seed0: int = 0) -> "dict[str, ServableCircuit]":
    """One deterministic circuit per SHAPES entry — reseeded per call,
    so two 'clusters' built from the same seed serve identical bits."""
    rng = np.random.RandomState(0)
    return {
        f"t{i}": make_servable(seed0 + i, *shape, rng)
        for i, shape in enumerate(SHAPES)
    }


def two_host_fleet(tracer=None):
    router = FleetRouter(tracer=tracer)
    hosts = {}
    for hid in ("h0", "h1"):
        host = ServingHost(hid, CircuitRegistry(), tracer=tracer)
        hosts[hid] = host
        router.add_host(hid, InProcTransport(host))
    for name, sc in make_circuits().items():
        router.register(name, [sc])
    return router, hosts


# ---------------------------------------------------------------------------
# HashRing / FleetPlanner
# ---------------------------------------------------------------------------

def test_ring_deterministic_and_total():
    ring = HashRing(["b", "a", "a"])  # dedup + order-independence
    assert ring.hosts == ("a", "b")
    again = HashRing(["a", "b"])
    owners = {f"t{i}": ring.owner(f"t{i}") for i in range(100)}
    assert owners == {t: again.owner(t) for t in owners}
    assert set(owners.values()) <= {"a", "b"}
    with pytest.raises(ValueError):
        HashRing([]).owner("t")
    with pytest.raises(ValueError):
        HashRing(["a"], vnodes=0)


def test_ring_join_moves_about_one_nth_and_only_to_joiner():
    """The consistent-hashing contract, quantitatively: adding a 5th
    host relocates roughly K/5 of 1000 tenants, and every relocated
    tenant lands on the joiner (hashing is deterministic, so fixed
    names make this exact, not flaky)."""
    tenants = [f"tenant{i}" for i in range(1000)]
    before = HashRing([f"h{i}" for i in range(4)])
    after = HashRing([f"h{i}" for i in range(5)])
    moved = [t for t in tenants if before.owner(t) != after.owner(t)]
    assert all(after.owner(t) == "h4" for t in moved)
    # expectation is K/n = 200; generous band still rules out rehashing
    # the world (which would move ~800)
    assert 100 <= len(moved) <= 350


def test_planner_pins_survive_and_lpt_balances():
    planner = FleetPlanner(imbalance_high=1.1)
    hosts = ["h0", "h1"]
    tenants = [f"t{i}" for i in range(8)]
    base = planner.plan(hosts, tenants)
    assert sorted(base.assignment) == sorted(tenants)
    assert base.pins == {}

    # all load on one host's tenants: LPT must move some of it over
    heavy_host = base.owner("t0")
    loads = {
        t: (1000.0 if base.owner(t) == heavy_host else 1.0)
        for t in tenants
    }
    balanced = planner.plan(hosts, tenants, loads=loads, prev=base,
                            generation=1)
    assert balanced.pins, "skewed load must produce LPT override pins"
    by_host = {
        h: sum(loads[t] for t in balanced.tenants_of(h)) for h in hosts
    }
    assert max(by_host.values()) < sum(loads.values())  # actually split

    # pins survive a membership change while tenant + host survive
    grown = planner.plan(hosts + ["h2"], tenants, prev=balanced,
                         generation=2)
    for t, h in balanced.pins.items():
        assert grown.owner(t) == h
    # ...and die with their host
    shrunk = planner.plan(["h0"], tenants, prev=balanced, generation=3)
    assert shrunk.pins == {
        t: h for t, h in balanced.pins.items() if h == "h0"
    }


def test_planner_equal_loads_deterministic():
    """Equal per-tenant loads leave the LPT override nothing but
    tie-breaks (which tenant of equals to move, which of two equally
    idle hosts receives) — all of which break by name, so two fresh
    planners produce byte-identical plans."""
    hosts = ["h0", "h1", "h2"]
    tenants = [f"t{i}" for i in range(12)]
    loads = {t: 5.0 for t in tenants}
    a = FleetPlanner().plan(hosts, tenants, loads=loads)
    b = FleetPlanner().plan(hosts, tenants, loads=loads)
    assert a.assignment == b.assignment
    assert a.pins == b.pins
    assert a.content_hash == b.content_hash
    # and the override only ever *improves* balance (host tenant counts
    # end within one move of each other under equal loads)
    counts = sorted(len(a.tenants_of(h)) for h in hosts)
    ring_counts = sorted(
        len(FleetPlanner().plan(hosts, tenants).tenants_of(h))
        for h in hosts
    )
    assert counts[-1] - counts[0] <= ring_counts[-1] - ring_counts[0]


# ---------------------------------------------------------------------------
# Workload traces
# ---------------------------------------------------------------------------

def test_workload_generate_deterministic_and_shaped():
    tenants = [f"t{i}" for i in range(6)]
    a = generate("skew", n_events=2000, tenants=tenants, seed=3)
    b = generate("skew", n_events=2000, tenants=tenants, seed=3)
    assert a.events == b.events
    assert a.meta["total_rows"] == a.total_rows
    times = [e.t for e in a.events]
    assert times == sorted(times)
    # skew: the head tenant dominates the tail tenant
    counts = {t: 0 for t in tenants}
    for e in a.events:
        counts[e.tenant] += 1
    assert counts["t0"] > 3 * counts["t5"]
    # spike: the burst decile at mid-trace out-draws a plateau decile
    s = generate("spike", n_events=2000, tenants=tenants, seed=3,
                 duration_s=10.0)
    mid = sum(1 for e in s.events if 4.5 <= e.t <= 5.5)
    edge = sum(1 for e in s.events if e.t <= 1.0)
    assert mid > 2 * edge
    with pytest.raises(ValueError):
        generate("sawtooth", n_events=10, tenants=tenants)
    with pytest.raises(ValueError):
        generate("skew", n_events=0, tenants=tenants)


def test_workload_trace_roundtrip_and_features(tmp_path):
    wl = generate("diurnal", n_events=500,
                  tenants=["a", "b"], seed=11)
    for name in ("trace.jsonl", "trace.jsonl.gz"):
        path = str(tmp_path / name)
        assert save_trace(wl, path) == 500
        back = load_trace(path)
        assert back.events == wl.events
        assert back.meta == wl.meta
    # features: determinism + exact dtype/shape (the parity criterion
    # rests on every replay materializing identical bits)
    ev = wl.events[0]
    x1, x2 = ev.features(7), ev.features(7)
    assert x1.dtype == np.float32 and x1.shape == (ev.rows, 7)
    np.testing.assert_array_equal(x1, x2)
    bad = str(tmp_path / "bad.jsonl")
    with open(bad, "w") as f:
        f.write('{"format": "not-a-trace"}\n')
    with pytest.raises(ValueError):
        load_trace(bad)


def test_workload_chunking():
    wl = generate("skew", n_events=10, tenants=["a"], seed=0)
    chunks = list(chunked(wl.events, 4))
    assert [len(c) for c in chunks] == [4, 4, 2]
    assert [e for c in chunks for e in c] == list(wl.events)
    with pytest.raises(ValueError):
        list(chunked(wl.events, 0))


# ---------------------------------------------------------------------------
# Wire codec
# ---------------------------------------------------------------------------

def test_codec_roundtrip_preserves_arrays_and_bytes():
    payload = {
        "x": np.arange(12, dtype=np.float32).reshape(3, 4),
        "ids": np.array([1, 2, 3], np.int32),
        "blob": b"\x00\x01\xffbundle",
        "nested": {"list": [np.zeros(2, np.uint8), "text", 7, 1.5, None,
                            True]},
    }
    back = _dec(__import__("json").loads(
        __import__("json").dumps(_enc(payload))))
    np.testing.assert_array_equal(back["x"], payload["x"])
    assert back["x"].dtype == np.float32
    np.testing.assert_array_equal(back["ids"], payload["ids"])
    assert back["blob"] == payload["blob"]
    np.testing.assert_array_equal(back["nested"]["list"][0],
                                  payload["nested"]["list"][0])
    assert back["nested"]["list"][1:] == ["text", 7, 1.5, None, True]
    assert isinstance(encode_frame(payload), bytes)


# ---------------------------------------------------------------------------
# ServingHost RPC surface
# ---------------------------------------------------------------------------

def test_host_rpc_lifecycle_and_step_isolation():
    host = ServingHost("hx", CircuitRegistry())
    tr = InProcTransport(host)
    assert tr.call("ping")["host_id"] == "hx"
    rng = np.random.RandomState(1)
    sc = make_servable(1, 4, 2, 40, 2, rng)
    tr.call("add_tenant",
            {"tenant": "t0", "bundles": [dump_bundle(sc, "ref")],
             "qos": {"max_batch": 16, "max_wait_s": 0.01,
                     "default_deadline_s": 0.5}})
    assert tr.call("tenants")["tenants"] == ["t0"]
    assert host.registry.qos("t0").max_batch == 16

    x = rng.randn(5, 4).astype(np.float32)
    out = tr.call("step", {"work": [["t0", x], ["ghost", x]]})
    good, bad = out["y"]
    np.testing.assert_array_equal(np.asarray(good), sc.predict(x))
    assert isinstance(bad, dict) and bad["error"] == "KeyError"

    # export is bit-identical to the registered circuit
    export = tr.call("export_tenant", {"tenant": "t0"})
    assert export["qos"]["max_batch"] == 16
    from repro.serve.fleet import load_bundle
    clone = load_bundle(export["bundles"][0])
    np.testing.assert_array_equal(clone.predict(x), sc.predict(x))

    tr.call("remove_tenant", {"tenant": "t0", "action": "migrate_out"})
    assert tr.call("ping")["n_tenants"] == 0
    assert tr.call("stats")["migrations_out"] == 1
    with pytest.raises(ValueError):
        tr.call("no_such_method", {})


def test_host_migration_swaps_ride_rebalance_audit_trail():
    """migrate_in / migrate_out land on the same `RebalanceEvent`
    stream the autoscaler writes — one audit trail for every plan
    cutover, whatever triggered it."""
    host = ServingHost("hx", CircuitRegistry())
    tr = InProcTransport(host)
    rng = np.random.RandomState(2)
    sc = make_servable(2, 3, 2, 25, 4, rng)
    tr.call("add_tenant",
            {"tenant": "m0", "bundles": [dump_bundle(sc, "ref")],
             "qos": None, "action": "migrate_in"})
    actions = [ev.action for ev in host.server.stats.rebalances]
    assert "migrate_in" in actions
    assert tr.call("stats")["migrations_in"] == 1


# ---------------------------------------------------------------------------
# FleetRouter: routing, replay, migration
# ---------------------------------------------------------------------------

def test_router_register_spreads_and_routes():
    router, hosts = two_host_fleet()
    owners = {t: router.owner_of(t) for t in router.tenants()}
    assert set(owners.values()) == {"h0", "h1"}  # both hosts used
    for hid, host in hosts.items():
        assert sorted(host.registry) == sorted(
            t for t, h in owners.items() if h == hid
        )
    with pytest.raises(KeyError):
        router.submit("ghost", np.zeros((1, 4), np.float32))
    with pytest.raises(ValueError):
        router.register("t0", [])  # already registered
    router.close(shutdown_hosts=False)


def test_router_replay_parity_fleet_vs_single_host():
    """The acceptance contract in miniature: a two-host replay with a
    mid-replay migration returns bitwise-identical per-request results
    to a single-host replay of the same trace, and loses nothing."""
    tracer = TraceRecorder(capacity=50_000)
    router, hosts = two_host_fleet(tracer=tracer)
    tenants = list(router.tenants())
    wl = generate("skew", n_events=600, tenants=tenants, seed=7)

    def on_chunk(ci, r):
        if ci == 1:
            t = tenants[0]
            dst = "h1" if r.owner_of(t) == "h0" else "h0"
            assert r.migrate(t, dst, reason="test") is not None

    outs = router.replay(wl.events, chunk_size=150, on_chunk=on_chunk)
    assert len(outs) == wl.n_events
    assert sum(1 for o in outs if not isinstance(o, np.ndarray)) == 0
    assert len(router.migrations) == 1
    assert router.migrations[0].tenant == tenants[0]

    solo = FleetRouter()
    solo.add_host(
        "solo", InProcTransport(ServingHost("solo", CircuitRegistry()))
    )
    for name, sc in make_circuits().items():
        solo.register(name, [sc])
    ref = solo.replay(wl.events, chunk_size=600)
    mismatches = sum(
        1 for a, b in zip(outs, ref) if not np.array_equal(a, b)
    )
    assert mismatches == 0

    # the migration and both host step spans share the trace timeline
    names = {e.name for e in tracer.events()}
    assert {"fleet.migrate", "fleet.router.chunk",
            "fleet.host.step"} <= names
    rep = router.report()
    assert rep["router"]["requests_routed"] == wl.n_events
    assert rep["router"]["migrations"] == 1
    router.close(shutdown_hosts=False)
    solo.close(shutdown_hosts=False)


def test_router_join_leave_migrates_zero_lost():
    router, hosts = two_host_fleet()
    before = {t: router.owner_of(t) for t in router.tenants()}

    h2 = ServingHost("h2", CircuitRegistry())
    plan = router.add_host("h2", InProcTransport(h2))
    after = {t: plan.owner(t) for t in router.tenants()}
    # join: every move targets the joiner; survivors never trade
    for t, h in after.items():
        assert h == before[t] or h == "h2"
        assert router.owner_of(t) == h
    # hosts actually hold what the plan says
    assert sorted(h2.registry) == sorted(
        t for t, h in after.items() if h == "h2"
    )

    plan = router.remove_host("h2")
    final = {t: plan.owner(t) for t in router.tenants()}
    for t, h in final.items():
        assert h in ("h0", "h1")
        if after[t] != "h2":  # leave: only the leaver's tenants move
            assert h == after[t]
    assert "h2" not in router.hosts
    # the fleet still serves every tenant after the churn
    wl = generate("skew", n_events=100, tenants=list(before), seed=9)
    outs = router.replay(wl.events, chunk_size=50)
    assert all(isinstance(o, np.ndarray) for o in outs)
    router.close(shutdown_hosts=False)


def test_router_remove_last_host_with_tenants_refused():
    router = FleetRouter()
    router.add_host(
        "only", InProcTransport(ServingHost("only", CircuitRegistry()))
    )
    rng = np.random.RandomState(3)
    router.register("t0", [make_servable(0, 4, 2, 40, 2, rng)])
    with pytest.raises(ValueError):
        router.remove_host("only")
    router.close(shutdown_hosts=False)


def test_router_live_submit_and_migration_buffering():
    """Submits racing a migration park router-side and complete against
    the new owner — the zero-lost contract on the deadline path."""
    router, hosts = two_host_fleet()
    for host in hosts.values():
        host.start()
    try:
        tenant = next(iter(router.tenants()))
        src = router.owner_of(tenant)
        dst = "h1" if src == "h0" else "h0"
        n_feats = make_circuits()[tenant].encoder.n_features
        x = np.zeros((2, n_feats), np.float32)

        baseline = router.submit(tenant, x, deadline_s=5.0).result(30.0)

        hold = threading.Event()
        release = threading.Event()

        class SlowExport(Transport):
            """Delays export_tenant so the test can submit while the
            migration window is provably open."""

            def __init__(self, inner):
                self.inner = inner

            def call(self, method, payload=None):
                if method == "export_tenant":
                    hold.set()
                    assert release.wait(30.0)
                return self.inner.call(method, payload)

        with router._lock:
            router._transports[src] = SlowExport(router._transports[src])

        worker = threading.Thread(
            target=router.migrate, args=(tenant, dst),
            kwargs={"reason": "buffer-test"}, daemon=True,
        )
        worker.start()
        assert hold.wait(30.0)
        parked = router.submit(tenant, x, deadline_s=30.0)
        # the submit dispatches on the router's pool: release the export
        # only once the request is parked, or a loaded machine can run
        # the whole migration before the pool thread looks
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline:
            with router._lock:
                if router._migrating.get(tenant):
                    break
            time.sleep(0.001)
        release.set()
        worker.join(30.0)
        assert not worker.is_alive()

        assert router.owner_of(tenant) == dst
        np.testing.assert_array_equal(parked.result(30.0), baseline)
        ev = router.migrations[-1]
        assert ev.buffered >= 1 and ev.tenant == tenant
        # post-migration submits route to the new owner
        np.testing.assert_array_equal(
            router.submit(tenant, x, deadline_s=30.0).result(30.0),
            baseline,
        )
    finally:
        for host in hosts.values():
            host.stop()
        router.close(shutdown_hosts=False)


def test_router_load_rebalance_moves_hot_tenants():
    """Observed-load windows drive the LPT override end to end: after a
    skewed replay, `rebalance()` migrates load off the hot host."""
    router, hosts = two_host_fleet()
    tenants = list(router.tenants())
    hot_host = router.owner_of(tenants[0])
    hot = [t for t in tenants if router.owner_of(t) == hot_host]
    wl = generate("skew", n_events=400, tenants=hot, seed=5)
    router.replay(wl.events, chunk_size=200)
    moved = router.rebalance(reason="load-test")
    assert moved, "all observed load on one host must trigger moves"
    assert all(m.from_host == hot_host for m in moved)
    # the moves are pinned, so a replan without load keeps them
    assert all(
        router.plan.pins.get(m.tenant) == m.to_host for m in moved
    )
    router.close(shutdown_hosts=False)


# ---------------------------------------------------------------------------
# Socket + subprocess transports
# ---------------------------------------------------------------------------

def test_socket_transport_same_results_as_inproc():
    host = ServingHost("sock0", CircuitRegistry())
    ready = threading.Event()
    thread = threading.Thread(
        target=serve_socket, args=(host,), kwargs={"ready": ready},
        daemon=True,
    )
    thread.start()
    assert ready.wait(30.0)
    tr = SocketTransport(ready.addr)
    rng = np.random.RandomState(4)
    sc = make_servable(4, 4, 2, 40, 2, rng)
    tr.call("add_tenant",
            {"tenant": "t0", "bundles": [dump_bundle(sc, "ref")],
             "qos": None})
    x = rng.randn(6, 4).astype(np.float32)
    out = np.asarray(tr.call("step", {"work": [["t0", x]]})["y"][0])
    np.testing.assert_array_equal(out, sc.predict(x))
    with pytest.raises(KeyError):
        tr.call("export_tenant", {"tenant": "ghost"})
    assert tr.call("shutdown") == {"ok": True}
    thread.join(30.0)
    assert not thread.is_alive()
    tr.close()


def test_subprocess_host_serves_migrated_bundle():
    """A process host starts empty and receives its tenant over the
    wire — a real-runs host is just a host whose every tenant migrated
    in."""
    proc, addr = spawn_host_process("proc0", timeout_s=120.0)
    try:
        tr = SocketTransport(addr, connect_timeout_s=30.0)
        rng = np.random.RandomState(5)
        sc = make_servable(5, 3, 2, 25, 4, rng)
        tr.call("add_tenant",
                {"tenant": "t0", "bundles": [dump_bundle(sc, "ref")],
                 "qos": None, "action": "migrate_in"})
        x = rng.randn(4, 3).astype(np.float32)
        out = np.asarray(tr.call("step", {"work": [["t0", x]]})["y"][0])
        np.testing.assert_array_equal(out, sc.predict(x))
        assert tr.call("stats")["migrations_in"] == 1
        tr.call("shutdown")
        tr.close()
        assert proc.wait(60.0) == 0
    finally:
        if proc.poll() is None:
            proc.kill()
