"""AOT serving artifacts: compile-once executables, pre-warmed plan
swaps, and cold boot with zero tracing.

The contract under test, layer by layer:

  * `repro.runtime.aot` — a compiled span launch serializes, round-trips
    and evaluates bit-identically to the eager path; `"ref"` declares
    no AOT support and `compile_spans` says so loudly;
  * `ArtifactStore` — executables are versioned manifest entries;
    unknown manifest versions are refused; corrupted or missing payloads
    degrade to compiling, never crash a boot;
  * `CircuitServer` — ticks dispatch through cached executables (no
    retrace across plans that share shard content hashes), `swap_plan`
    pre-warms, `export_executables`/`preload_executables` round-trip;
  * fleet — `export_fleet` freezes a live cluster into one store and
    `boot_from_artifact` restarts it with **zero traces** (asserted via
    the trace counter inside the jitted bodies, in a subprocess so no
    warm jit cache can mask a retrace) and bitwise parity.
"""
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.core import encoding as E
from repro.core import gates
from repro.core.api import ServableCircuit
from repro.core.genome import CircuitSpec, init_genome
from repro.runtime import aot, get_backend
from repro.runtime.base import BackendCapabilityError
from repro.serve.artifacts import ArtifactStore, STORE_FORMAT_VERSION
from repro.serve.circuits import CircuitRegistry, CircuitServer
from repro.serve.fleet.artifact import FleetArtifact
from repro.serve.planning import PlacementPolicy, PlanCompiler

from tests.conftest import REPO, SRC

RNG = np.random.RandomState(0)


def make_servable(seed=0, n_feats=5, bits=2, n_nodes=40, n_classes=3):
    rng = np.random.RandomState(seed)
    enc = E.fit_encoder(
        rng.randn(150, n_feats).astype(np.float32),
        E.EncodingConfig("quantize", bits),
    )
    n_out = max(1, int(np.ceil(np.log2(max(n_classes, 2)))))
    spec = CircuitSpec(enc.n_bits_total, n_nodes, n_out, gates.FULL_FS)
    return ServableCircuit(
        spec, init_genome(jax.random.key(seed), spec), enc, n_classes
    )


def fleet(n, seed0=100):
    reg = CircuitRegistry()
    shapes = [(4, 2, 40, 2), (7, 4, 80, 3), (3, 2, 25, 4), (10, 4, 120, 5)]
    for i in range(n):
        f, b, g, c = shapes[i % len(shapes)]
        reg.add(f"t{i}", make_servable(seed0 + i, f, b, g, c))
    return reg


# ---------------------------------------------------------------------------
# runtime seam: compile_spans / serialize / deserialize
# ---------------------------------------------------------------------------

def test_pallas_compile_spans_serializes_and_round_trips():
    backend = get_backend("pallas")
    caps = backend.capabilities()
    assert caps.supports_aot
    assert caps.aot_format == aot.AOT_FORMAT
    assert caps.aot_format_version == aot.AOT_FORMAT_VERSION

    reg = fleet(3)
    comp = PlanCompiler("pallas", PlacementPolicy())
    plan = comp.compile(reg.catalog())
    shard = plan.shards[0]
    span = 1
    spec = aot.SpanLaunchSpec(
        n_slots=shard.n_slots, k_pad=shard.n_slots,
        n_nodes=shard.opcodes.shape[1], n_outputs=shard.out_src.shape[1],
        n_inputs=shard.n_inputs_max, span_words=span,
    )
    compiled = backend.compile_spans(spec)
    payload = aot.serialize_executable(compiled)
    assert isinstance(payload, bytes) and len(payload) > 0
    loaded = aot.deserialize_executable(payload)

    k = shard.n_slots
    slots = np.arange(k, dtype=np.int32)
    x = RNG.randint(0, 2**32, (shard.n_inputs_max, k * span)).astype(
        np.uint32
    )
    woff = np.arange(k, dtype=np.int32) * span
    live = np.ones(k, np.int32)
    args = (shard.opcodes, shard.edge_src, shard.out_src, shard.in_width,
            slots, x, woff, live)
    want = backend.eval_population_spans(
        shard.opcodes[slots], shard.edge_src[slots], shard.out_src[slots],
        x, woff, shard.in_width[slots] * live, span_words=span,
    )
    np.testing.assert_array_equal(np.asarray(compiled(*args)),
                                  np.asarray(want))
    np.testing.assert_array_equal(np.asarray(loaded(*args)),
                                  np.asarray(want))


def test_ref_backend_declares_no_aot_and_refuses_compile():
    backend = get_backend("ref")
    assert not backend.capabilities().supports_aot
    spec = aot.SpanLaunchSpec(
        n_slots=2, k_pad=2, n_nodes=10, n_outputs=2, n_inputs=8,
        span_words=1,
    )
    with pytest.raises(BackendCapabilityError, match="supports_aot=False"):
        backend.compile_spans(spec)


def test_executable_key_is_deterministic():
    k = aot.executable_key("pallas", "abc123", 4)
    assert k == "pallas--abc123--s4"
    assert aot.executable_key("pallas", "abc123", 4) == k


# ---------------------------------------------------------------------------
# ArtifactStore: executables section, versioning, unified persistence
# ---------------------------------------------------------------------------

def test_store_executable_round_trip_and_entries(tmp_path):
    store = ArtifactStore(str(tmp_path))
    payload = b"\x00\x01binary payload\xff"
    store.put_executable(
        "pallas--deadbeef--s2", payload, backend="pallas",
        aot_format=aot.AOT_FORMAT,
        aot_format_version=aot.AOT_FORMAT_VERSION,
        spec=(4, 4, 40, 2, 10, 2),
    )
    # a fresh handle reads what the first one wrote
    again = ArtifactStore(str(tmp_path))
    assert again.get_executable("pallas--deadbeef--s2") == payload
    entry = again.executable_entries()["pallas--deadbeef--s2"]
    assert entry["backend"] == "pallas"
    assert entry["format"] == aot.AOT_FORMAT
    assert entry["format_version"] == aot.AOT_FORMAT_VERSION
    assert entry["spec"] == [4, 4, 40, 2, 10, 2]
    with pytest.raises(KeyError):
        again.get_executable("pallas--unknown--s1")


def test_store_refuses_unknown_manifest_version(tmp_path):
    ArtifactStore(str(tmp_path)).flush()
    mpath = tmp_path / "manifest.json"
    m = json.loads(mpath.read_text())
    m["format_version"] = STORE_FORMAT_VERSION + 1
    mpath.write_text(json.dumps(m))
    with pytest.raises(ValueError, match="unsupported store format"):
        ArtifactStore(str(tmp_path))
    m["format_version"] = STORE_FORMAT_VERSION
    m["kind"] = "something-else"
    mpath.write_text(json.dumps(m))
    with pytest.raises(ValueError, match="not an artifact-store manifest"):
        ArtifactStore(str(tmp_path))


def test_registry_and_executables_share_one_store(tmp_path):
    reg = fleet(3)
    store = ArtifactStore(str(tmp_path))
    store.put_registry(reg)
    store.put_executable(
        "pallas--cafe--s1", b"x", backend="pallas",
        aot_format=aot.AOT_FORMAT, aot_format_version=1, spec=(1,),
    )
    # registry reload unaffected by the executables section and vice versa
    loaded = ArtifactStore(str(tmp_path)).load_registry()
    assert sorted(loaded) == sorted(reg)
    assert ArtifactStore(str(tmp_path)).get_executable(
        "pallas--cafe--s1"
    ) == b"x"
    # re-putting the registry keeps executables alive through gc
    store2 = ArtifactStore(str(tmp_path))
    store2.put_registry(reg)
    assert store2.get_executable("pallas--cafe--s1") == b"x"


def test_deprecated_wrappers_still_work_and_warn(tmp_path):
    reg = fleet(2)
    with pytest.warns(DeprecationWarning, match="save_dir"):
        written = reg.save_dir(str(tmp_path))
    assert len(written) == len(reg)
    with pytest.warns(DeprecationWarning, match="load_dir"):
        loaded = CircuitRegistry.load_dir(str(tmp_path))
    assert sorted(loaded) == sorted(reg)
    sc = make_servable(7)
    with pytest.warns(DeprecationWarning, match="save"):
        path = sc.save(str(tmp_path / "one.npz"))
    with pytest.warns(DeprecationWarning, match="load"):
        back = ServableCircuit.load(path)
    x = RNG.randn(9, sc.encoder.n_features).astype(np.float32)
    np.testing.assert_array_equal(back.predict(x), sc.predict(x))


# ---------------------------------------------------------------------------
# CircuitServer: cached executables, prewarmed swaps, export/preload
# ---------------------------------------------------------------------------

def _serve_all(server, reg, rows=12):
    outs = {}
    for t in reg:
        x = np.random.RandomState(hash(t) % 2**31).randn(
            rows, reg.get(t).encoder.n_features
        ).astype(np.float32)
        outs[t] = (x, server.predict(t, x))
    return outs


def test_server_tick_uses_cached_executables():
    reg = fleet(3)
    server = CircuitServer(reg, backend="pallas")
    assert server._aot_capable
    first = _serve_all(server, reg)
    compiles = server.aot_stats["compiles"]
    assert compiles >= 1
    again = _serve_all(server, reg)
    assert server.aot_stats["compiles"] == compiles  # no recompiles
    assert server.aot_stats["exec_hits"] > 0
    for t, (x, y) in again.items():
        np.testing.assert_array_equal(y, reg.get(t).predict(x))
        np.testing.assert_array_equal(y, first[t][1])
    assert server.spans_seen()  # ticks recorded their launch buckets


def test_prewarmed_swap_compiles_before_the_fence():
    reg = fleet(4)
    server = CircuitServer(reg, backend="pallas")
    _serve_all(server, reg)
    reg.add("late", make_servable(999, 4, 2, 30, 2))
    compiler = PlanCompiler("pallas", PlacementPolicy())
    plan = compiler.recompile(reg.catalog(), server.peek_plan())
    before = server.aot_stats["compiles"]
    server.swap_plan(plan, compiler=compiler)
    warmed = server.aot_stats["compiles"] - before
    assert warmed >= 1  # new shard hash compiled during the prewarm step
    # the post-swap tick hits the prewarmed executable, no new compile
    compiles = server.aot_stats["compiles"]
    out = _serve_all(server, reg)
    assert server.aot_stats["compiles"] == compiles
    for t, (x, y) in out.items():
        np.testing.assert_array_equal(y, reg.get(t).predict(x))


def test_export_and_preload_round_trip_zero_compiles(tmp_path):
    reg = fleet(3)
    server = CircuitServer(reg, backend="pallas")
    _serve_all(server, reg)
    store = ArtifactStore(str(tmp_path))
    store.put_registry(reg)
    keys = server.export_executables(store)
    assert keys
    for key in keys:
        assert key in store.executable_entries()

    cold = CircuitServer(ArtifactStore(str(tmp_path)).load_registry(),
                         backend="pallas")
    summary = cold.preload_executables(store)
    assert summary["loaded"] == len(keys)
    assert summary["compiled"] == 0 and summary["load_failures"] == 0
    assert cold.aot_stats["compiles"] == 0
    out = _serve_all(cold, reg)
    assert cold.aot_stats["compiles"] == 0  # every launch was preloaded
    for t, (x, y) in out.items():
        np.testing.assert_array_equal(y, reg.get(t).predict(x))


def test_corrupted_executable_falls_back_to_compile(tmp_path):
    reg = fleet(2)
    server = CircuitServer(reg, backend="pallas")
    _serve_all(server, reg)
    store = ArtifactStore(str(tmp_path))
    store.put_registry(reg)
    keys = server.export_executables(store)
    # corrupt one payload on disk; manifest still points at it
    entry = store.executable_entries()[keys[0]]
    with open(os.path.join(str(tmp_path), entry["path"]), "wb") as f:
        f.write(b"not an executable")
    cold = CircuitServer(ArtifactStore(str(tmp_path)).load_registry(),
                         backend="pallas")
    summary = cold.preload_executables(store)
    assert summary["load_failures"] >= 1
    assert summary["compiled"] >= 1  # degraded, not dead
    out = _serve_all(cold, reg)
    for t, (x, y) in out.items():
        np.testing.assert_array_equal(y, reg.get(t).predict(x))


def test_missing_executable_file_falls_back_to_compile(tmp_path):
    reg = fleet(2)
    server = CircuitServer(reg, backend="pallas")
    _serve_all(server, reg)
    store = ArtifactStore(str(tmp_path))
    store.put_registry(reg)
    keys = server.export_executables(store)
    entry = store.executable_entries()[keys[0]]
    os.unlink(os.path.join(str(tmp_path), entry["path"]))
    cold = CircuitServer(ArtifactStore(str(tmp_path)).load_registry(),
                         backend="pallas")
    summary = cold.preload_executables(store)
    assert summary["load_failures"] >= 1
    out = _serve_all(cold, reg)
    for t, (x, y) in out.items():
        np.testing.assert_array_equal(y, reg.get(t).predict(x))


def test_executables_record_their_device(tmp_path):
    reg = fleet(2)
    server = CircuitServer(reg, backend="pallas")
    _serve_all(server, reg)
    store = ArtifactStore(str(tmp_path))
    keys = server.export_executables(store)
    here = jax.devices()[0]
    for key in keys:
        entry = store.executable_entries()[key]
        assert entry["platform"] == here.platform
        assert entry["device_kind"] == here.device_kind


@pytest.mark.parametrize("built_for", [
    {"platform": "tpu", "device_kind": "TPU v5 lite"},
    {"platform": "", "device_kind": ""},  # entry with no device recorded
], ids=["other-device", "unrecorded"])
def test_executable_for_another_device_is_skipped_by_name(
        tmp_path, caplog, built_for):
    reg = fleet(2)
    server = CircuitServer(reg, backend="pallas")
    _serve_all(server, reg)
    store = ArtifactStore(str(tmp_path))
    store.put_registry(reg)
    keys = server.export_executables(store)
    manifest = tmp_path / "manifest.json"
    m = json.loads(manifest.read_text())
    for key in keys:
        m["executables"][key].update(built_for)
    manifest.write_text(json.dumps(m))
    cold = CircuitServer(ArtifactStore(str(tmp_path)).load_registry(),
                         backend="pallas")
    with caplog.at_level("WARNING", logger="repro.serve.aot"):
        summary = cold.preload_executables(ArtifactStore(str(tmp_path)))
    assert summary["skipped"] == len(keys)
    assert summary["loaded"] == 0 and summary["load_failures"] == 0
    assert "was built for platform" in caplog.text
    out = _serve_all(cold, reg)
    for t, (x, y) in out.items():
        np.testing.assert_array_equal(y, reg.get(t).predict(x))


def test_failed_aot_compile_fails_the_tick():
    from repro.runtime import PallasBackend

    def refuse(spec, *, device=None):
        raise RuntimeError("compiler refused the launch")

    reg = fleet(2)
    backend = PallasBackend()
    backend.compile_spans = refuse
    server = CircuitServer(reg, backend=backend)
    x = np.zeros((3, reg.get("t0").encoder.n_features), np.float32)
    server.submit("t0", x)
    with pytest.raises(RuntimeError, match="compiler refused"):
        server.tick()


def test_server_pinned_to_a_device_launches_there():
    reg = fleet(2)
    device = jax.devices()[-1]
    server = CircuitServer(reg, backend="pallas", device=device)
    out = _serve_all(server, reg)
    plan = server.plan()
    for shard in plan.shards:
        for arr in server._dev[shard.content_hash]:
            assert arr.devices() == {device}
    assert all(k[2] == device.id for k in server._aot)
    for t, (x, y) in out.items():
        np.testing.assert_array_equal(y, reg.get(t).predict(x))


def test_ref_server_preload_trace_warms_instead(tmp_path):
    reg = fleet(2)
    ref_server = CircuitServer(reg, backend="ref")
    store = ArtifactStore(str(tmp_path))
    store.put_registry(reg)
    # no-AOT backend exports nothing, with the reason logged not raised
    assert ref_server.export_executables(store) == []
    # explicit prewarm warms the eager jit cache instead
    summary = ref_server.prewarm_plan(ref_server.plan(), spans=[1])
    assert summary["trace_warmed"] >= 1
    out = _serve_all(ref_server, reg)
    for t, (x, y) in out.items():
        np.testing.assert_array_equal(y, reg.get(t).predict(x))


# ---------------------------------------------------------------------------
# fleet artifact: manifest round-trip + subprocess cold boot
# ---------------------------------------------------------------------------

def test_fleet_artifact_manifest_round_trip(tmp_path):
    from repro.serve.fleet.artifact import (
        FLEET_FORMAT_VERSION,
        HostConfig,
    )

    art = FleetArtifact(
        generation=7, content_hash="h" * 16, hosts=("h0", "h1"),
        assignment={"a": "h0", "b": "h1"}, pins={"b": "h1"},
        host_configs={
            "h0": HostConfig(
                host_id="h0", backend="pallas", n_shards=1, span_align=1,
                assignment_mode="round_robin", stable_shapes=True,
                tenants=("a",), placement={"a": ((0, 0),)}, spans=(1,),
            ),
            "h1": HostConfig(
                host_id="h1", backend="pallas", n_shards=1, span_align=1,
                assignment_mode="round_robin", stable_shapes=True,
                tenants=("b",), placement={"b": ((0, 0),)}, spans=(1, 2),
            ),
        },
    )
    store = ArtifactStore(str(tmp_path))
    art.save(store)
    back = FleetArtifact.load(ArtifactStore(str(tmp_path)))
    assert back == art
    # version fence
    bad = art.to_manifest()
    bad["format_version"] = FLEET_FORMAT_VERSION + 1
    with pytest.raises(ValueError, match="unsupported fleet format"):
        FleetArtifact.from_manifest(bad)
    with pytest.raises(ValueError, match="no fleet section"):
        FleetArtifact.load(ArtifactStore(str(tmp_path / "empty")))


_COLD_BOOT = r"""
import sys
import numpy as np
from repro.runtime import aot
from repro.serve.fleet import FleetRouter

path = sys.argv[1]
aot.reset_trace_count()
router = FleetRouter.boot_from_artifact(path)
rows = np.load(path + "/probe.npz")
answers = {}
for tenant in router.tenants():
    x = rows[tenant]
    answers[tenant] = router.submit(tenant, x).result(timeout=60.0)
assert aot.trace_count() == 0, (
    "cold boot traced: " + repr(aot.trace_tags())
)
np.savez(path + "/cold_answers.npz", **answers)
router.close()
print("COLD_BOOT_OK")
"""


def test_subprocess_cold_boot_zero_traces_bitwise_parity(tmp_path):
    from repro.serve.fleet import FleetRouter, InProcTransport, ServingHost

    router = FleetRouter()
    for hid in ("h0", "h1"):
        host = ServingHost(hid, CircuitRegistry(), backend="pallas").start()
        router.add_host(hid, InProcTransport(host))
    circuits = {f"t{i}": make_servable(300 + i, 4 + i % 3, 2, 35, 2 + i % 2)
                for i in range(4)}
    probe = {}
    for name, sc in circuits.items():
        router.register(name, [sc])
        probe[name] = RNG.randn(10, sc.encoder.n_features).astype(
            np.float32
        )
    warm = {t: router.submit(t, x).result(timeout=60.0)
            for t, x in probe.items()}
    summary = router.export_fleet(str(tmp_path))
    assert summary["executables"] >= 2  # one per host at least
    np.savez(tmp_path / "probe.npz", **probe)
    router.close()

    # the subprocess has a stone-cold jit cache: any retrace at boot or
    # first serve trips the in-process counter and fails loudly
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run(
        [sys.executable, "-c", _COLD_BOOT, str(tmp_path)],
        capture_output=True, text=True, timeout=600, env=env, cwd=REPO,
    )
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert "COLD_BOOT_OK" in r.stdout
    cold = np.load(tmp_path / "cold_answers.npz")
    for tenant, y in warm.items():
        np.testing.assert_array_equal(cold[tenant], y)
