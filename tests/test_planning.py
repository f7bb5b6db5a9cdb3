"""Launch planning layer: PlacementPolicy → PlanCompiler → LaunchPlan.

Covers the declarative placement API end to end: policy validation,
deterministic compilation and content hashing, registry-mutation →
plan-invalidation, golden span offsets / padding for a fixed catalog,
span alignment against backend capabilities, and the acceptance parity
matrix — sharded (2+) and ensemble launches must predict bit-identically
to the single-shard ``"ref"`` path for the same catalog and inputs.
"""
import numpy as np
import pytest

from repro.runtime import get_backend
from repro.serve.circuits import CircuitRegistry, CircuitServer
from repro.serve.planning import (
    PlacementPolicy,
    PlanCompiler,
    SlotRef,
    circuit_digest,
    ensemble_vote,
)
from tests.test_serve_circuits import TENANT_SHAPES, make_servable

RNG = np.random.RandomState(11)


@pytest.fixture
def registry():
    reg = CircuitRegistry()
    for i, shape in enumerate(TENANT_SHAPES):
        reg.add(f"t{i}", make_servable(60 + i, *shape))
    return reg


# ---------------------------------------------------------------------------
# Policy
# ---------------------------------------------------------------------------

def test_policy_validation():
    PlacementPolicy()  # defaults are valid
    with pytest.raises(ValueError, match="n_shards"):
        PlacementPolicy(n_shards=0)
    with pytest.raises(ValueError, match="span_align"):
        PlacementPolicy(span_align=0)
    with pytest.raises(ValueError, match="assignment"):
        PlacementPolicy(assignment="alphabetical")
    PlacementPolicy(span_align=None)  # derive from backend


def test_span_align_resolution_against_backend():
    assert PlanCompiler("ref", PlacementPolicy()).span_align == 1
    assert PlanCompiler("ref", PlacementPolicy(span_align=4)).span_align == 4
    pal = get_backend("pallas")
    derived = PlanCompiler("pallas", PlacementPolicy(span_align=None))
    assert derived.span_align == pal.capabilities().word_alignment
    explicit = PlanCompiler("pallas", PlacementPolicy(span_align=128))
    assert explicit.span_align % pal.capabilities().word_alignment == 0


def test_native_pallas_rounds_span_alignment_to_lanes():
    from repro.runtime import PallasBackend

    native = PallasBackend(interpret=False)
    assert native.span_alignment(1) == 128
    assert native.span_alignment(200) == 256
    assert PallasBackend(interpret=True).span_alignment(1) == 1


def test_launch_slot_bound_is_named_by_the_compiler(registry):
    """A shard over the backend's per-launch slot bound is refused when
    the plan is built, naming the bound; more shards fit."""
    from repro.runtime import BackendCapabilityError, PallasBackend

    assert PallasBackend().max_launch_slots(300, 3) == 285
    assert get_backend("ref").max_launch_slots(300, 3) is None
    small = PallasBackend()
    small.max_launch_slots = lambda n_nodes, n_outputs: 2
    n = len(registry)
    with pytest.raises(BackendCapabilityError, match="at most 2"):
        PlanCompiler(small, PlacementPolicy()).compile(registry.catalog())
    plan = PlanCompiler(small, PlacementPolicy(n_shards=-(-n // 2))).compile(
        registry.catalog())
    assert max(sh.n_slots for sh in plan.shards) <= 2


def test_pallas_launch_over_slot_bound_raises():
    import jax.numpy as jnp

    from repro.runtime import BackendCapabilityError, PallasBackend

    small = PallasBackend()
    small.max_launch_slots = lambda n_nodes, n_outputs: 2
    opc = jnp.zeros((3, 4), jnp.int32)
    with pytest.raises(BackendCapabilityError, match="SMEM bound of 2"):
        small.eval_population(opc, jnp.zeros((3, 4, 2), jnp.int32),
                              jnp.zeros((3, 1), jnp.int32),
                              jnp.zeros((2, 4), jnp.uint32))


# ---------------------------------------------------------------------------
# Compilation: determinism, assignment, goldens
# ---------------------------------------------------------------------------

def test_compile_is_pure_and_deterministic(registry):
    cat = registry.catalog()
    comp = PlanCompiler("ref", PlacementPolicy(n_shards=2))
    a, b = comp.compile(cat), comp.compile(cat)
    assert a.content_hash == b.content_hash
    assert a.placement == b.placement
    for sa, sb in zip(a.shards, b.shards):
        assert sa.content_hash == sb.content_hash
        np.testing.assert_array_equal(sa.opcodes, sb.opcodes)


def test_golden_round_robin_placement(registry):
    """Pin the exact layout the default policy compiles for a fixed
    catalog: slot assignment, per-shard padding, and span offsets."""
    plan = PlanCompiler(
        "ref", PlacementPolicy(n_shards=2, span_align=4)
    ).compile(registry.catalog())
    # round-robin over catalog order: t0,t2 → shard 0; t1,t3 → shard 1
    assert plan.placement == {
        "t0": (SlotRef(0, 0),), "t1": (SlotRef(1, 0),),
        "t2": (SlotRef(0, 1),), "t3": (SlotRef(1, 1),),
    }
    s0, s1 = plan.shards
    assert s0.slot_tenants == ("t0", "t2") and s1.slot_tenants == ("t1", "t3")
    # TENANT_SHAPES: (feats, bits, gates, classes); in_width = feats*bits
    np.testing.assert_array_equal(s0.in_width, [8, 6])
    np.testing.assert_array_equal(s1.in_width, [28, 40])
    # per-shard padding: shard maxima, not global maxima
    assert s0.opcodes.shape == (2, 40) and s1.opcodes.shape == (2, 120)
    assert s0.n_inputs_max == 8 and s1.n_inputs_max == 40
    np.testing.assert_array_equal(s0.out_width, [1, 2])
    np.testing.assert_array_equal(s1.out_width, [2, 3])
    # span offsets: slot k owns words [k*span, (k+1)*span)
    np.testing.assert_array_equal(s0.word_offsets(8), [0, 8])
    assert plan.span_align == 4
    # plans are immutable snapshots
    with pytest.raises(ValueError):
        s0.opcodes[0, 0] = 99


def test_contiguous_and_balanced_assignments(registry):
    cat = registry.catalog()
    cont = PlanCompiler(
        "ref", PlacementPolicy(n_shards=2, assignment="contiguous")
    ).compile(cat)
    assert cont.shards[0].slot_tenants == ("t0", "t1")
    assert cont.shards[1].slot_tenants == ("t2", "t3")
    bal = PlanCompiler(
        "ref", PlacementPolicy(n_shards=2, assignment="balanced")
    ).compile(cat)
    # every shard gets work, and the heaviest two circuits are split
    costs = {
        t: registry.get(t).spec.n_inputs + registry.get(t).spec.n_nodes
        for t in registry
    }
    heavy = sorted(costs, key=costs.get)[-2:]
    shards_of_heavy = {bal.shard_of(t) for t in heavy}
    assert len(shards_of_heavy) == 2
    assert all(s.n_slots > 0 for s in bal.shards)


def test_more_shards_than_slots_clamps(registry):
    plan = PlanCompiler(
        "ref", PlacementPolicy(n_shards=64)
    ).compile(registry.catalog())
    assert plan.n_shards == len(TENANT_SHAPES)
    assert all(s.n_slots == 1 for s in plan.shards)


# ---------------------------------------------------------------------------
# Invalidation: generation bumps and content hashes
# ---------------------------------------------------------------------------

def test_remove_readd_bumps_generation_and_hash(registry):
    comp = PlanCompiler("ref")
    plan0 = comp.compile(registry.catalog())
    gen0 = registry.generation

    sc_old = registry.get("t1")
    registry.remove("t1")
    assert registry.generation == gen0 + 1
    plan_removed = comp.compile(registry.catalog())
    assert plan_removed.generation == gen0 + 1
    assert plan_removed.content_hash != plan0.content_hash

    # re-add different content under the same name: stale hash never reused
    registry.add("t1", make_servable(999, *TENANT_SHAPES[1]))
    plan_new = comp.compile(registry.catalog())
    assert plan_new.generation == gen0 + 2
    assert plan_new.content_hash != plan0.content_hash
    assert plan_new.content_hash != plan_removed.content_hash

    # hot-swap the original artifact back in: slot order moved (t1 now
    # sits last in the catalog), so the hash still differs from plan0 —
    # placement is content too
    registry.add("t1", sc_old, replace=True)
    plan_back = comp.compile(registry.catalog())
    assert plan_back.generation == gen0 + 3
    assert plan_back.content_hash != plan0.content_hash
    # but swapping away and back *in place* converges: the hash is about
    # *what launches where*, the generation about *when it changed*
    swap_hash = plan_back.content_hash
    registry.add("t1", make_servable(999, *TENANT_SHAPES[1]), replace=True)
    registry.add("t1", sc_old, replace=True)
    plan_again = comp.compile(registry.catalog())
    assert plan_again.generation == gen0 + 5
    assert plan_again.content_hash == swap_hash


def test_policy_changes_hash(registry):
    cat = registry.catalog()
    h1 = PlanCompiler("ref", PlacementPolicy()).compile(cat).content_hash
    h2 = PlanCompiler(
        "ref", PlacementPolicy(n_shards=2)
    ).compile(cat).content_hash
    h3 = PlanCompiler(
        "ref", PlacementPolicy(span_align=4)
    ).compile(cat).content_hash
    assert len({h1, h2, h3}) == 3


def test_circuit_digest_tracks_content(tmp_path):
    from repro.core.api import ServableCircuit

    a = make_servable(5, 4, 2, 30, 2)
    b = ServableCircuit.load(a.save(str(tmp_path / "a.npz")))
    c = make_servable(6, 4, 2, 30, 2)
    # bit-identical artifact (save/load roundtrip) → identical digest
    assert circuit_digest(a) == circuit_digest(b)
    assert circuit_digest(a) != circuit_digest(c)


def test_server_picks_up_new_plan_and_drops_stale_tensors(registry):
    server = CircuitServer(registry)
    h0 = server.plan().content_hash
    x = RNG.randn(5, 4).astype(np.float32)
    server.predict("t0", x)
    registry.add("t0", make_servable(321, *TENANT_SHAPES[0]), replace=True)
    assert server.plan().content_hash != h0
    np.testing.assert_array_equal(
        server.predict("t0", x), registry.get("t0").predict(x)
    )


# ---------------------------------------------------------------------------
# Ensemble voting
# ---------------------------------------------------------------------------

def test_ensemble_vote_majority_and_ties():
    ids = np.array([[0, 1, 2, 2], [0, 1, 1, 2], [1, 1, 0, 0]])
    # col 2 is a three-way tie → lowest class id wins
    np.testing.assert_array_equal(ensemble_vote(ids, 3), [0, 1, 0, 2])
    # even split breaks toward the lowest class id (deterministic)
    ids = np.array([[2, 0], [1, 0]])
    np.testing.assert_array_equal(ensemble_vote(ids, 3), [1, 0])
    # single member is the identity
    np.testing.assert_array_equal(
        ensemble_vote(np.array([[3, 1]]), 4), [3, 1]
    )


# ---------------------------------------------------------------------------
# Acceptance parity matrix: sharded + ensemble vs single-shard "ref"
# ---------------------------------------------------------------------------

def _fleet_with_ensemble() -> CircuitRegistry:
    reg = CircuitRegistry()
    for i, shape in enumerate(TENANT_SHAPES):
        reg.add(f"t{i}", make_servable(80 + i, *shape))
    reg.add_ensemble(
        "ens", [make_servable(90 + i, 6, 2, 50, 3) for i in range(3)]
    )
    return reg


def _traffic(reg: CircuitRegistry, rng) -> dict:
    return {
        tenant: rng.randn(
            3 + 7 * i, reg.get(tenant).encoder.n_features
        ).astype(np.float32)
        for i, tenant in enumerate(reg)
    }


@pytest.mark.parametrize("backend", ["ref", "pallas"])
@pytest.mark.parametrize("n_shards", [2, 3])
@pytest.mark.parametrize("assignment", ["round_robin", "balanced"])
def test_parity_matrix_sharded_ensemble_vs_ref(backend, n_shards, assignment):
    """Sharded (2+) and ensemble launches are bit-identical to the
    single-shard "ref" baseline for the same catalog and inputs."""
    rng = np.random.RandomState(n_shards * 17 + len(assignment))
    reg = _fleet_with_ensemble()
    traffic = _traffic(reg, rng)

    baseline_server = CircuitServer(reg, backend="ref")
    baseline = {
        t: baseline_server.predict(t, x) for t, x in traffic.items()
    }

    server = CircuitServer(
        reg, backend=backend,
        policy=PlacementPolicy(n_shards=n_shards, assignment=assignment),
    )
    tickets = {t: server.submit(t, x) for t, x in traffic.items()}
    report = server.tick()
    assert report.launches > 1  # genuinely sharded
    assert report.plan_shards == n_shards
    for t, ticket in tickets.items():
        np.testing.assert_array_equal(server.result(ticket), baseline[t])


@pytest.mark.parametrize("backend", ["ref", "pallas"])
def test_span_align_128_policy_satisfies_backend_alignment(backend):
    reg = _fleet_with_ensemble()
    be = get_backend(backend)
    server = CircuitServer(
        reg, backend=backend,
        policy=PlacementPolicy(n_shards=2, span_align=128),
    )
    assert server.plan().span_align == 128
    rng = np.random.RandomState(3)
    traffic = _traffic(reg, rng)
    tickets = {t: server.submit(t, x) for t, x in traffic.items()}
    report = server.tick()
    assert report.span_words % 128 == 0
    assert report.span_words % be.capabilities().word_alignment == 0
    baseline = CircuitServer(reg, backend="ref")
    for t, ticket in tickets.items():
        np.testing.assert_array_equal(
            server.result(ticket), baseline.predict(t, traffic[t])
        )


# ---------------------------------------------------------------------------
# Ensemble persistence rides the catalog
# ---------------------------------------------------------------------------

def test_load_dir_accepts_legacy_at_sign_tenant_names(tmp_path):
    """Directories written before '@m<idx>' was reserved may hold tenants
    like 'model@v2' or 'exp@2' — they must restore verbatim, not crash
    or be silently renamed as ensemble members.  Only the member shape
    save_dir actually writes (contiguous @m0..@m(k-1), k >= 2) parses as
    an ensemble, and a restored legacy fleet must save_dir again."""
    sc = make_servable(33, 4, 2, 30, 2)
    sc.save(str(tmp_path / "model@v2.circuit.npz"))
    sc.save(str(tmp_path / "exp@2.circuit.npz"))     # '@digit' is legal
    sc.save(str(tmp_path / "pad@m00.circuit.npz"))   # zero-pad: not ours
    sc.save(str(tmp_path / "ens@m0.circuit.npz"))    # well-formed pair
    sc.save(str(tmp_path / "ens@m1.circuit.npz"))
    # a plain 'a' bundle beside a@m0/a@m1 look-alikes: all three are
    # distinct legacy tenants, nothing is dropped or merged
    sc.save(str(tmp_path / "a.circuit.npz"))
    sc.save(str(tmp_path / "a@m0.circuit.npz"))
    sc.save(str(tmp_path / "a@m1.circuit.npz"))
    restored = CircuitRegistry.load_dir(str(tmp_path))
    assert set(restored) == {"model@v2", "exp@2", "pad@m00", "ens",
                             "a", "a@m0", "a@m1"}
    assert len(restored.members("exp@2")) == 1
    assert len(restored.members("ens")) == 2
    x = RNG.randn(5, 4).astype(np.float32)
    np.testing.assert_array_equal(
        restored.get("model@v2").predict(x), sc.predict(x)
    )
    # the documented persist → restart → persist flow must round-trip
    # for the '@'-containing names load_dir just accepted...
    keep = CircuitRegistry()
    for t in ("model@v2", "exp@2", "pad@m00"):
        keep.add(t, restored.get(t))
    out = tmp_path / "resaved"
    keep.save_dir(str(out))
    assert set(CircuitRegistry.load_dir(str(out))) == set(keep)
    # ...but names colliding with the reserved member suffix cannot be
    # persisted (they would be misparsed as members on the next load)
    reg = CircuitRegistry()
    reg.add("bad@m7", sc)
    with pytest.raises(ValueError, match="reserved"):
        reg.save_dir(str(tmp_path / "nope"))


def test_load_dir_incoherent_member_group_restores_plain_tenants(tmp_path):
    """Legacy plain tenants 'y@m0'/'y@m1' with incompatible shapes can't
    be an ensemble — the restore must keep them as separate tenants, not
    merge them or abort the whole fleet load."""
    a = make_servable(41, 4, 2, 30, 2)
    b = make_servable(42, 7, 2, 30, 3)  # different width AND classes
    a.save(str(tmp_path / "y@m0.circuit.npz"))
    b.save(str(tmp_path / "y@m1.circuit.npz"))
    restored = CircuitRegistry.load_dir(str(tmp_path))
    assert set(restored) == {"y@m0", "y@m1"}
    x = RNG.randn(3, 7).astype(np.float32)
    np.testing.assert_array_equal(
        restored.get("y@m1").predict(x), b.predict(x)
    )


def test_ensemble_fleet_persistence_roundtrip(tmp_path):
    reg = _fleet_with_ensemble()
    reg.save_dir(str(tmp_path))
    restored = CircuitRegistry.load_dir(str(tmp_path))
    assert set(restored) == set(reg)
    assert len(restored.members("ens")) == 3
    x = RNG.randn(12, 6).astype(np.float32)
    np.testing.assert_array_equal(
        CircuitServer(restored).predict("ens", x),
        CircuitServer(reg).predict("ens", x),
    )
