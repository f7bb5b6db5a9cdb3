"""`evolve_packed` runs the search through cached jitted programs: the
same search as the uncached `evolve`, traced once per shape, and never a
program built from functions that have since been replaced."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import encoding as E
from repro.core import evolve
from repro.core import fitness as F
from repro.core import gates
from repro.core.evolve import EvolveConfig, evolve_packed, make_eval_fn
from repro.core.genome import CircuitSpec, init_genome
from repro.observability.trace import captured, reset_captured
from tests.test_search_spans import small_fit


def problem(rows=300, seed=0):
    rng = np.random.RandomState(seed)
    x = rng.randn(rows, 3).astype(np.float32)
    y = ((x[:, 0] > 0) ^ (x[:, 1] > 0.5)).astype(np.int64)
    bits = E.encode(E.fit_encoder(x, E.EncodingConfig("quantile", 2)), x)
    data = E.pack_dataset(bits, y, 2)
    mtr, mva = E.split_masks(rows, data.x_words.shape[1], 0.5, seed=1)
    spec = CircuitSpec(bits.shape[1], 12, 1, gates.FULL_FS)
    return spec, data, mtr, mva


def leaves(state):
    return [np.asarray(jax.random.key_data(v) if i == 0 else v)
            for i, v in enumerate(jax.tree.leaves(state))]


@pytest.mark.parametrize("seeded", [False, True], ids=["random", "seeded"])
@pytest.mark.parametrize("backend", ["ref", "pallas"])
def test_cached_search_equals_uncached_evolve(backend, seeded):
    spec, data, mtr, mva = problem()
    cfg = EvolveConfig(kappa=6, max_gens=20, backend=backend)
    seed = init_genome(jax.random.key(7), spec) if seeded else None
    key = jax.random.key(3)
    want = evolve.evolve(key, spec, cfg,
                         make_eval_fn(spec, data, mtr, mva, backend),
                         seed_genome=seed)
    got = evolve_packed(key, spec, cfg, data, mtr, mva, seed_genome=seed)
    assert int(got.gen) > 0
    for a, b in zip(leaves(want), leaves(got), strict=True):
        np.testing.assert_array_equal(a, b)


def test_second_fit_of_the_same_shapes_traces_nothing(tmp_path):
    first = small_fit()
    reset_captured()
    with jax.profiler.trace(str(tmp_path)):
        second = small_fit()
    got = captured()
    reset_captured()
    assert "evolve.loop_traces" not in got
    assert got["evolve.loop"]["count"] == 2
    assert second.records_ == first.records_
    for a, b in zip(jax.tree.leaves(first.genome_),
                    jax.tree.leaves(second.genome_), strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _step_keeps_state(state, spec, cfg, eval_fn):
    return state._replace(gen=state.gen + 1, since=state.since + 1)


def _counts_nothing(out_words, data, mask_words):
    zeros = jnp.zeros(data.class_words.shape[0], jnp.int32)
    return zeros, zeros


@pytest.mark.parametrize("module,name,replacement", [
    (evolve, "generation_step", _step_keeps_state),
    (F, "confusion_counts", _counts_nothing),
], ids=["generation_step", "confusion_counts"])
def test_function_replaced_after_a_cached_search_takes_effect(
        monkeypatch, module, name, replacement):
    spec, data, mtr, mva = problem()
    cfg = EvolveConfig(kappa=6, max_gens=40)
    key = jax.random.key(5)
    sound = evolve_packed(key, spec, cfg, data, mtr, mva)
    assert float(sound.best_val) > 0

    monkeypatch.setattr(module, name, replacement)
    patched = evolve_packed(key, spec, cfg, data, mtr, mva)
    if name == "generation_step":  # the first parent, kept for kappa
        assert int(patched.gen) == cfg.kappa
        first = evolve.init_state(key, spec,
                                  make_eval_fn(spec, data, mtr, mva))
        assert float(patched.best_val) == float(first.best_val)
    else:  # every fitness reads 0
        assert float(patched.best_val) == 0.0

    monkeypatch.undo()
    again = evolve_packed(key, spec, cfg, data, mtr, mva)
    for a, b in zip(leaves(sound), leaves(again), strict=True):
        np.testing.assert_array_equal(a, b)
