"""Whole runs of each cell at test sizes on the CPU, with the chip check
skipped: a sound run is correct, the control fails its limit, and each
fault planted under the timed path turns ``correct`` false."""
import json
import os
import subprocess
import sys

import jax.numpy as jnp
import pytest

from harness import cell_result, control, env, search

env.configure_jax()

import cells  # noqa: E402

SEED = 2147483999
SECONDS = 2.0


def run(name, traced=False):
    return cell_result.run_and_emit(cells.cell(name), SEED, SECONDS, traced,
                                    require_chip=False)


@pytest.mark.parametrize("name", ["search-higgs", "search-led"])
def test_sound_run_is_correct(name):
    out = run(name)
    assert out["correct"], out["checks"]
    assert out["checks"]["no_gain_share"][0] <= search.NO_GAIN_LIMIT


def test_search_control_fails():
    """The control goes through the run's own check and comes out not
    correct, on its fitness gap alone."""
    out = cell_result.run_and_emit(cells.cell("search-higgs"), SEED, SECONDS,
                                   False, require_chip=False,
                                   control=control.bfloat16_fitness)
    assert not out["correct"]
    checks = out["checks"]
    assert checks["fitness_gap"][0] > search.FITNESS_LIMIT


# ------------------------------------------------------------- faults

def _answer_altered(out):
    return out.at[..., 0].set(~out[..., 0])


def test_search_half_the_rows_left_out_is_caught(monkeypatch):
    from repro.core import fitness as F

    counts = F.confusion_counts

    def first_half(out_words, data, mask_words):
        w = mask_words.shape[0]
        keep = jnp.where(jnp.arange(w) < w // 2, jnp.uint32(0xFFFFFFFF),
                         jnp.uint32(0))
        return counts(out_words, data, mask_words & keep)

    monkeypatch.setattr(F, "confusion_counts", first_half)
    assert not run("search-higgs")["correct"]


def test_search_answer_altered_is_caught(monkeypatch):
    from repro.kernels import circuit_eval

    kernel = circuit_eval.eval_population_kernel

    def altered(*args, **kwargs):
        return _answer_altered(kernel(*args, **kwargs))

    monkeypatch.setattr(circuit_eval, "eval_population_kernel", altered)
    assert not run("search-higgs")["correct"]


def test_search_state_unchanged_is_caught(monkeypatch):
    """A generation step that returns its state: the compiler drops the
    loop, and every search ends after 0 generations, short of kappa."""
    from repro.core import evolve

    monkeypatch.setattr(evolve, "generation_step",
                        lambda state, spec, cfg, eval_fn: state)
    out = run("search-higgs")
    assert not out["correct"]
    assert out["checks"]["no_gain_share"][0] == 1.0


def test_search_parent_kept_is_caught(monkeypatch):
    """A generation step that counts on but keeps its parent and its best
    circuit: every search ends after kappa generations on its first
    circuit, whose fitness the reference confirms: no search gains."""
    from repro.core import evolve

    step = evolve.generation_step

    def keep_parent(state, spec, cfg, eval_fn):
        new = step(state, spec, cfg, eval_fn)
        return state._replace(key=new.key, gen=state.gen + 1,
                              since=state.since + 1)

    monkeypatch.setattr(evolve, "generation_step", keep_parent)
    out = run("search-higgs")
    assert not out["correct"]
    assert out["checks"]["fitness_gap"][0] <= search.FITNESS_LIMIT
    assert out["checks"]["no_gain_share"][0] == 1.0


CHILD = """
import sys
sys.path[:0] = {paths!r}
import conftest
from harness import cell_result, env
env.configure_jax()
import cells
from repro.core import evolve
step = evolve.generation_step
# everything moves but the counters: the loop never ends
evolve.generation_step = lambda s, spec, cfg, f: step(s, spec, cfg, f)._replace(
    gen=s.gen, since=s.since)
cell = cells.cell("search-higgs")
cell.traffic["fit_timeout_s"] = 20
cell_result.run_and_emit(cell, {seed}, {seconds}, False, require_chip=False)
"""


def test_search_step_that_never_counts_is_caught():
    """A generation step that never advances the loop's counters never
    ends the fit: no answer comes, and the run ends not correct."""
    here = os.path.dirname(os.path.abspath(__file__))
    code = CHILD.format(paths=[here, os.path.dirname(here)], seed=SEED,
                        seconds=SECONDS)
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=300)
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert p.returncode != 0 and line["correct"] is False
    assert line["checks"]["fits_never_done"]["value"] == 1
