"""`search-clickpred` at test size on the CPU, on more than one word
block: the first 40,000 rows of the clickpred table are 1,250 words, so
the kernel (Pallas in interpret mode) walks three 512-word blocks, the
last of them padded.  A sound run is correct, and a fault planted at
this size turns ``correct`` false."""
import copy

import jax.numpy as jnp
import pytest

from harness import cell_result, env, spec, tabular

env.configure_jax()

import cells  # noqa: E402

SEED = 2147483999
SECONDS = 2.0
ROWS = 40_000


@pytest.fixture
def clickpred(monkeypatch):
    """The cell at test size, on the first ``ROWS`` rows of each table it
    draws."""
    table = tabular.table

    def first_rows(name, seed):
        x, y, n_classes = table(name, seed)
        return x[:ROWS], y[:ROWS], n_classes

    monkeypatch.setattr(tabular, "table", first_rows)
    c = copy.deepcopy(spec.load_cell("search-clickpred"))
    c.config.update(cells.TINY_SEARCH)
    c.traffic.update({"min_fit_s": 0.5, "fit_timeout_s": 120})
    return c


def run(cell):
    return cell_result.run_and_emit(cell, SEED, SECONDS, False,
                                    require_chip=False)


def test_rows_span_three_word_blocks():
    from repro.core.encoding import n_words
    from repro.runtime import resolve_backend

    words = n_words(ROWS)
    n_signals = 10 * 4 + cells.TINY_SEARCH["n_gates"]
    block = resolve_backend("pallas").pick_block_words(n_signals, words)
    assert (words, block) == (1_250, 512)
    assert -(-words // block) == 3 and words % block


def test_sound_run_is_correct(clickpred):
    out = run(clickpred)
    assert out["correct"], out["checks"]


def test_half_the_rows_left_out_is_caught(clickpred, monkeypatch):
    from repro.core import fitness as F

    counts = F.confusion_counts

    def first_half(out_words, data, mask_words):
        w = mask_words.shape[0]
        keep = jnp.where(jnp.arange(w) < w // 2, jnp.uint32(0xFFFFFFFF),
                         jnp.uint32(0))
        return counts(out_words, data, mask_words & keep)

    monkeypatch.setattr(F, "confusion_counts", first_half)
    assert not run(clickpred)["correct"]
