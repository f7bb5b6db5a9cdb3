"""`AutoTinyClassifier.fit` dispatches every encoding's search before it
reads any back: the result must be the one a plain loop gives, which
encodes, searches and reads back each encoding in turn."""
import jax
import numpy as np
import pytest

from repro.core import encoding as E
from repro.core.api import DEFAULT_ENCODINGS, AutoTinyClassifier
from repro.core.evolve import evolve_packed
from repro.core.genome import CircuitSpec
from repro.observability.trace import captured, reset_captured

N_CLASSES = 3


def table():
    rng = np.random.RandomState(7)
    x = rng.rand(300, 5).astype(np.float32)
    y = (np.digitize(x[:, 0] + 0.5 * x[:, 2], [0.5, 1.0])).astype(np.int64)
    return x, y


def classifier(encodings):
    return AutoTinyClassifier(n_gates=12, encodings=encodings, kappa=5,
                              max_gens=20, seed=3, backend="ref")


def sequential_fit(clf, x, y):
    """Each encoding start to finish: encode, search, read back."""
    n_out = max(1, int(np.ceil(np.log2(N_CLASSES))))
    records, best = [], None
    for ei, ecfg in enumerate(clf.encodings):
        enc = E.fit_encoder(x, ecfg)
        bits = E.encode(enc, x)
        data = E.pack_dataset(bits, y, N_CLASSES, n_out)
        mtr, mva = E.split_masks(x.shape[0], data.x_words.shape[1],
                                 clf.val_fraction, seed=clf.seed + ei)
        spec = CircuitSpec(n_inputs=bits.shape[1], n_nodes=clf.n_gates,
                           n_outputs=n_out, fn_set=clf.fn_set)
        final = evolve_packed(jax.random.key(clf.seed * 1000 + ei), spec,
                              clf.cfg, data, mtr, mva)
        rec = (ecfg, float(final.best_val), float(final.best_train),
               int(final.gen))
        records.append(rec)
        if best is None or rec[1] > best[0]:
            best = (rec[1], spec, final.best, enc, bits)
    _, spec, genome, enc, bits = best
    return records, spec, genome, enc, bits.mean(axis=0).astype(np.float32)


def assert_same_fit(clf, want):
    records, spec, genome, enc, ref_stats = want
    got = [(r.encoding, r.val_fitness, r.train_fitness, r.generations)
           for r in clf.records_]
    assert got == records
    assert clf.spec_ == spec
    for name in ("gate_fn", "edge_src", "out_src"):
        np.testing.assert_array_equal(getattr(clf.genome_, name),
                                      getattr(genome, name))
    assert (clf.encoder_.strategy, clf.encoder_.bits) == (enc.strategy,
                                                          enc.bits)
    np.testing.assert_array_equal(clf.encoder_.thresholds, enc.thresholds)
    np.testing.assert_array_equal(clf.encoder_.codes, enc.codes)
    np.testing.assert_array_equal(clf.ref_stats_, ref_stats)


@pytest.mark.parametrize("encodings", [DEFAULT_ENCODINGS,
                                       DEFAULT_ENCODINGS[2:3]],
                         ids=["four", "one"])
def test_fit_equals_a_sequential_loop(encodings):
    x, y = table()
    clf = classifier(encodings)
    want = sequential_fit(clf, x, y)
    clf.fit(x, y, N_CLASSES)
    assert len(clf.records_) == len(encodings)
    assert_same_fit(clf, want)


@pytest.mark.parametrize("encodings,overlapped", [
    (DEFAULT_ENCODINGS[:1], None), (DEFAULT_ENCODINGS, 3)],
    ids=["one", "four"])
def test_fit_counts_encodings_overlapped(tmp_path, encodings, overlapped):
    x, y = table()
    reset_captured()
    with jax.profiler.trace(str(tmp_path)):
        classifier(encodings).fit(x, y, N_CLASSES)
    got = captured().get("fit.encode_overlapped")
    reset_captured()
    assert (got and got["count"]) == overlapped
