"""The search's spans and counter (`AutoTinyClassifier.fit`, `evolve`)
reach a JAX profiler capture, and nothing outside one."""
import jax
import numpy as np
import pytest

from repro.core.api import AutoTinyClassifier
from repro.core.encoding import EncodingConfig
from repro.observability.trace import captured, reset_captured
from tests.test_observability import host_event_names

LEAVES = ("fit.encode", "evolve.init", "evolve.loop", "fit.readback",
          "fit.ref_stats")
# the steps of `fit.encode`, each a span inside it
ENCODE_STEPS = ("fit.encode.edges", "fit.encode.bits", "fit.encode.pack",
                "fit.encode.split")


def small_fit():
    rng = np.random.RandomState(0)
    x = rng.rand(256, 4).astype(np.float32)
    y = (x[:, 0] + x[:, 1] > 1.0).astype(np.int64)
    clf = AutoTinyClassifier(
        n_gates=10, encodings=[EncodingConfig("quantize", 2),
                               EncodingConfig("quantile", 2)],
        kappa=4, max_gens=12, seed=1)
    return clf.fit(x, y, 2)


@pytest.fixture(scope="module")
def capture(tmp_path_factory):
    """One test-size fit under a CPU profiler capture, with JAX's caches
    cleared first, so that the fit traces its search programs whatever
    ran before: the capture table and the trace's directory."""
    trace_dir = tmp_path_factory.mktemp("search_trace")
    jax.clear_caches()
    reset_captured()
    with jax.profiler.trace(str(trace_dir)):
        small_fit()
    got = captured()
    reset_captured()
    return got, trace_dir


@pytest.mark.parametrize("name,count", [
    ("fit", 1), ("fit.encode", 2), ("evolve.init", 2), ("evolve.loop", 2),
    ("fit.readback", 2), ("evolve.loop_traces", 1),
    ("fit.encode.edges", 2), ("fit.encode.bits", 2), ("fit.encode.pack", 2),
    ("fit.encode.split", 2), ("fit.ref_stats", 1),
    ("fit.encode_overlapped", 1)])
def test_fit_under_a_capture_counts_each_span(capture, name, count):
    got, _ = capture
    assert got[name]["count"] == count


def test_fit_spans_nest_inside_fit_and_reach_the_host_plane(capture):
    got, trace_dir = capture
    assert set(got) == {"fit", "evolve.loop_traces", "fit.encode_overlapped",
                        *LEAVES, *ENCODE_STEPS}
    assert all(got[name]["seconds"] > 0
               for name in ("fit", *LEAVES, *ENCODE_STEPS))
    assert sum(got[name]["seconds"] for name in LEAVES) <= got["fit"]["seconds"]
    assert {"fit", *LEAVES, *ENCODE_STEPS} <= host_event_names(trace_dir)


def test_encode_steps_nest_inside_fit_encode(capture):
    got, _ = capture
    steps = sum(got[name]["seconds"] for name in ENCODE_STEPS)
    assert steps <= got["fit.encode"]["seconds"]


def test_fit_after_stop_trace_records_nothing(tmp_path):
    reset_captured()
    jax.profiler.start_trace(str(tmp_path))
    jax.profiler.stop_trace()
    small_fit()
    assert captured() == {}
