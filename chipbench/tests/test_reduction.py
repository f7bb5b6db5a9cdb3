"""The trace reduction and the roofline count, on a trace recorded on a
TPU v5e: three launches of the search kernel at the higgs shape
(quantize-4: 116 input rows, 3,072 words after padding, 4 circuits of 300
gates, 1 output), two of them inside the annotated window."""
import os

import pytest

from harness import result, spec, trace

DATA = os.path.join(os.path.dirname(__file__), "data",
                    "search_kernel.xplane.pb")
# by hand: opcodes 4*300*4 + edges 4*300*2*4 + taps 4*1*4
# + x 116*3072*4 + out 4*1*3072*4
CALL_BYTES = 4800 + 9600 + 16 + 1_425_408 + 49_152


@pytest.fixture(scope="module")
def summary():
    return trace.reduce_trace(DATA, 1)


def test_custom_call_bytes_from_hlo_text():
    text = ("%eval_population_kernel.1 = u32[4,1,3072]{2,1,0:T(1,128)} "
            "custom-call(s32[1200]{0:T(1024)S(1)} %reshape.0, "
            "s32[2400]{0:T(1024)S(1)} %reshape.1, s32[4]{0:T(128)S(1)} "
            "%bitcast.2, u32[116,3072]{1,0:T(8,128)S(1)} %pad.0), "
            'custom_call_target="tpu_custom_call"')
    assert trace.custom_call_bytes(text) == CALL_BYTES
    assert trace.short_name(text) == "eval_population_kernel"
    assert trace.custom_call_bytes("%pad.0 = u32[116,3072] pad(...)") is None


def test_kernel_events_and_busy_time(summary):
    k = summary["kernels"]["eval_population_kernel"]
    assert k["count"] == 2
    assert k["bytes"] == 2 * CALL_BYTES
    assert k["seconds"] == pytest.approx(2 * 157.5e-6, rel=0.01)
    assert summary["busy_s"] >= k["seconds"]
    assert 0 < summary["busy_s"] < summary["window_s"] == pytest.approx(
        0.065577, rel=1e-3)
    names = [n for n, _ in summary["device_ops"]]
    assert names[0] == "eval_population_kernel"
    gaps = sum(s for _, s in summary["idle_gaps"])
    assert gaps == pytest.approx(summary["window_s"] - summary["busy_s"],
                                 rel=1e-6)


def test_roofline_share_from_bytes_and_peak(summary):
    cell = spec.load_cell("search-higgs")
    run = result.Run(cell=cell, device_kind="TPU v5 lite", trace=summary,
                     counters={"fits": 1, "generations": 2,
                               "compile_requests": 4})
    share = spec.reader("eval_population_kernel_roofline")(run)
    expected = 100 * 2 * CALL_BYTES / 819e9 / summary["kernels"][
        "eval_population_kernel"]["seconds"]
    assert share == pytest.approx(expected)
    assert 1.0 < share < 1.3  # about 1.15%: far under the roofline
    run.device_kind = "TPU v99"
    with pytest.raises(KeyError):
        spec.reader("eval_population_kernel_roofline")(run)


def test_every_per_layer_metric_has_a_reader():
    import json

    with open(spec.BENCHMARK) as f:
        bench = json.load(f)
    for m in bench["per_layer"]:
        assert callable(spec.reader(m["name"]))
