"""The circuit kernels compile for a TPU v5e chip.

Interpret mode, which every other test runs, cannot see the chip's own
limits: VMEM and SMEM capacity, tiling of blocks and of scalar-prefetched
operands.  These tests hand the kernels, at real widths, to the TPU
compiler for a described (not attached) v5e chip.  Nothing runs, so they
say nothing about results or time; the parity tests cover results.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and collection must give
every test worker the same tests.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.encoding import n_words
from repro.runtime import PallasBackend, aot

N_GATES = 300                    # paper §5.4
SERVE_INPUTS, SERVE_OUTPUTS = 48, 3  # widest shape of the serving mix
SPAN = 128                       # one lane-aligned span per slot
NATIVE = PallasBackend(interpret=False)  # Mosaic lowering, not interpret


@pytest.fixture(scope="module")
def chip():
    """One described v5e chip, with the persistent compile cache off
    (entries compiled for a described chip cannot be read back here)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    cache_was_on = jax.config.jax_enable_compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2")
        except Exception as err:  # noqa: BLE001 — no TPU compiler here
            jax.config.update("jax_enable_compilation_cache", cache_was_on)
            pytest.skip(f"no v5e:2x2 topology can be described here: {err}")
        yield topo.devices[0]
        jax.config.update("jax_enable_compilation_cache", cache_was_on)


def _shapes(device, *specs):
    sharding = SingleDeviceSharding(device)
    return [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in specs]


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("n_inputs,rows", [
    (29 * 4, 98_050),     # higgs, quantize-4
    (1_637 * 4, 5_418),   # christine, quantize-4: the widest VMEM table
    (10 * 4, 1_496_391),  # clickpred, quantize-4: the longest grid
], ids=["higgs", "christine-q4", "clickpred-q4"])
def test_search_kernel_compiles(chip, n_inputs, rows):
    lam, n_out = 4, 1
    args = _shapes(
        chip,
        ((lam, N_GATES), jnp.int32), ((lam, N_GATES, 2), jnp.int32),
        ((lam, n_out), jnp.int32), ((n_inputs, n_words(rows)), jnp.uint32),
    )
    _assert_kernel(jax.jit(NATIVE.eval_population).lower(*args).compile())


def _compile_spans(chip, slots):
    args = _shapes(
        chip,
        ((slots, N_GATES), jnp.int32), ((slots, N_GATES, 2), jnp.int32),
        ((slots, SERVE_OUTPUTS), jnp.int32),
        ((SERVE_INPUTS, slots * SPAN), jnp.uint32),
        ((slots,), jnp.int32), ((slots,), jnp.int32),
    )
    fn = jax.jit(lambda *a: NATIVE.eval_population_spans(*a, span_words=SPAN))
    return fn.lower(*args).compile()


@pytest.mark.parametrize("slots", [7, 64])
def test_spans_kernel_compiles(chip, slots):
    _assert_kernel(_compile_spans(chip, slots))


def test_spans_kernel_compiles_at_the_slot_bound(chip):
    """`PlanCompiler` refuses shards above `max_launch_slots`, so the
    bound itself must compile."""
    bound = NATIVE.max_launch_slots(N_GATES, SERVE_OUTPUTS)
    assert bound >= 64
    _assert_kernel(_compile_spans(chip, bound))


def test_span_launch_aot_compiles_64_slots(chip):
    spec = aot.SpanLaunchSpec(
        n_slots=64, k_pad=64, n_nodes=N_GATES, n_outputs=SERVE_OUTPUTS,
        n_inputs=SERVE_INPUTS, span_words=SPAN,
    )
    _assert_kernel(aot.compile_span_launch(NATIVE, spec, device=chip))
