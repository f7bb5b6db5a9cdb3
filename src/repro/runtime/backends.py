"""The built-in execution backends: jnp oracle, Pallas TPU, GPU stub.

The Pallas backend owns the block/VMEM policy that used to live in
`kernels/ops.py` (`pick_block_words`, the word-axis padding, the
interpret-on-CPU auto-detection) and the SMEM bound on circuits per
launch — backend policy belongs to the backend, not to a module-level
dispatcher.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import circuit_eval, ref
from repro.runtime import aot
from repro.runtime.base import (
    BackendCapabilities,
    BackendCapabilityError,
    EvalBackend,
)

VMEM_BUDGET_BYTES = 12 * 1024 * 1024  # leave headroom out of ~16 MB/core
# Scalar memory per TensorCore: the kernels' scalar-prefetched operands
# (flattened genomes, span offsets, input widths) must fit in it.  The
# reserve covers the compiler's padding of those operands and its own SMEM
# use: compiled for v5e, the spans kernel at 300 gates and 3 outputs fits
# 280 circuits and overflows at 287, and both kernels compile at the bound
# this reserve gives (285).
SMEM_BYTES = 1024 * 1024
SMEM_RESERVE_BYTES = 16 * 1024


@functools.partial(jax.jit, static_argnames=("span_words",))
def _spans_ref(opcodes, edge_src, out_src, x_words, word_off, in_width,
               span_words):
    # trace-time side effect only: counts actual (re)traces of the eager
    # serving launch so cold-boot tests can assert "zero tracing"
    aot.note_trace(f"ref.spans/s{span_words}")
    return ref.eval_population_spans_packed(
        opcodes, edge_src, out_src, x_words, word_off, in_width,
        span_words=span_words,
    )


class RefBackend(EvalBackend):
    """Pure-jnp oracle (`kernels/ref.py`): the bit-exactness reference every
    other backend is validated against.  Runs on any jax device."""

    name = "ref"

    def capabilities(self) -> BackendCapabilities:
        return BackendCapabilities(
            name=self.name,
            device_kinds=("cpu", "tpu", "gpu"),
            supports_spans=True,
            word_alignment=1,
            span_offset_contract="none",
        )

    def eval_population(self, opcodes, edge_src, out_src, x_words):
        # Not jitted here: the evolution loop traces this inside its own jit;
        # host callers (tests) get eager oracle semantics.
        return ref.eval_population_packed(opcodes, edge_src, out_src, x_words)

    def eval_population_spans(
        self, opcodes, edge_src, out_src, x_words, word_off, in_width,
        *, span_words: int,
    ):
        return _spans_ref(
            opcodes, edge_src, out_src, x_words,
            word_off.astype(jnp.int32), in_width.astype(jnp.int32),
            span_words,
        )


class PallasBackend(EvalBackend):
    """Pallas TPU kernels (`kernels/circuit_eval.py`).

    ``interpret=None`` auto-detects: interpret mode when JAX's platform is
    the CPU (bit-exact, slow — plumbing validation on CPU hosts), native
    Mosaic kernels on any other platform, so an accelerator that fails to
    come up fails loudly instead of serving from the interpreter.  Pass
    ``interpret=True/False`` to force either mode.
    """

    name = "pallas"

    def __init__(self, interpret: bool | None = None):
        self.interpret = interpret

    def _interpret(self) -> bool:
        if self.interpret is None:
            return jax.default_backend() == "cpu"
        return self.interpret

    def capabilities(self) -> BackendCapabilities:
        return BackendCapabilities(
            name=self.name,
            device_kinds=("tpu",) if not self._interpret() else ("cpu", "tpu"),
            supports_spans=True,
            word_alignment=circuit_eval.LANE,
            span_offset_contract="word_off entries must be multiples of span_words",
            supports_aot=True,
            aot_format=aot.AOT_FORMAT,
            aot_format_version=aot.AOT_FORMAT_VERSION,
        )

    def span_alignment(self, requested: int | None = None) -> int:
        """Native kernels block the word axis in whole lanes, so an
        explicit request is rounded up to a lane multiple there; the
        interpreter honours it as given."""
        align = super().span_alignment(requested)
        if self._interpret():
            return align
        lane = circuit_eval.LANE
        return -(-align // lane) * lane

    def max_launch_slots(self, n_nodes: int, n_outputs: int) -> int:
        """Most circuits one launch can hold: 4 bytes per opcode, two per
        gate's operands, one per output tap, plus a span offset and an
        input width, all within `SMEM_BYTES` less the reserve.  At 300
        gates and 3 outputs that is 285 circuits."""
        per_slot = 4 * (3 * int(n_nodes) + int(n_outputs) + 2)
        return (SMEM_BYTES - SMEM_RESERVE_BYTES) // per_slot

    def _check_slots(self, opcodes, out_src) -> None:
        pop, n = opcodes.shape
        limit = self.max_launch_slots(n, out_src.shape[1])
        if pop > limit:
            raise BackendCapabilityError(
                f"{pop} circuits of {n} gates in one Pallas launch exceed "
                f"its SMEM bound of {limit}; split the population over "
                "several launches (more plan shards)"
            )

    def pick_block_words(
        self, n_signals: int, w: int, lane: int = circuit_eval.LANE
    ) -> int:
        """Largest lane-multiple block whose (I+n)-row uint32 table fits
        the VMEM budget."""
        max_words = max(VMEM_BUDGET_BYTES // (4 * max(n_signals, 1)), lane)
        block = (max_words // lane) * lane
        block = min(block, 4 * lane)  # cap: 512 words = 16k rows per cell
        # no point exceeding the (padded) word count itself
        w_padded = ((w + lane - 1) // lane) * lane
        return min(block, w_padded)

    def eval_population(self, opcodes, edge_src, out_src, x_words):
        self._check_slots(opcodes, out_src)
        n_in, w = x_words.shape
        n = opcodes.shape[1]
        block = self.pick_block_words(n_in + n, w)
        w_pad = ((w + block - 1) // block) * block
        if w_pad != w:
            x_words = jnp.pad(x_words, ((0, 0), (0, w_pad - w)))
        out = circuit_eval.eval_population_kernel(
            opcodes.astype(jnp.int32),
            edge_src.astype(jnp.int32),
            out_src.astype(jnp.int32),
            x_words.astype(jnp.uint32),
            block_words=block,
            interpret=self._interpret(),
        )
        return out[..., :w]

    def eval_population_spans(
        self, opcodes, edge_src, out_src, x_words, word_off, in_width,
        *, span_words: int,
    ):
        self._check_slots(opcodes, out_src)
        n_in, w = x_words.shape
        n = opcodes.shape[1]
        block = self.pick_block_words(n_in + n, span_words)
        if span_words % block or w % block:
            block = span_words  # fall back to one block per span
        # block | span_words holds here, so offsets that honour the documented
        # multiple-of-span contract are block-aligned; the kernel's integer
        # division would silently evaluate the wrong span otherwise.
        if not isinstance(word_off, jax.core.Tracer):
            off = np.asarray(word_off)
            if off.size and (off % block).any():
                raise ValueError(
                    f"word_off entries must be multiples of span_words"
                    f"={span_words} (kernel block {block}); got {off.tolist()}"
                )
        return circuit_eval.eval_population_spans_kernel(
            opcodes.astype(jnp.int32),
            edge_src.astype(jnp.int32),
            out_src.astype(jnp.int32),
            x_words.astype(jnp.uint32),
            word_off.astype(jnp.int32),
            in_width.astype(jnp.int32),
            span_words=span_words,
            block_words=block,
            interpret=self._interpret(),
        )


class PallasGpuBackend(EvalBackend):
    """Reserved registry slot for the ROADMAP GPU lowering (Triton or
    Pallas-on-GPU of `circuit_eval.py`).  Registered so deployment configs
    can name it today; every eval entry point raises a clear capability
    error until the lowering lands."""

    name = "pallas-gpu"

    _MSG = (
        "backend 'pallas-gpu' is a reserved slot: the GPU lowering of the "
        "circuit-eval kernels is not implemented yet (see ROADMAP.md). "
        "Use backend='ref' (any device) or backend='pallas' (TPU native, "
        "interpret on CPU)."
    )

    def capabilities(self) -> BackendCapabilities:
        return BackendCapabilities(
            name=self.name,
            device_kinds=("gpu",),
            supports_spans=True,
            word_alignment=circuit_eval.LANE,
            span_offset_contract="word_off entries must be multiples of span_words",
            implemented=False,
        )

    def eval_population(self, opcodes, edge_src, out_src, x_words):
        raise BackendCapabilityError(self._MSG)

    def eval_population_spans(
        self, opcodes, edge_src, out_src, x_words, word_off, in_width,
        *, span_words: int,
    ):
        raise BackendCapabilityError(self._MSG)
