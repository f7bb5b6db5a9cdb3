"""Pallas TPU kernel: bit-packed sea-of-gates circuit evaluation.

This is the compute hot-spot of Auto Tiny Classifiers: every generation
evaluates λ candidate circuits over the full training+validation set
(population × rows × gates boolean ops).  TPU-native design (DESIGN.md §3):

  * dataset rows are bit-packed 32/uint32 word; the word axis is the *lane*
    axis (VPU-friendly, 128-word tiles) — one ALU op evaluates 32 rows;
  * the genome (opcodes / edge list / output taps) drives control flow and
    VMEM addressing, so it rides in SMEM via scalar prefetch;
  * each grid cell materialises the (I+n)-signal node-value table for its
    word block in a VMEM scratch buffer and walks the gates sequentially
    (the circuit is a DAG in topological index order — node i only reads
    signals < I+i, so a single forward sweep suffices);
  * grid = (population, word-blocks): embarrassingly parallel, no reductions.

VMEM footprint per cell: (I + n + O) × block_words × 4 B (+ the x block).
For the paper's regime (I ≲ 6.5k bits, n = 300) a 512-word block is ≤ ~14 MB
worst-case and ~0.8 MB for typical datasets; `ops.py` shrinks the block when
the table would overflow VMEM.

Validated in interpret mode against `ref.py` (tests/test_kernels.py sweeps
shapes, function sets and dtypes).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import gates

LANE = 128  # TPU lane count; word blocks are multiples of this


def _gate_select(op, a, b):
    """Opcode-indexed gate on uint32 words (VPU select chain)."""
    r = jnp.where(op == gates.AND, a & b, jnp.uint32(0))
    r = jnp.where(op == gates.OR, a | b, r)
    r = jnp.where(op == gates.NAND, ~(a & b), r)
    r = jnp.where(op == gates.NOR, ~(a | b), r)
    r = jnp.where(op == gates.XOR, a ^ b, r)
    r = jnp.where(op == gates.XNOR, ~(a ^ b), r)
    r = jnp.where(op == gates.NOT_A, ~a, r)
    r = jnp.where(op == gates.BUF_A, a, r)
    return r


def _walk_gates(p, n_in, n_nodes, n_out, opcodes_ref, edge_src_ref,
                out_src_ref, vals_ref, o_ref):
    """Forward sweep of circuit p over the seeded node-value table.

    The genome operands are flat i32 vectors in SMEM: gate i of circuit p
    is at ``p·n + i`` and its operands at ``(p·n + i)·2 + k``.  A 1-D
    layout keeps their SMEM size at ``P·n`` words; a ``[P, n, 2]`` array
    would be tiled to ``(⌈n/8⌉·8, 128)`` per circuit (155,648 B at
    n = 300), which caps a launch at 6 circuits."""
    base = p * n_nodes

    def body(i, _):
        g = base + i
        a = vals_ref[edge_src_ref[2 * g], :]
        b = vals_ref[edge_src_ref[2 * g + 1], :]
        vals_ref[n_in + i, :] = _gate_select(opcodes_ref[g], a, b)
        return 0

    jax.lax.fori_loop(0, n_nodes, body, 0)

    for j in range(n_out):  # O is small and static — unrolled taps
        o_ref[0, j, :] = vals_ref[out_src_ref[p * n_out + j], :]


def _kernel(
    # scalar-prefetch (SMEM), flattened:
    opcodes_ref,   # i32[P·n]
    edge_src_ref,  # i32[P·n·2]
    out_src_ref,   # i32[P·O]
    # VMEM blocks:
    x_ref,         # u32[I, BW]
    o_ref,         # u32[1, O, BW]
    # scratch:
    vals_ref,      # u32[I+n, BW]
    *,
    n_nodes: int,
    n_out: int,
):
    n_in = x_ref.shape[0]
    # Seed the node-value table with the input bits.
    vals_ref[:n_in, :] = x_ref[...]
    _walk_gates(pl.program_id(0), n_in, n_nodes, n_out, opcodes_ref,
                edge_src_ref, out_src_ref, vals_ref, o_ref)


def _spans_kernel(
    # scalar-prefetch (SMEM), flattened:
    opcodes_ref,   # i32[P·n]
    edge_src_ref,  # i32[P·n·2]
    out_src_ref,   # i32[P·O]
    block_off_ref,  # i32[P]  word-block offset of circuit p's span
    in_width_ref,   # i32[P]  live input rows of circuit p (rest masked to 0)
    # VMEM blocks:
    x_ref,         # u32[I_max, BW]  (block taken at block_off[p] + wb)
    o_ref,         # u32[1, O, BW]
    # scratch:
    vals_ref,      # u32[I_max+n, BW]
    *,
    n_nodes: int,
    n_out: int,
):
    """Span variant of `_kernel` for multi-tenant serving.

    Each circuit p owns a contiguous run of word blocks (its tenant's
    micro-batch) starting at ``block_off[p]`` — the x BlockSpec index_map
    reads the prefetched offsets, so one launch walks P disjoint spans
    instead of P × W full sweeps.  Input rows at or above ``in_width[p]``
    are zero-masked when seeding the node-value table: a tenant narrower
    than I_max can never observe another tenant's bits, even through a
    corrupted genome whose edges index past its own inputs.
    """
    p = pl.program_id(0)
    n_in = x_ref.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, x_ref.shape, 0)
    vals_ref[:n_in, :] = jnp.where(
        row < in_width_ref[p], x_ref[...], jnp.uint32(0)
    )
    _walk_gates(p, n_in, n_nodes, n_out, opcodes_ref, edge_src_ref,
                out_src_ref, vals_ref, o_ref)


@functools.partial(
    jax.jit, static_argnames=("span_words", "block_words", "interpret")
)
def eval_population_spans_kernel(
    opcodes: jax.Array,    # i32[P, n]
    edge_src: jax.Array,   # i32[P, n, 2]
    out_src: jax.Array,    # i32[P, O]
    x_words: jax.Array,    # u32[I_max, W_total]
    word_off: jax.Array,   # i32[P]  word offset of circuit p's span
    in_width: jax.Array,   # i32[P]  live input rows per circuit
    *,
    span_words: int,       # words each circuit evaluates (multiple of block)
    block_words: int = 512,
    interpret: bool = False,
) -> jax.Array:            # u32[P, O, span_words]
    pop, n = opcodes.shape
    n_in, w = x_words.shape
    n_out = out_src.shape[1]
    assert span_words % block_words == 0, (span_words, block_words)
    assert w % block_words == 0, (w, block_words)
    grid = (pop, span_words // block_words)
    block_off = word_off.astype(jnp.int32) // block_words

    return pl.pallas_call(
        functools.partial(_spans_kernel, n_nodes=n, n_out=n_out),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=grid,
            in_specs=[
                pl.BlockSpec(
                    (n_in, block_words),
                    lambda p, wb, opc, es, osrc, boff, iw: (0, boff[p] + wb),
                ),
            ],
            out_specs=pl.BlockSpec(
                (1, n_out, block_words), lambda p, wb, *_: (p, 0, wb)
            ),
            scratch_shapes=[pltpu.VMEM((n_in + n, block_words), jnp.uint32)],
        ),
        out_shape=jax.ShapeDtypeStruct((pop, n_out, span_words), jnp.uint32),
        interpret=interpret,
    )(opcodes.reshape(-1), edge_src.reshape(-1), out_src.reshape(-1),
      block_off, in_width.astype(jnp.int32), x_words)


@functools.partial(
    jax.jit, static_argnames=("block_words", "interpret")
)
def eval_population_kernel(
    opcodes: jax.Array,   # i32[P, n]
    edge_src: jax.Array,  # i32[P, n, 2]
    out_src: jax.Array,   # i32[P, O]
    x_words: jax.Array,   # u32[I, W]  (W must be a multiple of block_words)
    *,
    block_words: int = 512,
    interpret: bool = False,
) -> jax.Array:           # u32[P, O, W]
    pop, n = opcodes.shape
    n_in, w = x_words.shape
    n_out = out_src.shape[1]
    assert w % block_words == 0, (w, block_words)
    grid = (pop, w // block_words)

    return pl.pallas_call(
        functools.partial(_kernel, n_nodes=n, n_out=n_out),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=grid,
            in_specs=[
                pl.BlockSpec((n_in, block_words), lambda p, wb, *_: (0, wb)),
            ],
            out_specs=pl.BlockSpec(
                (1, n_out, block_words), lambda p, wb, *_: (p, 0, wb)
            ),
            scratch_shapes=[pltpu.VMEM((n_in + n, block_words), jnp.uint32)],
        ),
        out_shape=jax.ShapeDtypeStruct((pop, n_out, w), jnp.uint32),
        interpret=interpret,
    )(opcodes.reshape(-1), edge_src.reshape(-1), out_src.reshape(-1),
      x_words)
