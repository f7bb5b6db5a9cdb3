"""Plain numpy reference for what the cells compare.

Row by row and bit by bit, with no packing, no kernels and nothing taken
from the program: bucket thresholds for the paper's encodings (§5.2),
feature encoding, the gate walk of a feed-forward circuit (§3.1), the
class decode, the majority vote, and balanced accuracy over a row mask
(§3.3).  ``dtype`` on `balanced_accuracy` is where the control's lower
precision enters; everything else is exact.
"""
from __future__ import annotations

import numpy as np

# two-input gate functions by opcode (the paper's "full" set is the first
# four); each maps two bool arrays to one
GATES = {
    0: lambda a, b: a & b,        # AND
    1: lambda a, b: a | b,        # OR
    2: lambda a, b: ~(a & b),     # NAND
    3: lambda a, b: ~(a | b),     # NOR
    4: lambda a, b: a ^ b,        # XOR
    5: lambda a, b: ~(a ^ b),     # XNOR
    6: lambda a, b: ~a,           # NOT a
    7: lambda a, b: a,            # BUF a
}
FUNCTION_SETS = {"full": (0, 1, 2, 3), "nand": (2,)}


def n_buckets(strategy: str, bits: int) -> int:
    return bits if strategy == "onehot" else 2 ** bits


def thresholds(x: np.ndarray, strategy: str, bits: int) -> np.ndarray:
    """Per-feature bucket edges, float32[F, buckets-1].

    ``quantize``: equal-width buckets between the column's min and max;
    ``quantile``: equal-frequency buckets (linear-interpolated
    quantiles).  Edges never decrease along a row."""
    x = np.asarray(x, np.float64)
    nb = n_buckets(strategy, bits)
    frac = np.arange(1, nb) / nb
    if strategy == "quantize":
        lo, hi = x.min(axis=0), x.max(axis=0)
        width = np.where(hi > lo, hi - lo, 1.0)
        edges = lo[:, None] + width[:, None] * frac[None, :]
    elif strategy == "quantile":
        edges = np.quantile(x, frac, axis=0).T
    else:
        raise ValueError(f"no reference for encoding {strategy!r}")
    return np.maximum.accumulate(edges, axis=1).astype(np.float32)


def code_table(strategy: str, bits: int) -> np.ndarray:
    """uint8[buckets, bits]: bucket k's bits, least significant first."""
    k = np.arange(n_buckets(strategy, bits))
    return ((k[:, None] >> np.arange(bits)[None, :]) & 1).astype(np.uint8)


def encode(x: np.ndarray, edges: np.ndarray, codes: np.ndarray) -> np.ndarray:
    """bool[R, F·bits]: feature f's bucket (edges at or below the value)
    written as its code, input bit ``f·bits + b``."""
    x = np.asarray(x, np.float32)
    bucket = (x[:, :, None] >= edges[None, :, :]).sum(axis=2)
    bits = codes[bucket]                               # (R, F, bits)
    return bits.reshape(x.shape[0], -1).astype(bool)


def gate_walk(opcodes, edge_src, out_src, inputs: np.ndarray) -> np.ndarray:
    """bool[R, O]: evaluate one circuit on every row.  Node ``I + i``
    applies opcode ``i`` to the signals its two edges name."""
    inputs = np.asarray(inputs, bool)
    n_in = inputs.shape[1]
    signals = np.empty((n_in + len(opcodes), inputs.shape[0]), bool)
    signals[:n_in] = inputs.T
    for i, (op, (a, b)) in enumerate(zip(opcodes, edge_src)):
        signals[n_in + i] = GATES[int(op)](signals[a], signals[b])
    return signals[np.asarray(out_src)].T


def class_ids(out_bits: np.ndarray, n_classes: int) -> np.ndarray:
    """Output bit j carries 2**j of the class id; ids past the last class
    read as the last class."""
    weights = 1 << np.arange(out_bits.shape[1], dtype=np.int64)
    return np.minimum((out_bits.astype(np.int64) * weights).sum(axis=1),
                      n_classes - 1)


def vote(member_ids: np.ndarray, n_classes: int) -> np.ndarray:
    """Majority over ensemble members per row; ties go to the lowest id."""
    counts = np.zeros((member_ids.shape[1], n_classes), np.int64)
    for ids in member_ids:
        counts[np.arange(member_ids.shape[1]), ids] += 1
    return counts.argmax(axis=1)


def balanced_accuracy(out_bits, y, mask, n_classes, dtype=np.float64) -> float:
    """Mean recall over the classes present under ``mask``; a row is
    correct when every output bit equals its label's code bit."""
    codes = (y[:, None] >> np.arange(out_bits.shape[1])[None, :]) & 1
    hit = (out_bits == codes.astype(bool)).all(axis=1)
    recalls = []
    for c in range(n_classes):
        rows = mask & (y == c)
        count = int(rows.sum())
        if count:
            recalls.append(dtype(int((hit & rows).sum())) / dtype(count))
    if not recalls:
        return 0.0
    total = dtype(0)
    for r in recalls:
        total = dtype(total + r)
    return float(dtype(total / dtype(len(recalls))))
