"""The benchmark's own copy of the paper's Table-1 shapes and of the
synthetic tabular generator.

The repository has no network, so each Table-1 dataset is a seeded
synthetic table of the published shape: rows, features and classes as in
Table 1 (arXiv 2303.00031), a third of the columns made categorical, and
labels from a random axis-aligned decision tree over the informative
columns plus label noise.  The difficulty knobs come from a hash of the
dataset name, as in the program's generator, so a shape always gives the
same kind of table; ``seed`` picks which table of that kind.

Kept here so that a change to the program's generator cannot move the
yardstick.
"""
from __future__ import annotations

import hashlib

import numpy as np

# name: (classes, rows, features) — paper Table 1
TABLE1: dict[str, tuple[int, int, int]] = {
    "vehicle": (2, 846, 22),
    "cars": (3, 406, 8),
    "user-model-data": (4, 403, 5),
    "kc1": (2, 145, 95),
    "phoneme": (2, 5404, 6),
    "skin-seg": (2, 245057, 4),
    "ecoli-data": (4, 336, 8),
    "iris": (3, 150, 7),
    "blood": (2, 748, 4),
    "higgs": (2, 98050, 29),
    "wifi-localization": (4, 2000, 7),
    "nomao": (2, 34465, 119),
    "olinda-outlier": (4, 75, 3),
    "australian": (2, 690, 15),
    "segment": (2, 2310, 20),
    "led": (10, 500, 7),
    "numerai": (2, 96320, 22),
    "miniboone": (2, 130064, 51),
    "wall-robot": (4, 5456, 3),
    "jasmine": (2, 2984, 145),
    "yeast": (10, 1484, 8),
    "christine": (2, 5418, 1637),
    "sylvine": (2, 5124, 21),
    "seismic-bumps": (3, 210, 8),
    "ccfraud": (2, 284807, 31),
    "clickpred": (2, 1496391, 10),
    "vowel": (2, 528, 21),
    "nursery": (5, 12958, 9),
    "spectf-data": (2, 267, 45),
    "teaching-assist": (3, 151, 7),
    "wisconsin": (2, 194, 33),
    "sonar": (2, 208, 61),
    "ionosphere": (2, 351, 35),
}


def name_seed(name: str) -> int:
    return int.from_bytes(hashlib.sha256(name.encode()).digest()[:4], "little")


def _tree_labels(rng, x: np.ndarray, n_classes: int, depth: int) -> np.ndarray:
    """Label rows by a random axis-aligned decision tree over ``x``."""
    y = np.zeros(x.shape[0], dtype=np.int64)
    stack = [(np.arange(x.shape[0]), 0)]
    leaf = 0
    while stack:
        idx, d = stack.pop()
        if d == depth or len(idx) == 0:
            if len(idx):
                y[idx] = leaf % n_classes
                leaf += 1
            continue
        f = rng.randint(x.shape[1])
        vals = x[idx, f]
        thr = np.quantile(vals, rng.uniform(0.25, 0.75)) if len(idx) > 4 else 0.0
        stack.append((idx[vals <= thr], d + 1))
        stack.append((idx[vals > thr], d + 1))
    return y


def table(name: str, seed: int):
    """``(x float32[R, F], y int64[R], n_classes)`` for one Table-1 shape."""
    n_classes, n_rows, n_feats = TABLE1[name]
    knob = name_seed(name)
    rng = np.random.RandomState((knob + seed) % (2 ** 32))
    noise = 0.03 + (knob % 97) / 97 * 0.22
    frac_informative = 0.4 + (knob % 53) / 53 * 0.5
    n_inf = max(2, int(n_feats * frac_informative)) if n_feats > 2 else n_feats
    depth = int(np.clip(2 + (knob % 5), 2, 6))

    x = rng.randn(n_rows, n_feats).astype(np.float32)
    for j in range(n_feats // 3):
        k = 2 + (knob + j) % 6
        col = x[:, j]
        x[:, j] = np.floor((col - col.min()) / (np.ptp(col) + 1e-6) * k)
    y = _tree_labels(rng, x[:, :n_inf], n_classes, depth)
    flip = rng.rand(n_rows) < noise
    y[flip] = rng.randint(0, n_classes, flip.sum())
    return x, y, n_classes
