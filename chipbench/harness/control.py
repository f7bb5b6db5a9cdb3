"""The control: the reference put in the program's place, one precision
below what the configuration states, and judged by the same check that
decides ``correct``.  It has to come out not correct.

The configuration states float32 fitness.  The control replaces the
fitness each fit reports for its chosen circuit (train and validation)
by the reference's balanced accuracy accumulated in bfloat16; the check
then reads it as ``fitness_gap`` against the reference in float64.
"""
from __future__ import annotations

import ml_dtypes

from harness import search


def bfloat16_fitness(config, clf, x, y, n_classes, val_masks) -> None:
    val, train = search.reference_fitness(config, clf, x, y, n_classes,
                                          val_masks, ml_dtypes.bfloat16)
    rec = clf.records_[search.chosen_encoding(config, clf)]
    rec.val_fitness, rec.train_fitness = val, train
