"""Island-parallel evolution across a device mesh (the paper's technique at
scale): islands on the `model` axis, dataset rows sharded over `data`, exact
psum fitness, ring migration.

Runs on 8 fake host devices:
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python examples/evolve_distributed.py
"""
import os
import sys

if "XLA_FLAGS" not in os.environ:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
sys.path.insert(0, "src")

import jax
import numpy as np

from repro.core import encoding as E
from repro.core import gates
from repro.core.evolve import EvolveConfig
from repro.core.genome import CircuitSpec
from repro.core.islands import (
    IslandConfig, best_island, evolve_islands, pad_words_for,
)
from repro.data import load_dataset, train_test_split
from repro.launch.mesh import make_host_mesh
from repro.utils.compile_cache import use_compile_cache


def main():
    mesh = make_host_mesh(data=2, model=4)
    print(f"mesh: {dict(zip(mesh.axis_names, mesh.devices.shape))} "
          f"({mesh.size} devices) → 4 islands × 2-way sharded fitness")

    ds = load_dataset("phoneme")
    tr, te = train_test_split(ds, 0.2, seed=0)
    enc = E.fit_encoder(tr.x, E.EncodingConfig("quantile", 2))
    bits = E.encode(enc, tr.x)
    data = E.pack_dataset(bits, tr.y, ds.n_classes,
                          pad_words_to=pad_words_for(mesh, ("data",)))
    w = data.x_words.shape[1]
    mtr, mva = E.split_masks(tr.x.shape[0], w, 0.5, seed=1)

    spec = CircuitSpec(bits.shape[1], 300, 1, gates.FULL_FS)
    cfg = EvolveConfig(lam=4, kappa=300, max_gens=2500)
    icfg = IslandConfig(migrate_every=32, island_axis="model",
                        data_axes=("data",))
    keys = jax.random.split(jax.random.key(0), 4)
    states = evolve_islands(keys, spec, cfg, icfg, data, mtr, mva, mesh)
    print("per-island val fitness:",
          np.asarray(states.best_val).round(3).tolist())
    best = best_island(states)

    # evaluate the winner on the held-out test set
    from repro.core import fitness as F
    from repro.core.genome import opcodes
    from repro.kernels import ops

    te_bits = E.encode(enc, te.x)
    te_words = E.pack_bits_rows(te_bits, E.n_words(te.x.shape[0]))
    out = ops.eval_circuit(
        opcodes(best.best, spec), best.best.edge_src, best.best.out_src,
        te_words,
    )
    pred = np.minimum(
        np.asarray(F.predicted_class_ids(out, te.x.shape[0])),
        ds.n_classes - 1,
    )
    ba = F.balanced_accuracy_rows(pred, te.y, np.ones_like(te.y, bool),
                                  ds.n_classes)
    print(f"global best island: val={float(best.best_val):.3f} "
          f"test balanced acc={ba:.3f}")


if __name__ == "__main__":
    use_compile_cache()
    main()
