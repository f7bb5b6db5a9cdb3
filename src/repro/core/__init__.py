"""Auto Tiny Classifiers — the paper's core contribution in JAX.

Modules:
  * genome.py   — CircuitSpec / Genome / init_genome
  * encoding.py — EncodingConfig / fit_encoder / pack_dataset
  * evolve.py   — EvolveConfig / evolve / evolve_packed
  * api.py      — AutoTinyClassifier (sklearn-style end-to-end flow)

The package itself imports nothing: `repro.kernels` depends on
`repro.core.gates`, and `evolve` depends on `repro.runtime`, which
imports the kernels — so eager re-exports here would close an import
cycle for any process whose first import is a kernel module.
"""
