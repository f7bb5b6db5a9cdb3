"""Deployment-scenario example (paper §1): a Tiny Classifier as the
*always-on wake-up trigger* for a sleeping SoC running an LM.

The LM (smoke config) embeds short token windows; mean-pooled activations
are treated as tabular features; an evolved ≤300-gate circuit predicts
"interesting vs not" so the big model only wakes on interesting inputs.
This is the point of contact between the paper's technique and the LM
substrate (DESIGN.md §Arch-applicability).

    PYTHONPATH=src python examples/wakeup_gate.py
"""
import sys

sys.path.insert(0, "src")

import jax
import numpy as np

from repro.configs import get_config
from repro.core.api import AutoTinyClassifier
from repro.core.encoding import EncodingConfig
from repro.models import lm
from repro.utils.compile_cache import use_compile_cache


def main():
    cfg = get_config("minitron-8b").smoke()
    params = lm.init_params(jax.random.key(0), cfg)
    rng = np.random.RandomState(0)

    # synthesize "interesting" (low-entropy, repeated-token) vs background
    n, s = 1200, 16
    toks = rng.randint(0, cfg.vocab, (n, s)).astype(np.int32)
    y = rng.randint(0, 2, n)
    rep = rng.randint(0, cfg.vocab, n)
    for i in range(n):
        if y[i]:
            idx = rng.rand(s) < 0.8
            toks[i, idx] = rep[i]

    # features: mean-pooled final hidden state (cheap near-sensor proxy)
    import jax.numpy as jnp

    @jax.jit
    def feats(t):
        logits, _, _ = lm.forward(params, cfg, tokens=t)
        return logits.mean(axis=1)  # (B, vocab) pooled logits

    x = np.asarray(feats(jnp.asarray(toks)))[:, :16]  # 16 feature columns

    split = int(0.8 * n)
    clf = AutoTinyClassifier(
        n_gates=150, max_gens=2000, kappa=300,
        encodings=(EncodingConfig("quantile", 2),), seed=0,
    )
    clf.fit(x[:split], y[:split], 2)
    acc = clf.balanced_score(x[split:], y[split:])
    rep_hw = clf.hardware_report()
    print(f"wake-up gate balanced accuracy: {acc:.3f}")
    print(f"gate cost: {rep_hw.ge_total:.0f} GE, {rep_hw.power_mw:.4f} mW "
          f"@45nm — vs the always-on LM it replaces")
    net = clf.netlist()
    print(f"circuit: {net.n_gates} gates, depth {net.depth()}, "
          f"{len(net.used_inputs)} input bits consumed")


if __name__ == "__main__":
    use_compile_cache()
    main()
