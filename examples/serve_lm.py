"""Batched serving demo: prefill + decode with the Engine (deliverable b).

    PYTHONPATH=src python examples/serve_lm.py [--arch minitron-8b]
"""
import argparse
import sys

sys.path.insert(0, "src")

import jax
import numpy as np

from repro.configs import get_config
from repro.models import lm
from repro.serve.engine import Engine, Request, throughput_report
from repro.utils.compile_cache import use_compile_cache


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="minitron-8b")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--new-tokens", type=int, default=12)
    args = ap.parse_args(argv)

    cfg = get_config(args.arch).smoke()
    params = lm.init_params(jax.random.key(0), cfg)
    engine = Engine(cfg, params, batch_size=4, max_len=96)
    rng = np.random.RandomState(0)
    reqs = [
        Request(uid=i, prompt=rng.randint(0, cfg.vocab, rng.randint(5, 14)),
                max_new_tokens=args.new_tokens,
                temperature=0.0 if i % 2 == 0 else 0.8)
        for i in range(args.requests)
    ]
    rep = throughput_report(engine, reqs)
    for r in reqs:
        print(f"req {r.uid} (T={r.temperature}): "
              f"prompt[:5]={r.prompt[:5].tolist()} → out[:8]={r.output[:8]}")
    print(rep)


if __name__ == "__main__":
    use_compile_cache()
    main()
