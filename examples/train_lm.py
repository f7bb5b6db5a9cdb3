"""End-to-end training driver: train a ~100M-parameter dense LM for a few
hundred steps on the synthetic bigram stream, with checkpointing and
auto-resume (deliverable b).

    PYTHONPATH=src python examples/train_lm.py [--steps 300]
"""
import argparse
import sys

sys.path.insert(0, "src")

import dataclasses

import jax
import jax.numpy as jnp

from repro.data.pipeline import TokenStream
from repro.models.common import ModelConfig
from repro.train import checkpoint as ckpt
from repro.train.optimizer import OptConfig
from repro.train.train_step import make_train_state, make_train_step
from repro.utils.compile_cache import use_compile_cache

# ~100M params: 12L × d512 × ff2048, vocab 8192 (wide-enough to be honest,
# small enough for CPU steps)
CFG = ModelConfig(
    name="demo-100m",
    n_layers=12, d_model=512, n_heads=8, n_kv_heads=4, head_dim=64,
    d_ff=2048, vocab=8192, attn_kind="full", rope_kind="rope",
    act="swiglu", dtype="float32", remat="none",
)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_lm")
    args = ap.parse_args(argv)

    print(f"model: {CFG.n_params()/1e6:.1f}M params")
    opt = OptConfig(lr=1e-3)
    stream = TokenStream(vocab=CFG.vocab, batch=args.batch,
                         seq_len=args.seq, seed=0)
    state = make_train_state(jax.random.key(0), CFG, opt)
    start = 0
    if ckpt.latest_step(args.ckpt_dir):
        template = jax.eval_shape(lambda: state)
        state, start = ckpt.restore(args.ckpt_dir, template)
        print(f"resumed at step {start}")
    step = jax.jit(make_train_step(CFG, opt))
    import time

    for i in range(start, args.steps):
        t0 = time.time()
        batch = {k: jnp.asarray(v) for k, v in stream.batch_at(i).items()}
        state, m = step(state, batch)
        if i % 20 == 0 or i == args.steps - 1:
            print(f"step {i:4d}  loss={float(m['loss']):.4f}  "
                  f"({(time.time()-t0)*1000:.0f} ms)", flush=True)
        if (i + 1) % 100 == 0:
            ckpt.save(args.ckpt_dir, i + 1, state)
    ckpt.save(args.ckpt_dir, args.steps, state)
    print("done; checkpoint at", args.ckpt_dir)


if __name__ == "__main__":
    use_compile_cache()
    main()
