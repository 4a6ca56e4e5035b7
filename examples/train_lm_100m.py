"""End-to-end LM training driver: a ~100M-parameter llama-family model
trained for a few hundred steps on the deterministic synthetic corpus,
with checkpoint/restart supervision.  Loss must drop substantially.

    PYTHONPATH=src python examples/train_lm_100m.py [--steps 300]

On this CPU container a step of the 100M config at batch 2 x 256 tokens
takes a few seconds; pass --tiny for a seconds-long smoke run.
"""
import argparse
import dataclasses
import math

import jax
import numpy as np

from repro import runtime
from repro.launch.train import run_training, small_config
from repro.models import registry


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--checkpoint-dir", default="artifacts/lm100m_ckpt")
    args = ap.parse_args()

    base = registry.load_arch("llama3_2_3b")
    if args.tiny:
        cfg = small_config(base, d_model=128, layers=2, vocab=512)
        batch, seq = 8, 64
    else:
        # ~100M: 14L x d640 (d_ff 2560) + 16k vocab
        cfg = small_config(base, d_model=640, layers=14, vocab=16384)
        batch, seq = 2, 256
    n_params = sum(math.prod(l.shape) for l in jax.tree.leaves(
        jax.eval_shape(lambda: registry.init_params(jax.random.key(0), cfg))))
    print(f"model: {cfg.name} scaled to {n_params/1e6:.1f}M params")

    # data vocab 512 << model vocab: a few hundred steps of synthetic chain
    # are enough to show a decisive loss drop
    out = run_training(cfg, steps_n=args.steps, global_batch=batch,
                       seq_len=seq, lr=1e-3, data_vocab=512,
                       checkpoint_dir=args.checkpoint_dir,
                       checkpoint_every=100, log_every=10)
    losses = out["losses"]
    first = float(np.mean(losses[:10]))
    last = float(np.mean(losses[-10:]))
    print(f"loss: {first:.3f} -> {last:.3f}")
    assert last < first - 0.5, "loss did not drop"
    print("OK")


if __name__ == "__main__":
    runtime.enable_compile_cache()
    main()
