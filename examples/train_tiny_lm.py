"""Train a ~tiny LM of one assigned architecture for a few hundred steps.

Demonstrates the training substrate end to end: synthetic bigram data,
sharded AdamW, grad accumulation, checkpointing, loss decreasing.

    PYTHONPATH=src python examples/train_tiny_lm.py --arch granite-moe-1b-a400m
"""
import argparse
import tempfile

import jax
import jax.numpy as jnp

from repro.compile_cache import enable_compile_cache
from repro.config import ParallelConfig, get_arch
from repro.data import lm_batches
from repro.models import transformer as T
from repro.train import (AdamWConfig, checkpoint, init_opt_state,
                         make_train_step)


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="starcoder2-3b")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    args = ap.parse_args()

    cfg = get_arch(args.arch).reduced()
    print(f"arch={args.arch} (reduced: {cfg.num_layers}L d={cfg.d_model} "
          f"vocab={cfg.vocab_size})")
    params = T.init_params(cfg, jax.random.key(0))
    opt_cfg = AdamWConfig(lr=2e-3, warmup_steps=20, total_steps=args.steps)
    state = init_opt_state(params)
    step = jax.jit(make_train_step(cfg, ParallelConfig(grad_accum=2), opt_cfg),
                   donate_argnums=(0, 1))

    ckpt_dir = tempfile.mkdtemp(prefix="eda-tiny-")
    for i, batch in enumerate(lm_batches(args.batch, args.seq,
                                         cfg.vocab_size, steps=args.steps)):
        batch = {k: jnp.asarray(v) for k, v in batch.items()}
        params, state, m = step(params, state, batch)
        if i % 25 == 0 or i == args.steps - 1:
            print(f"step {i:4d} loss {float(m['loss']):.4f} "
                  f"lr {float(m['lr']):.2e}")
        if (i + 1) % 100 == 0:
            checkpoint.save(ckpt_dir, i + 1, {"params": params},
                            blocking=False)
    print(f"checkpoints: {checkpoint.all_steps(ckpt_dir)} in {ckpt_dir}")


if __name__ == "__main__":
    main()
