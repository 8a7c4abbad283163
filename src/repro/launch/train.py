"""Training launcher.

Example (CPU debug, reduced config):
  PYTHONPATH=src python -m repro.launch.train --arch yi-9b --reduced \
      --steps 100 --batch 8 --seq 64 --ckpt-dir /tmp/ckpt

On a real TPU slice the same entry point runs with --mesh data,model sizes
matching the slice; data/model axis sizes of 1 disable the corresponding
parallelism (CPU default).
"""
from __future__ import annotations

import argparse
import logging

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_IDS, get_config
from repro.data import SyntheticLMDataset
from repro.launch.compile_cache import use_compile_cache
from repro.optim import adamw
from repro.train.train_step import init_train_state, make_train_step
from repro.train.trainer import Trainer


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ARCH_IDS), default="yi-9b")
    ap.add_argument("--reduced", action="store_true",
                    help="reduced same-family config (CPU-sized)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--grad-compression", action="store_true")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    use_compile_cache()

    logging.basicConfig(level=logging.INFO)
    cfg = get_config(args.arch, reduced=args.reduced)
    opt_cfg = adamw.OptConfig(lr=args.lr, warmup_steps=max(args.steps // 20, 5),
                              total_steps=args.steps)
    state = init_train_state(jax.random.PRNGKey(args.seed), cfg, opt_cfg,
                             grad_compression=args.grad_compression)
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(state["params"]))
    print(f"arch={cfg.name} params={n_params / 1e6:.1f}M "
          f"steps={args.steps} batch={args.batch}x{args.seq}")

    step = jax.jit(make_train_step(cfg, opt_cfg,
                                   microbatches=args.microbatches,
                                   grad_compression=args.grad_compression))
    data = SyntheticLMDataset(vocab=cfg.vocab, seq_len=args.seq,
                              global_batch=args.batch, seed=args.seed,
                              frontend=cfg.frontend, d_model=cfg.d_model,
                              n_patches=cfg.n_patches)
    trainer = Trainer(train_step=step, state=state, dataset=data,
                      ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every)
    if trainer.maybe_resume():
        print(f"resumed at step {int(trainer.state['step'])}")
    history = trainer.run(args.steps)
    first = history[0]["loss"] if history else float("nan")
    last = history[-1]["loss"] if history else float("nan")
    print(f"done: loss {first:.4f} -> {last:.4f} over {len(history)} steps; "
          f"median step {np.median([h['step_time_s'] for h in history]):.3f}s")


if __name__ == "__main__":
    main()
