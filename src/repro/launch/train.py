"""Training loop: drafter domain fine-tuning and target pretraining on the
synthetic multi-domain corpus, plus the generic (shardable) train_step used
by the multi-pod dry-run.

Usage (CPU example driver):
  PYTHONPATH=src python -m repro.launch.train --arch tiny --steps 200
"""
from __future__ import annotations

import argparse
from typing import Optional

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.data.synthetic import SyntheticCorpus, token_batches
from repro.models import model as M
from repro.optim.optimizers import Optimizer, apply_updates, get_optimizer


def make_train_step(cfg: ModelConfig, opt: Optimizer, remat: bool = True):
    """Returns train_step(params, opt_state, batch[, frontend]) ->
    (params, opt_state, metrics). jit/pjit-able as is."""

    def train_step(params, opt_state, tokens, frontend=None):
        (loss, parts), grads = jax.value_and_grad(
            M.lm_loss, has_aux=True)(params, cfg, tokens, frontend=frontend,
                                     remat=remat)
        updates, opt_state = opt.update(grads, opt_state, params)
        params = apply_updates(params, updates)
        return params, opt_state, {"loss": loss, **parts}

    return train_step


def train_model(cfg: ModelConfig, corpus: SyntheticCorpus,
                domain: Optional[str], steps: int, batch: int = 8,
                seq: int = 64, lr: float = 3e-3, seed: int = 0,
                optimizer: str = "adamw", params=None, log_every: int = 50,
                verbose: bool = True):
    """Train (or fine-tune, if params given) on one domain or the mixture."""
    key = jax.random.PRNGKey(seed)
    if params is None:
        # master weights and optimizer state stay f32; the forward still
        # computes in cfg.dtype
        params = M.init_params(key, cfg.with_overrides(dtype="float32"))
    opt = get_optimizer(optimizer, lr)
    opt_state = opt.init(params)
    step_fn = jax.jit(make_train_step(cfg, opt, remat=False))

    losses = []
    for i, rows in enumerate(token_batches(corpus, domain, batch, seq, steps)):
        params, opt_state, metrics = step_fn(params, opt_state,
                                             jnp.asarray(rows))
        losses.append(float(metrics["loss"]))
        if verbose and (i % log_every == 0 or i == steps - 1):
            print(f"  [{cfg.name}|{domain or 'mixture'}] step {i:4d} "
                  f"loss {losses[-1]:.4f}")
    return params, losses


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--vocab", type=int, default=256)
    ap.add_argument("--domain", type=str, default=None)
    args = ap.parse_args()

    from repro.configs.drafters import tiny_target
    cfg = tiny_target(args.vocab)
    corpus = SyntheticCorpus(args.vocab)
    params, losses = train_model(cfg, corpus, args.domain, args.steps,
                                 args.batch, args.seq)
    print(f"final loss: {losses[-1]:.4f} (start {losses[0]:.4f})")


if __name__ == "__main__":
    main()
