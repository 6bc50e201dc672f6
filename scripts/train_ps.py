#!/usr/bin/env python
"""Async parameter-server training entrypoint — the reference's PS
trainer script (SURVEY.md §2a "Parameter-server / async trainer": rank 0
holds params, workers send grads / recv params). Process-level async —
see pytorch_distributed_nn_tpu.parallel.ps for the design.

Usage:
    python scripts/train_ps.py --preset mlp_mnist --workers 2 --steps 60
"""

from __future__ import annotations

import argparse
import logging
import sys

sys.path.insert(0, ".")

from pytorch_distributed_nn_tpu.runtime.device import (
    configure_compile_cache,
)

configure_compile_cache()

import jax
import jax.numpy as jnp

from pytorch_distributed_nn_tpu.config import get_config
from pytorch_distributed_nn_tpu.data import get_dataset
from pytorch_distributed_nn_tpu.models import get_model
from pytorch_distributed_nn_tpu.parallel import ps
from pytorch_distributed_nn_tpu.train.losses import get_loss_fn
from pytorch_distributed_nn_tpu.train.optim import make_optimizer


def main(argv: list[str]) -> int:
    logging.basicConfig(level=logging.INFO)
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="mlp_mnist")
    ap.add_argument("--workers", type=int, default=2)
    ap.add_argument("--steps", type=int, default=60,
                    help="total gradient pushes across workers")
    ap.add_argument("--max-staleness", type=int, default=8)
    args = ap.parse_args(argv)

    cfg = get_config(args.preset)
    dataset = get_dataset(cfg.data.dataset, seed=cfg.seed,
                          batch_size=cfg.data.batch_size,
                          seq_len=cfg.data.seq_len,
                          vocab_size=cfg.data.vocab_size,
                          path=cfg.data.path,
                          token_dtype=cfg.data.token_dtype,
                          sample=cfg.data.sample,
                          holdout_frac=cfg.data.holdout_frac,
                          image_size=cfg.data.image_size)
    model = get_model(cfg.model)
    loss_fn = get_loss_fn(cfg.data.dataset)
    x0, _ = dataset.batch(0)
    params = model.init(jax.random.key(cfg.seed), jnp.asarray(x0[:1]),
                        train=False)["params"]
    tx = make_optimizer(cfg.optim, total_steps=args.steps)

    def loss_of(params, x, y):
        logits = model.apply({"params": params}, x, train=False)
        return loss_fn(logits, y)

    grad_fn = jax.jit(jax.grad(loss_of))

    per_worker = args.steps // args.workers
    worker_batches = [
        [tuple(map(jnp.asarray, dataset.batch(w * per_worker + i)))
         for i in range(per_worker)]
        for w in range(args.workers)
    ]
    final_params, applied = ps.run_ps_local(params, tx, grad_fn,
                                            worker_batches)
    x, y = map(jnp.asarray, dataset.batch(10_000))
    final_loss = float(loss_of(final_params, x, y))
    print(f"ps: applied {applied} grads from {args.workers} workers, "
          f"held-out loss {final_loss:.4f}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
