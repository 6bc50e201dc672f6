#!/usr/bin/env python
"""Distributed training in one file: mesh axes, strategies, golden
equivalence, gradient accumulation.

Run: JAX_PLATFORMS=cpu JAX_NUM_CPU_DEVICES=8 python examples/distributed_training.py
"""

import sys

sys.path.insert(0, ".")

from pytorch_distributed_nn_tpu.runtime.device import (
    configure_compile_cache,
)

configure_compile_cache()

import jax

from pytorch_distributed_nn_tpu.config import get_config
from pytorch_distributed_nn_tpu.runtime.mesh import make_mesh
from pytorch_distributed_nn_tpu.train.trainer import Trainer

print(f"devices: {len(jax.devices())}")


def run(tag, **edits):
    cfg = get_config("mlp_mnist", steps=8, log_every=1)
    cfg.data.prefetch = 0
    for key, value in edits.items():
        cfg = cfg.override(**{key: value})
    trainer = Trainer(cfg, mesh=make_mesh(cfg.mesh.resolve(
        len(jax.devices()))))
    trainer.train()
    print(f"{tag:<28} final loss {trainer.losses()[-1] if trainer.history else float('nan'):.4f}")
    return trainer


# 1. Plain data parallelism: batch sharded over all devices, params
#    replicated; XLA derives the gradient all-reduce from the shardings.
run("dp x8")

# 2. The same math, hand-rolled: per-device grads + explicit psum —
#    the reference's pedagogical `average_gradients` path.
run("dp_explicit x8", **{"parallel.strategy": "dp_explicit"})

# 3. ZeRO-3: params + optimizer state sharded over the fsdp axis;
#    XLA inserts allgather-params / reduce-scatter-grads.
run("zero-3 (fsdp=8)", **{"parallel.strategy": "zero",
                          "mesh.fsdp": 8, "mesh.data": 1})

# 4. Gradient accumulation: 4 microbatches per optimizer step, same
#    global-batch math, ~4x lower peak activation memory.
run("dp + grad_accum=4", **{"parallel.grad_accum": 4})

# All four runs optimize the same stream — compare the printed losses:
# dp / dp_explicit / grad_accum agree to float tolerance (golden
# equivalence, the repo's core correctness oracle; see
# tests/test_dp_golden.py and tests/test_grad_accum.py).
