#!/usr/bin/env python
"""Round-2 features in one file: 1F1B pipeline schedule (with dropout
and in-training eval), pipeline x tensor parallelism, and training from
the reference's real on-disk dataset formats (MNIST idx files with a
true test split).

Run: JAX_PLATFORMS=cpu JAX_NUM_CPU_DEVICES=8 python examples/pipeline_and_real_data.py
"""

import sys
import tempfile
from pathlib import Path

sys.path.insert(0, ".")

from pytorch_distributed_nn_tpu.runtime.device import (
    configure_compile_cache,
)

configure_compile_cache()

import gzip
import struct

import jax
import numpy as np

from pytorch_distributed_nn_tpu.config import get_config
from pytorch_distributed_nn_tpu.runtime.mesh import MeshSpec, make_mesh
from pytorch_distributed_nn_tpu.train.trainer import Trainer

print(f"devices: {len(jax.devices())}")

# ---------------------------------------------------------------------
# 1) Pipeline schedules: GPipe vs 1F1B — same math, different memory.
#    1F1B runs a manual backward on the PipeDream-flush timetable, so
#    in-flight activations are bounded by stage depth (not microbatch
#    count) and dropout works (deterministic per-microbatch masks,
#    recomputed identically in the backward).
# ---------------------------------------------------------------------

def pipeline_cfg(schedule, *, dropout=0.0, tensor=1):
    cfg = get_config("transformer_lm_pp", steps=6, log_every=2)
    cfg.data.prefetch = 0
    cfg.data.batch_size = 16
    cfg.data.seq_len = 16
    cfg.data.vocab_size = 101
    cfg.model.compute_dtype = "float32"
    cfg.model.remat = False
    cfg.model.extra = dict(num_layers=4, d_model=32, num_heads=2,
                           mlp_dim=64, vocab_size=101, max_len=64,
                           dropout=dropout)
    cfg.parallel.microbatches = 4
    cfg.parallel.pipeline_schedule = schedule
    cfg.mesh = MeshSpec(pipe=2, tensor=tensor,
                        data=8 // (2 * tensor))
    return cfg


for schedule in ("gpipe", "1f1b"):
    cfg = pipeline_cfg(schedule)
    trainer = Trainer(cfg, mesh=make_mesh(cfg.mesh.resolve(8)))
    losses = [r.loss for r in trainer.train()]
    print(f"{schedule:6s}: loss {losses[0]:.4f} -> {losses[-1]:.4f}")

# dropout + in-training eval, 1F1B only (gpipe rejects dropout)
cfg = pipeline_cfg("1f1b", dropout=0.1)
trainer = Trainer(cfg, mesh=make_mesh(cfg.mesh.resolve(8)))
trainer.train()
rec = trainer.evaluate(num_batches=2)  # forward-only pipelined eval
print(f"1f1b + dropout: eval loss {rec.loss:.4f} acc {rec.accuracy:.3f}")

# pipeline x tensor parallelism: Megatron TP inside each stage (the
# `tensor` axis stays auto in the pipeline shard_map)
cfg = pipeline_cfg("1f1b", tensor=2)
trainer = Trainer(cfg, mesh=make_mesh(cfg.mesh.resolve(8)))
losses = [r.loss for r in trainer.train()]
print(f"pipe x tp: loss {losses[0]:.4f} -> {losses[-1]:.4f}")

# ---------------------------------------------------------------------
# 2) Real on-disk data: write a tiny MNIST in the actual idx format
#    (as torchvision downloads it), then train from it. The t10k pair
#    automatically becomes the held-out eval stream.
# ---------------------------------------------------------------------

def write_idx(path, arr):
    code = {np.dtype(np.uint8): 0x08}[arr.dtype]
    head = struct.pack(">HBB", 0, code, arr.ndim)
    head += struct.pack(f">{arr.ndim}I", *arr.shape)
    with gzip.open(str(path) + ".gz", "wb") as f:
        f.write(head + arr.tobytes())


tmp = Path(tempfile.mkdtemp())
rng = np.random.default_rng(0)
for stem, n in (("train", 512), ("t10k", 128)):
    y = (np.arange(n) % 10).astype(np.uint8)
    x = rng.integers(0, 256, (n, 28, 28)).astype(np.uint8)
    for i, yi in enumerate(y):  # learnable class stripes
        x[i, yi * 2:yi * 2 + 3, :] = 255
    write_idx(tmp / f"{stem}-images-idx3-ubyte", x)
    write_idx(tmp / f"{stem}-labels-idx1-ubyte", y)

cfg = get_config("mlp_mnist", steps=30, log_every=10)
cfg.data.dataset = "mnist_idx"
cfg.data.path = str(tmp)
cfg.data.batch_size = 32
cfg.data.prefetch = 0
cfg.optim.lr = 0.1
trainer = Trainer(cfg)
losses = [r.loss for r in trainer.train()]
rec = trainer.evaluate(num_batches=2)  # drawn from the REAL t10k split
print(f"mnist_idx: train {losses[0]:.3f} -> {losses[-1]:.3f}, "
      f"t10k eval loss {rec.loss:.3f} acc {rec.accuracy:.3f}")
print("done.")
