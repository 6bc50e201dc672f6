#!/usr/bin/env python
"""Long-context training: what makes 32k+ tokens fit and go fast.

Three pieces (scaled to toy dims here so it runs anywhere; the real
config is the `llama3_longcontext` preset — 32k tokens on one v5e chip
at ~13.8k tokens/s):

1. flash attention — Pallas kernels stream K/V through VMEM, so the
   (T, T) score matrix never exists in HBM (forward AND backward; on
   CPU the wrapper falls back to an exact jnp reference);
2. chunked LM cross-entropy — at long T the (B, T, vocab) logits are
   the real memory limiter, so the head projection + softmax run per
   T-chunk (`xent_chunk`) and full logits never materialize;
3. ring attention — past one chip, shard the SEQUENCE over the `seq`
   mesh axis: KV shards rotate around the ICI ring (`ppermute`) while
   an online softmax accumulates. `attn_impl='ring'` + `mesh.seq` is
   the whole integration.

Run: JAX_PLATFORMS=cpu JAX_NUM_CPU_DEVICES=8 python examples/long_context.py
"""

import sys

sys.path.insert(0, ".")

from pytorch_distributed_nn_tpu.runtime.device import (
    configure_compile_cache,
)

configure_compile_cache()

import jax

from pytorch_distributed_nn_tpu.config import get_config
from pytorch_distributed_nn_tpu.runtime.mesh import MeshSpec, make_mesh
from pytorch_distributed_nn_tpu.train.trainer import Trainer

TINY = dict(num_layers=2, d_model=64, num_heads=4, num_kv_heads=2,
            mlp_dim=128, vocab_size=97)


def run(tag, mesh_spec, **edits):
    cfg = get_config("llama3_longcontext", steps=4, log_every=1)
    cfg.data.prefetch = 0
    cfg.data.batch_size = 8
    cfg.data.seq_len = 128          # 32768 on the real preset
    cfg.data.vocab_size = 97
    cfg.xent_chunk = 32             # 2048 on the real preset
    cfg.model.extra = dict(TINY)
    cfg.model.compute_dtype = "float32"
    cfg.model.remat = False
    cfg.mesh = mesh_spec
    for key, value in edits.items():
        cfg = cfg.override(**{key: value})
    trainer = Trainer(cfg, mesh=make_mesh(cfg.mesh.resolve(
        len(jax.devices()))))
    trainer.train()
    print(f"{tag:<34} final loss "
          f"{trainer.losses()[-1] if trainer.history else float('nan'):.4f}")


# single-"chip" reference: flash (falls back to exact jnp math on CPU)
# + chunked xent
run("1-device math (chunked xent)", MeshSpec(data=-1))

# context parallelism: sequence sharded 4-way, KV ring over the mesh —
# same loss curve (golden equivalence holds through the ring)
run("ring attention (seq=4 x data=2)", MeshSpec(seq=4, data=2),
    **{"model.extra": dict(TINY, attn_impl="ring")})
