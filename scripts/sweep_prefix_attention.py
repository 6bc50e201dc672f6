#!/usr/bin/env python
"""Time the prefix-attention kernel on the chip, a tile choice a line
(chip only; ``PERF.md`` sec. 6 holds the table this wrote):

    python scripts/sweep_prefix_attention.py [block_q,block_k,heads ...]

Shapes are the served MLA's, 64 heads of 192 / 128 in bf16, batch 1: a
whole prompt of 6,528 tokens padded to 8,192 (what the A.X-K1 cell
prefills on a miss), the same with every position real, a suffix of 512
behind 6,144 restored rows, and LongCat's buckets with ``T = S``. Each
line gives the kernel's milliseconds and the share of the chip's peak
its useful work (scores inside the mask) comes to; the first choice is
also compared with the ``jax.numpy`` recurrence.
"""

import sys
import time

sys.path.insert(0, ".")

import jax
import jax.numpy as jnp
import numpy as np

from pytorch_distributed_nn_tpu.ops.pallas import prefix_attention as pa
from pytorch_distributed_nn_tpu.runtime.device import configure_compile_cache

H, DK, DV, PEAK = 64, 192, 128, 197e12
# name: (T, S, first position, real queries)
SHAPES = {
    "whole 6528 of 8192": (8192, 8192, 0, 6528),
    "whole 8192": (8192, 8192, 0, 8192),
    "suffix 512 at 6144": (512, 8192, 6144, 512),
    "bucket 2048": (2048, 2048, 0, 2048),
    "bucket 1024": (1024, 1024, 0, 1024),
}


def operands(T, S, first, real):
    ks = jax.random.split(jax.random.key(T + first), 3)
    q = jax.random.normal(ks[0], (1, H, T, DK), jnp.bfloat16)
    k = jax.random.normal(ks[1], (1, H, S, DK), jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, H, S, DV), jnp.bfloat16)
    pos = first + jnp.arange(T)
    return q, k, v, jnp.where(jnp.arange(T) < real, pos, -1)[None]


def timed(fn, args, n=8):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n, out


def main(argv) -> int:
    configure_compile_cache()
    choices = [tuple(int(x) for x in a.split(",")) for a in argv] \
        or [(512, 1024, 4)]
    print(jax.devices()[0].device_kind, flush=True)
    for name, (T, S, first, real) in SHAPES.items():
        args = operands(T, S, first, real)
        pos = np.asarray(args[3][0])
        useful = 2.0 * H * (DK + DV) * float((pos[pos >= 0] + 1).sum())
        for i, (bq, bk, heads) in enumerate(choices):
            bq, bk = pa.tiles(T, S, bq, bk)
            fn = jax.jit(lambda q, k, v, p, bq=bq, bk=bk, heads=heads:
                         pa._pallas(q, k, v, p, scale=DK ** -0.5,
                                    block_q=bq, block_k=bk, heads=heads))
            try:
                secs, out = timed(fn, args)
            except Exception as e:  # noqa: BLE001 - a choice over VMEM
                print(f"{name}: {bq} x {bk}, {heads} heads: "
                      f"{type(e).__name__} {str(e)[:120]}", flush=True)
                continue
            line = (f"{name}: {bq} x {bk}, {heads} heads: "
                    f"{secs * 1e3:.3f} ms, "
                    f"{100 * useful / secs / PEAK:.1f} % of peak")
            if i == 0:
                want = jax.jit(lambda q, k, v, p: pa._blockwise(
                    q, k, v, p, scale=DK ** -0.5, block_q=bq,
                    block_k=bk))(*args)
                gap = jnp.abs(out.astype(jnp.float32)
                              - want.astype(jnp.float32))[:, :, :real].max()
                line += f"; against jax.numpy {float(gap):.2e}"
            print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
