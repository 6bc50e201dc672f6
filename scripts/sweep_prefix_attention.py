#!/usr/bin/env python
"""Time the prefix-attention kernel on the chip, a tile choice a line
(chip only; ``PERF.md`` sec. 6 holds the tables this wrote):

    python scripts/sweep_prefix_attention.py [block_q,block_k,heads ...]

Shapes are the served ones in bf16, batch 1. MLA's, 64 heads of 192 /
128: a whole prompt of 6,528 tokens padded to 8,192 (what the A.X-K1
cell prefills on a miss), the same with every position real, a suffix of
512 behind 6,144 restored rows, and LongCat's buckets with ``T = S``.
Grouped queries of 128 / 128: Mistral's 32 query heads to 8 K/V heads at
its documents' buckets and at the lists' mean prompt of the largest
(3,150 real of 4,096), K-EXAONE's 64 to 8 at its largest bucket. Each
line gives the kernel's milliseconds and the share of the chip's peak
its useful work (scores inside the mask) comes to; the first choice is
also compared with the ``jax.numpy`` recurrence.

Then the routine as ``nn/attention.MultiHeadAttention`` calls it for a
prefill (``_prefill_attention``: the row cache's layout, the transposes
counted) beside the dense routine it replaced (``_cache_attention``),
Mistral's heads at every bucket from 32 to 4,096 with ``T = S``, 17
layers' worth in one program, milliseconds a layer. Under 128 rows the
kernel cannot tile and the first of the two is the recurrence.
"""

import sys
import time

sys.path.insert(0, ".")

import jax
import jax.numpy as jnp
import numpy as np

from pytorch_distributed_nn_tpu.nn import attention
from pytorch_distributed_nn_tpu.ops.pallas import prefix_attention as pa
from pytorch_distributed_nn_tpu.runtime.device import configure_compile_cache

PEAK = 197e12
MLA = (64, 64, 192, 128)        # query heads, K/V heads, qk and v widths
MISTRAL = (32, 8, 128, 128)
K_EXAONE = (64, 8, 128, 128)
# name: (heads and widths, T, S, first position, real queries)
SHAPES = {
    "whole 6528 of 8192": (MLA, 8192, 8192, 0, 6528),
    "whole 8192": (MLA, 8192, 8192, 0, 8192),
    "suffix 512 at 6144": (MLA, 512, 8192, 6144, 512),
    "bucket 2048": (MLA, 2048, 2048, 0, 2048),
    "bucket 1024": (MLA, 1024, 1024, 0, 1024),
    "32/8 bucket 1024": (MISTRAL, 1024, 1024, 0, 1024),
    "32/8 bucket 2048": (MISTRAL, 2048, 2048, 0, 2048),
    "32/8 bucket 4096": (MISTRAL, 4096, 4096, 0, 4096),
    "32/8 3150 of 4096": (MISTRAL, 4096, 4096, 0, 3150),
    "64/8 bucket 2048": (K_EXAONE, 2048, 2048, 0, 2048),
}
LAYERS = 17
BUCKETS = (32, 64, 128, 256, 512, 1024, 2048, 4096)


def operands(heads, T, S, first, real):
    H, Hkv, dk, dv = heads
    ks = jax.random.split(jax.random.key(T + first), 3)
    q = jax.random.normal(ks[0], (1, H, T, dk), jnp.bfloat16)
    k = jax.random.normal(ks[1], (1, Hkv, S, dk), jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, Hkv, S, dv), jnp.bfloat16)
    pos = first + jnp.arange(T)
    return q, k, v, jnp.where(jnp.arange(T) < real, pos, -1)[None]


def timed(fn, args, n=8):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n, out


def kernel_table(choices) -> None:
    for name, (heads, T, S, first, real) in SHAPES.items():
        H, _, dk, dv = heads
        args = operands(heads, T, S, first, real)
        pos = np.asarray(args[3][0])
        useful = 2.0 * H * (dk + dv) * float((pos[pos >= 0] + 1).sum())
        for i, (bq, bk, step) in enumerate(choices):
            bq, bk = pa.tiles(T, S, bq, bk)
            fn = jax.jit(lambda q, k, v, p, bq=bq, bk=bk, step=step:
                         pa._pallas(q, k, v, p, scale=dk ** -0.5,
                                    block_q=bq, block_k=bk, heads=step))
            try:
                secs, out = timed(fn, args)
            except Exception as e:  # noqa: BLE001 - a choice over VMEM
                print(f"{name}: {bq} x {bk}, {step} heads: "
                      f"{type(e).__name__} {str(e)[:120]}", flush=True)
                continue
            line = (f"{name}: {bq} x {bk}, {step} heads: "
                    f"{secs * 1e3:.3f} ms, "
                    f"{100 * useful / secs / PEAK:.1f} % of peak")
            if i == 0:
                want = jax.jit(lambda q, k, v, p: pa._blockwise(
                    q, k, v, p, scale=dk ** -0.5, block_q=bq,
                    block_k=bk))(*args)
                gap = jnp.abs(out.astype(jnp.float32)
                              - want.astype(jnp.float32))[:, :, :real].max()
                line += f"; against jax.numpy {float(gap):.2e}"
            print(line, flush=True)


def as_called_table() -> None:
    """``_prefill_attention`` and the dense routine over the row cache,
    LAYERS layers of Mistral's heads in one program each."""
    H, Hkv, D, _ = MISTRAL
    for T in BUCKETS:
        ks = jax.random.split(jax.random.key(T), 3)
        q = jax.random.normal(ks[0], (LAYERS, 1, T, H, D), jnp.bfloat16)
        k = jax.random.normal(ks[1], (LAYERS, 1, T, Hkv, D), jnp.bfloat16)
        v = jax.random.normal(ks[2], (LAYERS, 1, T, Hkv, D), jnp.bfloat16)
        positions = jnp.arange(T)[None]
        seen = positions[:, None, :] <= positions[:, :, None]
        routines = {
            "prefill_attention": lambda a: attention._prefill_attention(
                *a, positions),
            "dense": lambda a: attention._cache_attention(
                *a, seen, jnp.bfloat16),
        }
        line, outs = f"32/8 as called, T = S = {T}:", []
        for name, one in routines.items():
            fn = jax.jit(lambda q, k, v, one=one: jax.lax.map(
                one, (q, k, v)))
            secs, out = timed(fn, (q, k, v), n=4)
            outs.append(out.astype(jnp.float32))
            line += f" {name} {secs * 1e3 / LAYERS:.4f} ms a layer,"
        print(f"{line} gap {float(jnp.abs(outs[0] - outs[1]).max()):.2e}",
              flush=True)


def main(argv) -> int:
    configure_compile_cache()
    choices = [tuple(int(x) for x in a.split(",")) for a in argv] \
        or [(pa.QUERY_BLOCK, pa.KEY_BLOCK, pa.HEADS_A_STEP)]
    print(jax.devices()[0].device_kind, flush=True)
    kernel_table(choices)
    as_called_table()
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
