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

With ``round`` as the first argument, the decode round's kernel
(``round_attention``) instead, a key block a column:

    python scripts/sweep_prefix_attention.py round [block_k ...]

the served caches of rows by position as they lie (slots x rows x a
position's K/V heads side by side), at ragged depths with idle slots as
each cell's traffic leaves them: Mistral's chat (32 x 1,024, two slots
in three idle) and documents (8 x 4,096), K-EXAONE's full layers,
LFM2's (heads of 64, two a lane tile), Jamba's (20 query heads to one
K/V head) at one position a row, and SDAR's block round (8 positions a
row), four layers' leaves in one program, microseconds a layer, beside
the dense routine over the same rows by head and the time the attended
rows' bytes take at the chip's peak.

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
PEAK_BYTES = 819e9
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
# name: (slots, rows, query heads, K/V heads, head width, fed positions a
# row, share of the slots idle, a live row's depth as shares of its rows)
ROUNDS = {
    "mistral chat 32 x 1024": (32, 1024, 32, 8, 128, 1, 0.68, (0.1, 0.6)),
    "mistral docs 8 x 4096": (8, 4096, 32, 8, 128, 1, 0.0, (0.25, 0.95)),
    "k-exaone 32 x 4096": (32, 4096, 64, 8, 128, 1, 0.0, (0.05, 0.45)),
    "lfm2 64 x 4096": (64, 4096, 32, 8, 64, 1, 0.0, (0.05, 0.55)),
    "jamba 64 x 4096": (64, 4096, 20, 1, 128, 1, 0.0, (0.05, 0.6)),
    "sdar 64 x 2048 block round": (64, 2048, 32, 4, 128, 8, 0.0,
                                   (0.1, 0.6)),
}
ROUND_LAYERS = 4
# calls of a layer's routine in one program, each with its queries
# rolled (so that none is folded into another): four layers of one call
# each ran shorter than the host takes to dispatch a program (~0.7 ms),
# which the first sweep's small shapes read instead of the kernel
ROUND_REPEATS = 8


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


def round_table(blocks) -> None:
    """``_round_attention``'s kernel at each key block, and the dense
    routine, ROUND_LAYERS layers' leaves in one program each."""
    for name, (B, S, H, Hkv, D, T, idle, (lo, hi)) in ROUNDS.items():
        rng = np.random.RandomState(B + S)
        live = rng.rand(B) >= idle
        depth = (rng.uniform(lo, hi, B) * S).astype(np.int32) // T * T
        lengths = jnp.asarray(np.where(live, T, 0), jnp.int32)
        seen = jnp.asarray(depth)[:, None] + jnp.arange(T)[None]
        if T > 1:
            seen = seen // T * T + T - 1
        attended = int(((np.asarray(seen) + 1) * live[:, None]).sum()) // T
        base = jax.random.key(S + H)
        # a leaf a layer, each an argument of its own: cut from one
        # stacked array, each would be copied before it is read
        layers = [tuple(jax.random.normal(kk, shape, jnp.bfloat16)
                        for kk, shape in zip(
                            jax.random.split(jax.random.fold_in(base, i), 3),
                            ((B, T, H, D), (B, S, Hkv * D), (B, S, Hkv * D))))
                  for i in range(ROUND_LAYERS)]
        floor = attended * Hkv * D * 2 * 2 / PEAK_BYTES

        def dense(a):
            heads = lambda x: x.reshape(B, S, Hkv, D)  # noqa: E731
            return attention._cache_attention(
                a[0], heads(a[1]), heads(a[2]),
                jnp.arange(S)[None, None, :] <= seen[:, :, None],
                jnp.bfloat16)

        def program(one):
            return jax.jit(lambda ls: [
                [one((jnp.roll(a[0], r, axis=2),) + a[1:])
                 for r in range(ROUND_REPEATS)] for a in ls])

        calls = ROUND_LAYERS * ROUND_REPEATS
        secs, want = timed(program(dense), (layers,))
        line = (f"{name}: {int(live.sum())} live, {attended} rows attended "
                f"({100 * attended / (B * S):.1f} % of the leaf, "
                f"{floor * 1e6:.0f} us at peak); us a layer: dense "
                f"{secs * 1e6 / calls:.0f}")
        real = (jnp.arange(T)[None] < lengths[:, None])[..., None, None]
        for bk in blocks:
            if S % bk:
                continue

            def kernel(a):
                return attention._round_attention(*a, seen, lengths,
                                                  jnp.bfloat16)

            # (read when the round is traced)
            pa.ROUND_KEY_BLOCKS = pa.ROUND_KEY_BLOCKS_ONE_HEAD = (bk,)
            try:
                secs, out = timed(program(kernel), (layers,))
            except Exception as e:  # noqa: BLE001 - a block over VMEM
                line += f", {bk}: {type(e).__name__}"
                continue
            gap = max(float(jnp.where(
                real, jnp.abs(o[0].astype(jnp.float32)
                              - w[0].astype(jnp.float32)), 0).max())
                for o, w in zip(out, want))
            line += f", {bk}: {secs * 1e6 / calls:.0f} (gap {gap:.1e})"
        print(line, flush=True)


def main(argv) -> int:
    configure_compile_cache()
    if argv[:1] == ["round"]:
        print(jax.devices()[0].device_kind, flush=True)
        round_table([int(a) for a in argv[1:]] or pa.ROUND_KEY_BLOCKS)
        return 0
    choices = [tuple(int(x) for x in a.split(",")) for a in argv] \
        or [(pa.QUERY_BLOCK, pa.KEY_BLOCK, pa.HEADS_A_STEP)]
    print(jax.devices()[0].device_kind, flush=True)
    kernel_table(choices)
    as_called_table()
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
