"""Int8 quantization kernels for compressed gradient allreduce
(EQuARX-style, PAPERS.md arXiv 2506.17615).

The wire format is symmetric per-bucket int8: scale = absmax/127 agreed
across the axis (pmax), stochastic rounding so the gradient estimator
stays unbiased. On TPU the quantize step is a Pallas kernel using the
hardware PRNG (`pltpu.prng_random_bits` + `pltpu.stochastic_round`); off
TPU a jnp fallback with `jax.random` keeps tests exact-shape compatible.

Used by ops/buckets.make_bucket_reduce(quantized="int8"): quantize →
psum in int32 (exact integer addition — no precision loss in the
reduction itself) → dequantize by scale/n.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# (rows, 128) tiles: 256 rows × 128 lanes = 128 KiB f32 per block — far
# under the ~16 MiB VMEM budget even with double buffering, and the row
# count is a multiple of every dtype's sublane minimum.
_TILE_ROWS = 256
_LANES = 128
_TILE_ELEMS = _TILE_ROWS * _LANES


def _quantize_kernel(seed_ref, x_ref, scale_ref, out_ref):
    # decorrelate tiles: each grid step gets its own PRNG stream
    pltpu.prng_seed(seed_ref[0] + pl.program_id(0))
    scale = scale_ref[0, 0]
    scaled = x_ref[...] / scale
    bits = pltpu.bitcast(pltpu.prng_random_bits(scaled.shape), jnp.uint32)
    # stochastic floor-rounding: floor(x + u), u ~ U[0,1)
    # (pltpu.stochastic_round only targets float dtypes, so hand-roll;
    # mosaic lacks uint32→f32 casts, so take the top 24 bits via int32)
    bits24 = pltpu.bitcast(bits >> 8, jnp.int32)
    u = bits24.astype(jnp.float32) * (1.0 / 16777216.0)
    rounded = jnp.floor(scaled + u)
    out_ref[...] = jnp.clip(rounded, -127.0, 127.0).astype(jnp.int8)


@jax.jit
def _quantize_tpu(flat, scale, seed):
    n = flat.shape[0]
    padded = (-n) % _TILE_ELEMS
    x = jnp.pad(flat, (0, padded)).reshape(-1, _LANES)
    rows = x.shape[0]
    out = pl.pallas_call(
        _quantize_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(rows // _TILE_ROWS,),
            in_specs=[
                pl.BlockSpec((_TILE_ROWS, _LANES), lambda i, *_: (i, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec(memory_space=pltpu.SMEM),
            ],
            out_specs=pl.BlockSpec((_TILE_ROWS, _LANES), lambda i, *_: (i, 0),
                                   memory_space=pltpu.VMEM),
        ),
        out_shape=jax.ShapeDtypeStruct((rows, _LANES), jnp.int8),
    )(jnp.asarray([seed], jnp.int32).ravel(), x,
      jnp.asarray(scale, jnp.float32).reshape(1, 1))
    return out.ravel()[:n]


def quantize_int8(x, scale, *, seed):
    """Stochastic-round x/scale to int8. x: any shape; scale: scalar;
    seed: int or traced int32 scalar."""
    if jax.default_backend() == "tpu":
        return _quantize_tpu(x.ravel(), scale, seed).reshape(x.shape)
    return _quantize_reference(x, scale, seed)


def _quantize_reference(x, scale, seed):
    """jnp oracle and off-TPU path: stochastic rounding via uniform
    noise (another random stream than the kernel's, the same law)."""
    key = jax.random.fold_in(jax.random.key(17), seed)
    scaled = x / scale
    noise = jax.random.uniform(key, scaled.shape)
    rounded = jnp.floor(scaled + noise)
    return jnp.clip(rounded, -127, 127).astype(jnp.int8)


def dequantize_int8(q, scale):
    return q.astype(jnp.float32) * scale
