"""Gradient bucketing — the DDP ``Reducer`` equivalent.

The reference's key perf behavior is DDP's C++ Reducer: gradients are
packed into ~25 MB buckets and all-reduced per-bucket, overlapped with the
remaining backward pass (SURVEY.md §2b Reducer row; BASELINE.json "large
fused gradient buckets"). On TPU both halves are the compiler's: the
*fusion* (few large collectives instead of one tiny psum per tensor) is
its all-reduce combiner's, and the *overlap* its scheduler's, which by
default leaves every all-reduce alone on the core's timeline
(``parallel/dp.py`` asks it for what overlap it can give). What is ours
is the order and the grouping the reductions are issued in, the wire
format, and not getting in the combiner's way.

:func:`make_bucket_reduce` builds a ``grads -> grads`` transform for the
explicit shard_map DP path. A bucket is a *group of leaves*, not a
buffer: leaves are taken last to first (DDP's bucket assignment, see
:func:`group_leaves`), grouped by dtype, greedily packed to ``bucket_mb``,
and each group's leaves are mean-reduced where they lie, each in its own
shape and layout. XLA's all-reduce combiner merges the per-leaf
reductions into a few collectives with tuple operands, by its own
threshold (128 MiB for a v5e: about one collective a 100 MB bucket; on
this path ``bucket_mb`` orders and logs, it does not bound a
collective), and the division by the axis size stays elementwise on each
leaf, where it fuses into the optimizer's update. Nothing is packed: a
flat rank-1 buffer does not lie in the (8, 128) tiles its leaves lie in,
so every ``ravel`` into one and every slice back out was a relayout copy
at run time — as much again as the all-reduce itself on four chips
(PERF.md sec. 5 and 6, PR 30, holds every number).

``quantized`` compresses the wire format (EQuARX-style, PAPERS.md):
``"bf16"``/True halves f32 traffic by casting each leaf; ``"int8"``
quarters it — stochastic-rounded symmetric int8 (Pallas hardware-PRNG
kernel on TPU) with an exact int32 psum and a shared pmax scale, so the
reduction itself loses nothing beyond the 8-bit encode. The int8 wire is
the one form that still packs a bucket into a flat operand: its kernel
tiles one and seeds its PRNG by tile.
"""

from __future__ import annotations

import logging
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from pytorch_distributed_nn_tpu.ops import collectives as cc

log = logging.getLogger(__name__)


def partition_buckets(
    sizes_bytes: Sequence[int], bucket_bytes: int
) -> list[list[int]]:
    """Greedy contiguous packing of leaf indices into buckets of at most
    ``bucket_bytes`` (a leaf larger than the budget gets its own bucket).
    Pure function — unit-tested against the FakeWorld (SURVEY.md §4
    "Unit" row)."""
    if bucket_bytes <= 0:
        raise ValueError("bucket_bytes must be positive")
    buckets: list[list[int]] = []
    current: list[int] = []
    used = 0
    for idx, size in enumerate(sizes_bytes):
        if current and used + size > bucket_bytes:
            buckets.append(current)
            current, used = [], 0
        current.append(idx)
        used += size
    if current:
        buckets.append(current)
    return buckets


def _nbytes(leaf) -> int:
    return leaf.size * leaf.dtype.itemsize


def group_leaves(leaves, bucket_bytes: int) -> list[list[int]]:
    """Indices of ``leaves`` (anything with ``size`` and ``dtype``) in
    their buckets. Reverse order: last-layer grads are ready first in
    backward, so their bucket's allreduce can start earliest (DDP's
    heuristic; only roughly true of a flax tree, which flattens by
    sorted key — BERT-base's first bucket holds ``tok_embed``, ready
    last — and harmless: the reductions do not depend on each other, so
    XLA places each where its operands are ready). One dtype a bucket,
    so a bucket reduces in its leaves' native dtype — no f32 upcast
    doubling bf16 wire traffic."""
    by_dtype: dict = {}
    for i in reversed(range(len(leaves))):
        by_dtype.setdefault(leaves[i].dtype, []).append(i)
    groups = []
    for idxs in by_dtype.values():
        sizes = [_nbytes(leaves[i]) for i in idxs]
        groups += [[idxs[j] for j in bucket]
                   for bucket in partition_buckets(sizes, bucket_bytes)]
    return groups


def _int8_mean(group, axis, seed):
    """Mean of one bucket over an int8 wire. The one form that packs:
    the quantize kernel tiles a flat operand and seeds its PRNG by tile,
    and one scale a bucket is one ``pmax``."""
    from pytorch_distributed_nn_tpu.ops.pallas.quantize import (
        dequantize_int8,
        quantize_int8,
    )

    dtype = group[0].dtype
    flat = jnp.concatenate([g.ravel() for g in group])
    absmax = cc.all_reduce_max(jnp.abs(flat).max(), axis)
    scale = jnp.maximum(absmax / 127.0, 1e-12)
    # decorrelate rounding noise across devices so it averages down
    # ~1/sqrt(n) in the mean
    q = quantize_int8(flat.astype(jnp.float32), scale,
                      seed=seed + cc.linear_axis_index(axis))
    total = cc.all_reduce_sum(q.astype(jnp.int32), axis)
    mean = (dequantize_int8(total, scale)
            / cc.axis_size(axis)).astype(dtype)
    offsets = np.cumsum([0] + [g.size for g in group])
    return [mean[lo:hi].reshape(g.shape)
            for g, lo, hi in zip(group, offsets, offsets[1:])]


def make_bucket_reduce(
    *,
    bucket_mb: float = 25.0,
    axis=("data", "fsdp"),
    quantized: bool | str = False,
) -> Callable:
    """Build the bucketed gradient-mean transform (runs inside shard_map).

    ``quantized``: False (exact), "bf16"/True (cast wire), or "int8"
    (stochastic-rounded; ``seed`` keyword decorrelates rounding across
    steps — pass the step counter).
    """
    bucket_bytes = int(bucket_mb * 1024 * 1024)
    mode = {False: None, True: "bf16"}.get(quantized, quantized)
    if mode not in (None, "bf16", "int8"):
        raise ValueError(f"unknown quantized mode {quantized!r}")

    def reduce_grads(grads, *, seed=0):
        leaves, treedef = jax.tree.flatten(grads)
        groups = group_leaves(leaves, bucket_bytes)
        reduced: list = [None] * len(leaves)
        packed = []
        for number, idxs in enumerate(groups, 1):  # number: unique seeds
            group = [leaves[i] for i in idxs]
            dtype = group[0].dtype
            # the scope is metadata: obs/scopes.py tells a bucket's
            # device time from it
            with jax.named_scope(f"grad_reduce/bucket{number}"):
                if mode == "int8" and jnp.issubdtype(dtype, jnp.floating):
                    means = _int8_mean(group, axis,
                                       seed * 65537 + number * 257)
                    packed += group
                elif mode == "bf16" and dtype.itemsize > 2:
                    wire = [g.astype(jnp.bfloat16) for g in group]
                    means = [m.astype(dtype)
                             for m in cc.tree_all_reduce_mean(wire, axis)]
                else:
                    means = cc.tree_all_reduce_mean(group, axis)
            for i, m in zip(idxs, means):
                reduced[i] = m
        log.info(
            "bucket reduce (trace): %d leaves in %d buckets of <= %d "
            "bytes, wire %s; leaves/bytes a bucket: %s; through a packed "
            "operand: %d leaves, %d bytes",
            len(leaves), len(groups), bucket_bytes, mode or "exact",
            [(len(g), sum(_nbytes(leaves[i]) for i in g)) for g in groups],
            len(packed), sum(map(_nbytes, packed)))
        return jax.tree.unflatten(treedef, reduced)

    return reduce_grads
