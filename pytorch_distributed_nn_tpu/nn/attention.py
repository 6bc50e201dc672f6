"""Multi-head attention shared by the transformer families.

One module covers BERT (bidirectional), Transformer-LM (causal), and
Llama (causal + rotary + grouped-query). The inner product is routed
through :func:`dot_product_attention`, which selects the implementation:
``xla`` (einsum softmax — XLA fuses this well for moderate sequence
lengths) or ``flash`` (the Pallas blockwise kernel, ops/pallas/) once the
sequence is long enough to be HBM-bound. Ring/context-parallel attention
wraps the same kernel over the ``seq`` mesh axis (parallel/sequence.py).
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from flax import linen as nn

from pytorch_distributed_nn_tpu.nn.quantized import Int8DenseGeneral
from pytorch_distributed_nn_tpu.ops.pallas.prefix_attention import (
    prefix_attention,
    round_attention,
    round_key_block,
    round_rows_read,
    rows_read,
    seen_from,
)


def yarn_frequencies(dim: int, theta: float, factor: float,
                     original_positions: int, beta_fast: float = 32.0,
                     beta_slow: float = 1.0) -> tuple:
    """YaRN's per-pair frequencies (arXiv:2309.00071 sec. 3.2, as
    ``transformers`` ``_compute_yarn_parameters`` writes them): pair i of
    ``dim / 2`` turns by ``f_i = theta**(-2i/dim)`` where it makes more
    than ``beta_fast`` turns over the ``original_positions`` the model
    was trained on (pairs below ``low``), by ``f_i / factor`` where it
    makes fewer than ``beta_slow`` (pairs above ``high``), and by a
    linear blend between. Returns ``dim / 2`` floats: no power law, so
    :func:`rotary_embedding` takes them as ``freqs``."""
    def pair_of(turns):   # the (real-valued) pair that makes `turns` turns
        return dim * math.log(original_positions / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))
    low = max(math.floor(pair_of(beta_fast)), 0)
    high = min(math.ceil(pair_of(beta_slow)), dim - 1)
    span = (high - low) or 0.001
    out = []
    for i in range(dim // 2):
        f = theta ** (-2.0 * i / dim)
        g = min(max((i - low) / span, 0.0), 1.0)
        out.append(f / factor * g + f * (1.0 - g))
    return tuple(out)


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's magnitude correction ``0.1 * mscale * ln(factor) + 1``
    (1 for no extension)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rotary_embedding(q, k, *, theta: float = 10000.0, positions=None,
                     freqs=None):
    """Apply rotary position embeddings to q, k of shape (B, T, H, D).
    Pair i turns by ``theta**(-2i/D)`` a position, or by ``freqs[i]``
    where the (D/2) frequencies are given (:func:`yarn_frequencies`)."""
    d = q.shape[-1]
    if d % 2:
        raise ValueError(f"rotary needs even head_dim, got {d}")
    if positions is None:
        positions = jnp.arange(q.shape[1])[None, :]  # (1, T)
    if freqs is None:
        freqs = theta ** (-jnp.arange(0, d // 2) * 2.0 / d)  # (D/2,)
    else:
        freqs = jnp.asarray(freqs, jnp.float32)
    angles = positions[..., None] * freqs  # (B?, T, D/2)
    cos = jnp.cos(angles)[:, :, None, :]  # (B?, T, 1, D/2)
    sin = jnp.sin(angles)[:, :, None, :]

    def rotate(x):
        x1, x2 = jnp.split(x, 2, axis=-1)
        return jnp.concatenate(
            [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
        )

    return rotate(q), rotate(k)


def _auto_impl(q_shape, k_shape, *, has_mask: bool,
               device_count: Optional[int] = None) -> str:
    """The 'auto' flash-vs-xla decision (see dot_product_attention's
    docstring for the v5e measurements behind the thresholds).

    ``device_count=None`` assumes the shapes are GLOBAL (jit/GSPMD
    trace-time shapes) and divides the B*H rows by ``jax.device_count()``
    for the fully-sharded worst case. Callers inside ``shard_map`` see
    per-device SHARD shapes and must pass ``device_count=1`` — otherwise
    the rows are divided twice and the T in [1024, 2048) flash upgrade
    never fires (advisor r3 finding)."""
    T = q_shape[1]
    if device_count is None:
        device_count = jax.device_count()
    rows_per_chip = (q_shape[0] * q_shape[2]) // max(device_count, 1)
    return ("flash" if jax.default_backend() == "tpu"
            and not has_mask and k_shape[1] == T
            and (T >= 2048 or (T >= 1024 and rows_per_chip >= 64))
            else "xla")


def dot_product_attention(
    q, k, v, *, causal: bool, impl: str = "xla",
    mask: Optional[jax.Array] = None,
    device_count: Optional[int] = None,
):
    """q: (B, T, H, D); k/v: (B, S, Hkv, D) with H % Hkv == 0.

    Returns (B, T, H, D). f32 softmax accumulation regardless of input
    dtype (MXU-friendly: bf16 operands, f32 accumulate).

    impl: 'xla' (fused by the compiler; required for padding masks and
    cross-length kv), 'flash' (Pallas kernels in both directions: the
    streamed forward plus the two-pass lse-replay backward), or 'auto'.
    Measured on v5e (llama-shaped blocks, fwd+bwd): xla wins at T=512;
    T=1k is an OCCUPANCY question — the flash grid parallelizes over
    B*H row-programs, and with too few the chip idles (batch-1
    full-model bench favors xla; batch-4 favors flash 1.2x; batch-16
    favors flash 1.41x, r3 A/B). Flash clearly wins from 2k up at any
    batch (1.33x+ with 1024-token blocks, growing with T — xla's
    (T, T) scores thrash HBM from 8k). So 'auto' picks flash on TPU
    for self-attention with no padding mask at T >= 2048, or at
    T >= 1024 with >= 64 B*H rows PER CHIP (the measured break-even).
    Trace-time shapes are GLOBAL under jit/GSPMD, so the per-chip rows
    divide the worst case — batch and heads fully sharded — by the
    device count; single-chip runs are unchanged, and a pod DP run at
    per-chip batch 1 correctly stays on xla.
    """
    if impl == "auto":
        impl = _auto_impl(q.shape, k.shape, has_mask=mask is not None,
                          device_count=device_count)
    if impl not in ("xla", "flash"):
        raise ValueError(f"unknown attention impl {impl!r}")
    B, T, H, D = q.shape
    S, Hkv = k.shape[1], k.shape[2]
    if impl == "flash":
        if mask is not None:
            raise ValueError(
                "flash impl does not take a padding mask; use impl='xla'"
            )
        from pytorch_distributed_nn_tpu.ops.pallas.flash_attention import (
            flash_attention,
        )

        # kv stays grouped: the kernel streams each KV tile for its
        # whole Q-head group (expanding here would multiply KV HBM
        # traffic by H/Hkv)
        return flash_attention(q, k, v, causal=causal)
    if H != Hkv:  # grouped-query: repeat kv heads for the einsum path
        k = jnp.repeat(k, H // Hkv, axis=2)
        v = jnp.repeat(v, H // Hkv, axis=2)
    scale = D ** -0.5
    logits = jnp.einsum(
        "bthd,bshd->bhts", q, k, preferred_element_type=jnp.float32
    ) * scale
    if causal:
        causal_mask = jnp.tril(jnp.ones((T, S), dtype=bool))
        logits = jnp.where(causal_mask[None, None], logits, -1e30)
    if mask is not None:
        if mask.ndim == 2:  # (B, S) padding mask
            logits = jnp.where(mask[:, None, None, :], logits, -1e30)
        else:  # (B|1, T, S) position mask (decode: causal-by-index)
            logits = jnp.where(mask[:, None, :, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhts,bshd->bthd", probs, v,
                      preferred_element_type=jnp.float32).astype(q.dtype)


def _row_update(buf, new, starts):
    """Per-row cache write: row ``i`` of ``new`` (T leading tokens)
    lands at ``buf[i, starts[i]:starts[i]+T]``. The continuous-batching
    primitive — each sequence in the batch advances at its own index
    instead of the shared scalar ``cache_index``. ``starts`` (B,) int32
    is never negative; a live row's window lies inside the row, and a
    live row gets the same bytes whichever way it is written.

    One token a row (every decode round) is one indexed scatter a
    leaf, which the TPU runs as one in-place fusion. A row whose start
    is out of range (a stopped row at ``max_seq_len``) is not written
    at all: the row is dead, and its next occupant's prefill overwrites
    or masks it. Several tokens a row (a prefill) are one
    ``dynamic_update_slice`` a row, which clamps an out-of-range window
    as a whole onto the row's end. The TPU compiles that form to a
    serial loop over the batch rows: one iteration for the engine's
    prefill, but B iterations a leaf a round if a decode round used it
    (a block decoder's round writes with :func:`_block_update`)."""
    with jax.named_scope("cache_write"):
        if new.shape[1] == 1:
            return buf.at[jnp.arange(buf.shape[0]), starts].set(
                new[:, 0], mode="drop", unique_indices=True,
                indices_are_sorted=True)
        return jax.vmap(
            lambda b, n, s: jax.lax.dynamic_update_slice(
                b, n, (s,) + (0,) * (b.ndim - 1))
        )(buf, new, starts)


def _block_update(buf, new, starts):
    """:func:`_row_update` for a round that feeds every row a block of
    a few positions (a block decoder's): one indexed scatter a leaf, as
    for one token a row, a position out of range dropped by itself (a
    stopped row at ``max_seq_len``)."""
    with jax.named_scope("cache_write"):
        return buf.at[jnp.arange(buf.shape[0])[:, None],
                      starts[:, None] + jnp.arange(new.shape[1])[None]].set(
            new, mode="drop", unique_indices=True, indices_are_sorted=True)


def _quantize_kv(x):
    """(B, T, H, D) → int8 values + (B, T, H) f32 scales: symmetric
    per-(token, head) absmax over the head dim. Zero rows (e.g. a
    dead head) get scale 1 so the stored zeros round-trip exactly."""
    xf = x.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(xf), axis=-1)
    scale = jnp.where(absmax > 0, absmax / 127.0, 1.0)
    q = jnp.clip(jnp.round(xf / scale[..., None]), -127,
                 127).astype(jnp.int8)
    return q, scale


def _cache_attention(q, k, v, pos_mask, dtype, kscale=None, vscale=None):
    """Decode attention over the KV cache with GQA kept GROUPED: q
    reshapes to (B, T, Hkv, G, D) instead of repeating the cached K/V.
    (The einsum-path `jnp.repeat` materializes H/Hkv copies of the
    whole cache every step — at the 8B's b=128/S=256 that is ~17 GB of
    extra HBM traffic per decoded token; removing it is worth 3x+ on
    large-batch decode, measured r5, BASELINE.md decode table.)

    With ``kscale``/``vscale`` (both (B, S, Hkv) f32) the cache is the
    int8 layout and is never dequantized into a materialized copy:
    per-(token, head) scales commute with the two contractions — K's
    scale multiplies the logits AFTER QK^T (each logit is linear in
    one cached K row), V's scale multiplies the softmax probabilities
    BEFORE PV (the output is linear in each cached V row). The int8
    payloads go straight into the matmuls as raw integers (exact in
    bf16: |v| ≤ 127) and the f32 scales touch only the (…, S) score
    plane.

    q: (B, T, H, D); k/v: (B, S, Hkv, D) float — or int8 when the
    scales are given; pos_mask: (B|1, T, S). Returns (B, T, H, D)."""
    B, T, H, D = q.shape
    q5 = q.reshape(B, T, k.shape[2], H // k.shape[2], D)
    logits = jnp.einsum("btkgd,bskd->bkgts", q5, k.astype(dtype),
                        preferred_element_type=jnp.float32)
    logits *= D ** -0.5
    if kscale is not None:
        logits *= kscale.transpose(0, 2, 1)[:, :, None, None, :]
    logits = jnp.where(pos_mask[:, None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    if vscale is not None:
        probs = probs * vscale.transpose(0, 2, 1)[:, :, None, None, :]
    out = jnp.einsum("bkgts,bskd->btkgd", probs.astype(dtype),
                     v.astype(dtype),
                     preferred_element_type=jnp.float32)
    return out.reshape(B, T, H, D).astype(dtype)


def lane_pack(head_dim: int, kv_heads: int) -> int:
    """K/V heads that one lane tile of a flat cache row holds side by
    side: 2 for an even number of heads of 64, else 1. The chip lays an
    array out in tiles of 128 lanes along its last axis, and a round's
    two products want a head's dims on them: read a head of 64 at a
    time, a cache is transposed whole, every layer, every round (four
    copies of 268 MB a layer at 64 slots x 4,096: 14.9 of a round's 30.7
    ms on the chip, PERF.md sec. 6, PR 46). :func:`_round_attention`
    reads such a row as ``kv_heads / 2`` heads of 128, the same bytes in
    the same order, in place."""
    return 2 if head_dim == 64 and kv_heads % 2 == 0 else 1


def _ring_held(last, rows):
    """The position a ring of ``rows`` rows holds in each row once
    position ``last`` (B,) is written: the newest one ``<= last``
    congruent to the row; negative where nothing was written yet."""
    r = jnp.arange(rows)[None, :]
    return last[:, None] - (last[:, None] - r) % rows


def ring_rows_scored(T: int, R: int) -> int:
    """Key rows a query of a ring layer is scored against when T tokens
    a row are fed: the ring alone for one, else its block's keys in
    flight and the R before them (:func:`_ring_attention`)."""
    return R if T == 1 else R + (R if T % R == 0 else T)


def _ring_attention(q, k, v, ring_k, ring_v, starts, lengths, dtype):
    """Sliding-window attention through a ring cache.

    The ring (B, R, Hkv, D) holds position ``p`` in row ``p mod R``; R
    is the window, so the ring is exactly the keys a query may see. The
    T fed tokens of row ``b`` stand at positions ``starts[b] + t``, the
    first ``lengths[b]`` of them real (a left-aligned prefix; the rest
    is a bucket's padding and must not displace a real position).
    Returns ``(out, ring_k, ring_v)``.

    One token a row (a decode round) writes its row in place and reads
    the R rows. Several (a prefill) attend the keys in flight in blocks
    of R queries, each against its own and the previous block (the
    ring's old content standing in before the first), so scores and
    their temporaries follow T x 2R, not T x T; then the newest real
    position of every residue goes to its row."""
    B, T = q.shape[:2]
    R = ring_k.shape[1]
    if T == 1:
        ring_k = _row_update(ring_k, k, starts % R)
        ring_v = _row_update(ring_v, v, starts % R)
        visible = _ring_held(starts, R) >= 0
        out = _cache_attention(q, ring_k, ring_v, visible[:, None, :], dtype)
        return out, ring_k, ring_v
    fed = starts[:, None] + jnp.arange(T)[None]                  # (B, T)
    k_all = jnp.concatenate([ring_k, k.astype(ring_k.dtype)], axis=1)
    v_all = jnp.concatenate([ring_v, v.astype(ring_v.dtype)], axis=1)
    pos_all = jnp.concatenate([_ring_held(starts - 1, R), fed], axis=1)
    nb = T // R if T % R == 0 else 1
    tb = T // nb

    def windows(x):   # (B, R + T, ...) -> (B * nb, R + tb, ...)
        if nb == 1:
            return x
        x = x.reshape((B, nb + 1, R) + x.shape[2:])
        x = jnp.concatenate([x[:, :-1], x[:, 1:]], axis=2)
        return x.reshape((B * nb, 2 * R) + x.shape[3:])

    k_pos = windows(pos_all)[:, None, :]
    q_pos = fed.reshape(B * nb, tb)[:, :, None]
    band = (k_pos >= 0) & (k_pos <= q_pos) & (q_pos - k_pos < R)
    out = _cache_attention(q.reshape((B * nb, tb) + q.shape[2:]),
                           windows(k_all), windows(v_all), band, dtype)
    out = out.reshape(q.shape)
    with jax.named_scope("cache_write"):
        newest = _ring_held(starts + lengths - 1, R) - starts[:, None]
        fresh = (newest >= 0)[:, :, None, None]
        take = jnp.clip(newest, 0, T - 1)[:, :, None, None]
        ring_k = jnp.where(fresh, jnp.take_along_axis(k, take, axis=1),
                           ring_k)
        ring_v = jnp.where(fresh, jnp.take_along_axis(v, take, axis=1),
                           ring_v)
    return out, ring_k, ring_v


# scores a head (queries x keys) up to which the dense routine is not
# slower than the blockwise one for a prefill over rows by position: its
# three transposes and the kernel's launch are then most of its time
# (on the chip at 32 / 8 heads of 128, us a layer, dense | blockwise:
# 32 x 32 15 | 17, 128 x 128 22 | 36, 512 x 512 78 | 116, 1,024 x 1,024
# 649 | 241; PERF.md sec. 6)
DENSE_SCORES = 512 * 512


def prefill_in_tiles(T: int, S: int) -> bool:
    """Whether T fed tokens a row against S rows by position attend
    blockwise (:func:`_prefill_attention`): several tokens whose dense
    scores would be large. A decode round and a small bucket keep the
    dense routine over the whole row (:func:`_cache_attention`)."""
    return T > 1 and T * S > DENSE_SCORES


def _prefill_attention(q, k, v, positions, lengths=None):
    """Several tokens a row against rows by position, blockwise
    (:mod:`ops.pallas.prefix_attention`: float32 scores a tile at a
    time that are never written out, no key tile past a query tile's
    largest position read; the K/V head of a group serves its query
    heads). q (B, T, H, D); k/v (B, S, Hkv, D), the cache with the fed
    tokens written; ``positions`` (B|1, T), where each fed token
    stands; ``lengths`` (B,), if given, how many of a row's are real:
    the others see nothing and get zeros. Returns (B, T, H, D)."""
    B, T = q.shape[:2]
    q_pos = jnp.broadcast_to(positions, (B, T))
    if lengths is not None:
        q_pos = seen_from(q_pos, jnp.arange(T)[None] < lengths[:, None])
    heads_first = lambda x: x.transpose(0, 2, 1, 3)  # noqa: E731
    return heads_first(prefix_attention(
        heads_first(q), heads_first(k), heads_first(v), q_pos,
        scale=q.shape[-1] ** -0.5))


def _round_block(S: int, head_dim: int, kv_heads: int, dtype) -> int:
    """The key block of a round's kernel over flat rows of ``kv_heads``
    heads of ``head_dim``, S to a slot; 0 where the dense routine runs
    (:func:`round_key_block`; heads of 64 two a lane tile count as one
    of 128)."""
    pack = lane_pack(head_dim, kv_heads)
    return round_key_block(S, kv_heads // pack, pack * head_dim, dtype)


def _round_attention(q, k, v, seen, lengths, dtype):
    """A decode round against rows by position: q (B, T, H, D), the
    fed positions of each row (one, or a block decoder's block or two);
    k, v (B, S, Hkv * D), the flat rows such a cache holds, the fed
    positions written; ``seen`` (B|1, T) the last key each query sees;
    ``lengths`` (B,) how many of a row's queries are real (0: a slot
    that is not live). On a TPU in bf16 (:func:`round_key_block`) one
    kernel a layer, whose grid step is a row and a key block: the G
    query heads of a K/V head times the T positions are that head's
    query rows, a row's key blocks past what it sees are not read, a
    query that is not real sees nothing and gets zeros, so a row with
    none reads nothing. Heads of 64 lie two a lane tile
    (:func:`lane_pack`) and are read as one head of 128: a query is
    laid into its own head's lanes, zeros in the other's, so that its
    product with the wide row is its product with its own head,
    exactly, and of the lanes that come back its own are kept (twice
    the multiplies, which a round bound by the cache's bytes does not
    feel; the bytes are the cache's own). Anywhere else the dense
    routine over the whole row, reshaped by head, which takes no notice
    of ``lengths``. Returns (B, T, H, D)."""
    B, T, H, D = q.shape
    S, kv_heads = k.shape[1], k.shape[2] // D
    G = H // kv_heads
    block_k = _round_block(S, D, kv_heads, k.dtype)
    if not block_k:
        heads = lambda x: x.reshape(B, S, kv_heads, D)  # noqa: E731
        return _cache_attention(
            q, heads(k), heads(v),
            jnp.arange(S)[None, None, :] <= seen[:, :, None], dtype)
    seen = jnp.broadcast_to(seen, (B, T))
    if lengths is not None:
        seen = seen_from(seen, jnp.arange(T)[None] < lengths[:, None])
    pack = lane_pack(D, kv_heads)
    P = kv_heads // pack
    # query head ((p * pack + r) * G + g) reads K/V head p * pack + r
    rows = q.reshape(B, T, P, pack, G, D).transpose(0, 2, 3, 1, 4, 5)
    if pack > 1:
        lanes = jnp.eye(pack, dtype=bool)[:, None, None, :, None]
        rows = jnp.where(lanes, rows[..., None, :], jnp.zeros((), q.dtype))
    out = round_attention(
        rows.reshape(B, P, pack * T * G, pack * D), k, v,
        jnp.tile(jnp.repeat(seen, G, axis=1), (1, pack)),
        scale=D ** -0.5, block_k=block_k)
    if pack > 1:
        out = jnp.where(lanes, out.reshape(B, P, pack, T, G, pack, D),
                        jnp.zeros((), out.dtype)).sum(axis=5)
    return out.reshape(B, P * pack, T, G, D).transpose(
        0, 2, 1, 3, 4).reshape(B, T, H, D).astype(dtype)


def cache_rows_read(attn, T: int, seen, real):
    """Key rows a cached call of ``attn`` (a bound
    :class:`MultiHeadAttention` over rows by position) that feeds T
    tokens a row reads for its ``real`` (B, T) queries, summed, each
    seeing up to ``seen`` (B, T): the key tiles its query tiles visit
    (a blockwise prefill), the key blocks up to the last position a
    row's real queries see (a round through :func:`_round_attention`'s
    kernel), or the row's whole length a query (the dense routine)."""
    key = attn.get_variable("cache", "cached_key")
    S = key.shape[1]
    if prefill_in_tiles(T, S):
        return rows_read(seen, real, S)
    if attn.is_round(T) and key.ndim == 3:
        block_k = _round_block(S, attn.head_dim,
                               attn.num_kv_heads or attn.num_heads, key.dtype)
        if block_k:
            return round_rows_read(seen, real, S, block_k)
    return real.sum() * S


class MultiHeadAttention(nn.Module):
    num_heads: int
    head_dim: int
    num_kv_heads: Optional[int] = None  # None = MHA; < num_heads = GQA
    causal: bool = False
    rotary: bool = False
    rope_theta: float = 10000.0
    impl: str = "xla"
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32
    use_bias: bool = True
    # weight-only int8 projections (nn/quantized.py): q/k/v/out kernels
    # stored int8 + per-out-channel scales, dequantized tile-wise in
    # the Pallas matmul — the capacity mode that fits Llama-3-8B's
    # weights in one chip's HBM. Bias-free only (the Llama family).
    quantized: bool = False
    # decode KV-cache storage: "compute" (the activation dtype, bf16 in
    # the presets) or "int8" — per-(token, head) symmetric scales,
    # halving cache HBM so the servable batch roughly doubles (the 8B
    # b=192 OOM edge). The int8 path never materializes a dequantized
    # cache: K's scale folds into the logits AFTER the QK^T contraction
    # and V's scale folds into the probabilities BEFORE the PV one —
    # algebraically exact, oracle-tested in tests/test_kv_cache.py.
    cache_dtype: str = "compute"
    # quantized path only: compute q/k/v in ONE int8 matmul over a
    # (H + 2*Hkv, head_dim) fused kernel instead of three. Exact for
    # per-output-channel scales (quantize(concat) == concat(quantize) —
    # each output channel's absmax is untouched by the concat), and at
    # decode batch 1 the step is per-op-launch bound (~0.3 ms/layer of
    # fixed cost vs ~0.27 ms of weight streaming), so fewer launches is
    # latency. quantize_model_params merges float q/k/v kernels into
    # the fused layout.
    fused_qkv: bool = False
    # sliding window: a query sees its ``window`` newest keys, itself
    # among them (0: every key before it). The decode cache of such a
    # layer is a ring of ``window`` rows a sequence, position p in row
    # p mod window, whatever length the cache is sized for
    # (:func:`_ring_attention`).
    window: int = 0
    # RMSNorm over each head's dims of q and of k, before any rotation:
    # one gain vector of head_dim for all heads (``q_norm/scale``,
    # ``k_norm/scale``)
    qk_norm: bool = False
    norm_eps: float = 1e-5
    # causal by blocks (a block-diffusion decoder's mask, decode cache
    # only): a query at position p sees every key of its own block of
    # ``see_block`` positions and of every block before it, up to
    # ``p // see_block * see_block + see_block - 1``; rotation keeps p.
    # 0 or 1: causal. A block or two of fed tokens a row (T of
    # ``see_block`` or twice that) are a decode round over every slot,
    # written by one scatter a leaf and attended by
    # :func:`_round_attention`, as one token a row is.
    see_block: int = 0

    @nn.nowrap
    def is_block_round(self, T: int) -> bool:
        """Whether T fed tokens a row are a block decoder's round."""
        return self.see_block > 1 and T in (self.see_block,
                                            2 * self.see_block)

    @nn.nowrap
    def is_round(self, T: int) -> bool:
        """Whether T fed tokens a row are a decode round: one, or a
        block decoder's block or two."""
        return T == 1 or self.is_block_round(T)

    @nn.compact
    def __call__(self, x, mask: Optional[jax.Array] = None,
                 decode: bool = False,
                 cache_positions: Optional[jax.Array] = None,
                 lora=None, lengths: Optional[jax.Array] = None):
        """``decode=True`` enables the autoregressive KV cache (flax
        "cache" collection): initialize by calling ``model.init`` with a
        (B, max_len) input and ``decode=True`` — that sizes the cache —
        then apply with ``mutable=["cache"]`` feeding (B, 1) (or a
        (B, P) prefill chunk); keys/values land at ``cache_index``,
        rotary positions are absolute, and attention masks to the
        filled prefix. Causal-only (the cache is a running prefix).

        ``cache_positions`` (B,) int32 switches decode to *per-row*
        cache indexing: row ``i``'s fed tokens write at slot
        ``cache_positions[i]`` (its own filled length), rotary positions
        and the causal-by-index mask follow per row, and the shared
        scalar ``cache_index`` is neither read nor advanced. This is
        what lets a continuous-batching engine hold sequences at
        different decode depths in ONE batched cache (serve/engine.py)
        and what batched ragged-prompt generation reduces to
        (inference/generate.py ``prompt_lengths``). Each row's
        computation is exactly the shared-index computation for that
        row, so greedy decode stays token-identical to the sequential
        path.

        ``lora`` — per-row LoRA deltas for multi-tenant serving
        (nn/lora.py): a ``(a_q, b_q, a_v, b_v)`` tuple of per-BATCH-row
        factors (each leading dim B, already gathered from the stacked
        adapter bank by the caller). The deltas land on the q/v
        projection *outputs* before rotary and before any cache write,
        so cached KV rows embed the adapter's deltas — which is why the
        prefix cache namespaces its content addresses by adapter id. A
        zero-B adapter contributes an exact-0.0 delta: adding it leaves
        greedy decode token-identical to running without a bank.

        ``lengths`` (B,) int32: how many of the T fed tokens of each
        row are real (a left-aligned prefix; default all T). Padding
        written by absolute position lands past a row's end and is
        masked until overwritten; in a ring it would displace a real
        position, so it is not written. A blockwise prefill over rows
        by position (:func:`prefill_in_tiles`) and a decode round
        through the kernel (:func:`_round_attention`) let what is not
        real attend to nothing (its rows of the result are zeros, and a
        row with ``lengths`` 0, a slot that is not live, reads no key);
        the dense routine and the int8 cache take no notice."""
        kv_heads = self.num_kv_heads or self.num_heads
        if self.quantized:
            if self.use_bias:
                raise ValueError("quantized attention is bias-free")
            dense = lambda heads, name: Int8DenseGeneral(  # noqa: E731
                (heads, self.head_dim), axis=-1, name=name,
                dtype=self.dtype,
            )
        else:
            dense = lambda heads, name: nn.DenseGeneral(  # noqa: E731
                (heads, self.head_dim), axis=-1, name=name,
                dtype=self.dtype, param_dtype=self.param_dtype,
                use_bias=self.use_bias,
            )
        if self.quantized and self.fused_qkv:
            h = self.num_heads
            qkv = dense(h + 2 * kv_heads, "qkv")(x)
            q = qkv[..., :h, :]
            k = qkv[..., h:h + kv_heads, :]
            v = qkv[..., h + kv_heads:, :]
        else:
            q = dense(self.num_heads, "query")(x)
            k = dense(kv_heads, "key")(x)
            v = dense(kv_heads, "value")(x)
        if lora is not None:
            from pytorch_distributed_nn_tpu.nn.lora import lora_delta

            a_q, b_q, a_v, b_v = lora
            q = q + lora_delta(x, a_q, b_q)
            v = v + lora_delta(x, a_v, b_v)
        if self.qk_norm:
            # (the models package imports this module)
            from pytorch_distributed_nn_tpu.models.llama import RMSNorm

            norm = lambda name: RMSNorm(  # noqa: E731
                eps=self.norm_eps, dtype=self.dtype,
                param_dtype=self.param_dtype, name=name)
            q, k = norm("q_norm")(q), norm("k_norm")(k)
        if self.window and not self.causal:
            raise ValueError("a sliding window is causal")
        if decode and not self.causal:
            raise ValueError("decode cache requires causal attention")
        if decode and mask is not None:
            raise ValueError(
                "decode mode ignores padding masks; strip padding (or "
                "left-trim) before prefill"
            )
        if cache_positions is not None and not decode:
            raise ValueError(
                "cache_positions is a decode-cache feature (per-row "
                "cache indices); it needs decode=True"
            )
        if self.impl in ("ring", "ulysses"):
            # Sequence/context parallelism at the model level: the
            # activation's T dim is sharded over the `seq` mesh axis and
            # attention runs inside a nested shard_map (seq manual,
            # other mesh axes stay auto) — either as a KV ring or as
            # Ulysses all-to-all head-scatter (parallel/sequence.py).
            # Requires an ambient mesh (Trainer sets it when mesh.seq >
            # 1) and causal attention; rotary positions are global
            # (computed from the shard's ring index) and applied before
            # any resharding, so both schemes see identical q/k.
            if decode:
                raise ValueError(
                    f"{self.impl} attention has no decode cache; "
                    "generate with impl='auto'"
                )
            if not self.causal or mask is not None:
                raise ValueError(
                    f"{self.impl} attention is causal-only and takes "
                    "no mask"
                )
            from jax.sharding import PartitionSpec as _P

            from pytorch_distributed_nn_tpu.parallel.sequence import (
                ring_attention,
                ulysses_attention,
            )
            from pytorch_distributed_nn_tpu.runtime.mesh import AXIS_SEQ

            seq_impl = self.impl

            def attn_local(q, k, v):
                if self.rotary:
                    Tl = q.shape[1]
                    start = jax.lax.axis_index(AXIS_SEQ) * Tl
                    pos = start + jnp.arange(Tl)[None]
                    q, k = rotary_embedding(q, k, theta=self.rope_theta,
                                            positions=pos)
                    q = q.astype(self.dtype)
                    k = k.astype(self.dtype)
                if seq_impl == "ulysses":
                    return ulysses_attention(q, k, v, axis=AXIS_SEQ,
                                             causal=True)
                return ring_attention(q, k, v, axis=AXIS_SEQ,
                                      causal=True)

            # axis_names: manual over seq ONLY — without it shard_map
            # goes manual over every mesh axis and the unsharded specs
            # all-gather the batch dim over data x fsdp, silently
            # negating data parallelism at every attention layer
            # (check_vma stays on: check_vma=False combined with
            # axis_names flips every mesh axis manual and the specs
            # get rejected; ring carries are pvary'd instead)
            out = jax.shard_map(
                attn_local,
                in_specs=(_P(None, AXIS_SEQ),) * 3,
                out_specs=_P(None, AXIS_SEQ),
                axis_names={AXIS_SEQ},
            )(q, k, v)
        elif decode:
            B, T = x.shape[0], x.shape[1]
            if self.cache_dtype not in ("compute", "int8"):
                raise ValueError(
                    f"unknown cache_dtype {self.cache_dtype!r}; have "
                    "('compute', 'int8')"
                )
            int8_cache = self.cache_dtype == "int8"
            if self.window and int8_cache:
                raise ValueError("a ring cache is kept in the compute "
                                 "dtype; cache_dtype='int8' has no ring")
            init_k = nn.initializers.zeros
            # init sizes the cache from the (B, max_len) input; a window
            # layer's is its ring, whatever max_len. Rows by position in
            # the compute dtype lie flat, a position's K/V heads side by
            # side in one row: the layout a round's kernel reads in
            # place, and the one every routine but that one reshapes by
            # head (a ring and the int8 layout keep a head a row)
            kv_shape = (B, self.window or T, kv_heads, self.head_dim) \
                if self.window or int8_cache \
                else (B, T, kv_heads * self.head_dim)
            cached_k = self.variable(
                "cache", "cached_key", init_k, None, kv_shape,
                jnp.int8 if int8_cache else k.dtype,
            )
            cached_v = self.variable(
                "cache", "cached_value", init_k, None, kv_shape,
                jnp.int8 if int8_cache else v.dtype,
            )
            if int8_cache:
                k_scale = self.variable(
                    "cache", "cached_key_scale", init_k, None,
                    (B, T, kv_heads), jnp.float32,
                )
                v_scale = self.variable(
                    "cache", "cached_value_scale", init_k, None,
                    (B, T, kv_heads), jnp.float32,
                )
            cache_index = self.variable(
                "cache", "cache_index",
                lambda: jnp.zeros((), jnp.int32),
            )
            if self.is_initializing():
                # init only sizes the cache from the (B, max_len) input;
                # the out-projection just needs a correctly-shaped
                # activation, so skip the attention math entirely
                out = jnp.zeros_like(q)
            else:
                S = cached_k.value.shape[1]
                if cache_positions is None:
                    idx = cache_index.value
                    positions = idx + jnp.arange(T)[None]  # absolute
                    cache_index.value = idx + T

                    def write(buf, new):
                        with jax.named_scope("cache_write"):
                            return jax.lax.dynamic_update_slice(
                                buf, new, (0, idx) + (0,) * (buf.ndim - 2))
                else:
                    # per-row mode: each sequence advances at its own
                    # index; the shared counter stays untouched (it is
                    # meaningless across rows at different depths)
                    starts = cache_positions.astype(jnp.int32)
                    positions = starts[:, None] + jnp.arange(T)[None]

                    def write(buf, new):
                        # a block round: a row's open block, and the
                        # finished one before it or as many dead positions
                        if self.is_block_round(T):
                            return _block_update(buf, new, starts)
                        return _row_update(buf, new, starts)
                if self.rotary:
                    q, k = rotary_embedding(q, k, theta=self.rope_theta,
                                            positions=positions)
                    q, k = q.astype(self.dtype), k.astype(self.dtype)
                # attend to the filled prefix: k_pos <= this row's q_pos
                # (per-row rows are left-aligned, so slot == position)
                k_pos = jnp.arange(S)[None, None, :]
                seen = positions  # the last key position a query sees
                if self.see_block > 1:
                    if self.window or int8_cache:
                        raise ValueError(
                            "a mask by blocks is built for rows by "
                            "position in the compute dtype")
                    seen = positions // self.see_block * self.see_block \
                        + self.see_block - 1
                q_pos = seen[:, :, None]
                pos_mask = k_pos <= q_pos  # (B|1, T, S)
                if self.window:
                    # the ring has its own rows and mask; one path for
                    # both modes: the shared index is every row's start
                    out, cached_k.value, cached_v.value = _ring_attention(
                        q, k, v, cached_k.value, cached_v.value,
                        jnp.broadcast_to(positions[:, 0], (B,)),
                        jnp.full((B,), T, jnp.int32) if lengths is None
                        else lengths.astype(jnp.int32), self.dtype)
                elif int8_cache:
                    kq_new, ks_new = _quantize_kv(k)
                    vq_new, vs_new = _quantize_kv(v)
                    cached_k.value = write(cached_k.value, kq_new)
                    cached_v.value = write(cached_v.value, vq_new)
                    k_scale.value = write(k_scale.value, ks_new)
                    v_scale.value = write(v_scale.value, vs_new)
                    out = _cache_attention(
                        q, cached_k.value, cached_v.value, pos_mask,
                        self.dtype, kscale=k_scale.value,
                        vscale=v_scale.value,
                    )
                else:
                    by_head = lambda x: x.reshape(  # noqa: E731
                        x.shape[:2] + (kv_heads, self.head_dim))

                    def flat(x):   # a fed position as the leaf holds one
                        with jax.named_scope("cache_write"):
                            return x.reshape(x.shape[:2] + kv_shape[2:])

                    cached_k.value = write(cached_k.value, flat(k))
                    cached_v.value = write(cached_v.value, flat(v))
                    if prefill_in_tiles(T, S):
                        out = _prefill_attention(
                            q, by_head(cached_k.value),
                            by_head(cached_v.value), seen, lengths)
                    elif self.is_round(T):
                        out = _round_attention(
                            q, cached_k.value, cached_v.value, seen,
                            lengths, self.dtype)
                    else:
                        out = _cache_attention(
                            q, by_head(cached_k.value),
                            by_head(cached_v.value), pos_mask, self.dtype,
                        )
        else:
            if self.rotary:
                q, k = rotary_embedding(q, k, theta=self.rope_theta)
                q, k = q.astype(self.dtype), k.astype(self.dtype)
            impl = self.impl
            if self.window:
                if mask is not None:
                    raise ValueError("a window layer takes no padding "
                                     "mask; feed it decode=True")
                t = jnp.arange(x.shape[1])
                mask = (t[:, None] - t[None, :] < self.window)[None]
                impl = "xla"   # the band is a mask, which flash lacks
            causal = self.causal
            if self.see_block > 1:
                if mask is not None:
                    raise ValueError("a mask by blocks takes no other")
                t = jnp.arange(x.shape[1])
                mask = (t[None, :] <= t[:, None] // self.see_block
                        * self.see_block + self.see_block - 1)[None]
                impl, causal = "xla", False
            out = dot_product_attention(q, k, v, causal=causal,
                                        impl=impl, mask=mask)
        if self.quantized:
            return Int8DenseGeneral(
                x.shape[-1], axis=(-2, -1), name="out", dtype=self.dtype,
            )(out)
        return nn.DenseGeneral(
            x.shape[-1], axis=(-2, -1), name="out", dtype=self.dtype,
            param_dtype=self.param_dtype, use_bias=self.use_bias,
        )(out)
