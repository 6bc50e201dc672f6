"""The blockwise attention of a prefill (``ops/pallas/prefix_attention``:
MLA's expanded path, and the rows-by-position cache of
``nn/attention.MultiHeadAttention``): the Pallas kernel in interpret
mode and the ``jax.numpy`` recurrence, each against the dense masked
softmax on small MLA shapes (a qk width that differs from the v width)
and on grouped-query ones (4 and 8 query heads a K/V head, where
``nn/attention._cache_attention`` is the dense form), and the
arithmetic that says which key rows a query block visits. The compiled
kernel at the served sizes is in ``tests/test_chip_compile.py`` (no
chip) and, against the recurrence, at the end of this file (chip only:
``tests/conftest.py`` holds pytest to the CPU, so on the chip run it
with ``python -m pytest --noconftest tests/test_prefix_attention.py -k
compiled``; ``scripts/validate_tpu_kernels.py`` makes the same
comparison beside the other kernels').
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_nn_tpu.nn import attention, mla
from pytorch_distributed_nn_tpu.ops.pallas import prefix_attention as pa

H, DK, DV = 4, 24, 16
SCALE = DK ** -0.5
EXECUTIONS = {
    "kernel": functools.partial(pa._pallas, interpret=True),
    "jax_numpy": pa._blockwise,
}


def _dense(q, k, v, q_pos, scale=SCALE):
    """The plain form: every score, one softmax a query."""
    s = jnp.einsum("bhtd,bhsd->bhts", q, k) * scale
    seen = jnp.arange(k.shape[2])[None, None, None, :] \
        <= q_pos[:, None, :, None]
    p = jax.nn.softmax(jnp.where(seen, s, -1e30), axis=-1)
    return jnp.einsum("bhts,bhsd->bhtd", p, v)


def _operands(B, T, S, key=0, heads=(H, H), widths=(DK, DV)):
    ks = jax.random.split(jax.random.key(key), 3)
    return (jax.random.normal(ks[0], (B, heads[0], T, widths[0])),
            jax.random.normal(ks[1], (B, heads[1], S, widths[0])),
            jax.random.normal(ks[2], (B, heads[1], S, widths[1])))


def _dense_grouped(q, k, v, q_pos, scale):
    """The dense routine of a decode cache, which keeps K and V grouped
    (rows by position, heads second to last)."""
    rows = lambda x: x.transpose(0, 2, 1, 3)  # noqa: E731
    seen = jnp.arange(k.shape[2])[None, None, :] <= q_pos[:, :, None]
    assert scale == q.shape[-1] ** -0.5   # the routine's own
    return rows(attention._cache_attention(rows(q), rows(k), rows(v), seen,
                                           q.dtype))


def _case(name):
    """``(q, k, v, q_pos, (block_q, block_k), real queries (B, T))``."""
    if name == "whole":                     # T = S, offset 0
        q, k, v = _operands(2, 32, 32)
        pos = jnp.broadcast_to(jnp.arange(32)[None], (2, 32))
        return q, k, v, pos, (8, 8), pos >= 0
    if name == "suffix_rows_differ":        # T < S, an offset a row
        q, k, v = _operands(2, 16, 48, 1)
        pos = jnp.asarray([[20], [7]]) + jnp.arange(16)[None]
        return q, k, v, pos, (8, 16), pos >= 0
    if name == "ragged":                    # neither in whole tiles
        q, k, v = _operands(2, 27, 43, 2)
        pos = jnp.asarray([[13], [0]]) + jnp.arange(27)[None]
        return q, k, v, pos, (8, 16), pos >= 0
    if name == "padded_tail":               # queries that are no tokens
        q, k, v = _operands(2, 32, 32, 3)
        real = jnp.arange(32)[None] < jnp.asarray([[19], [8]])
        pos = jnp.where(real, jnp.arange(32)[None], -1)
        return q, k, v, pos, (8, 8), real
    if name == "nan_past_the_prefix":       # the row's tail is never read
        q, k, v = _operands(2, 16, 64, 4)
        pos = jnp.asarray([[17], [3]]) + jnp.arange(16)[None]
        k = k.at[:, :, 33:].set(jnp.nan)    # 32 is the last row seen
        v = v.at[:, :, 33:].set(jnp.nan)
        return q, k, v, pos, (8, 8), pos >= 0
    # grouped queries, dk = dv = 16: G query heads to a K/V head
    if name == "gqa4_whole":                # 8 / 2 heads, T = S
        q, k, v = _operands(2, 32, 32, 10, (8, 2), (16, 16))
        pos = jnp.broadcast_to(jnp.arange(32)[None], (2, 32))
        return q, k, v, pos, (8, 8), pos >= 0
    if name == "gqa8_whole":                # 8 / 1: the step's heads
        q, k, v = _operands(1, 32, 32, 11, (8, 1), (16, 16))   # are half
        pos = jnp.arange(32)[None]                             # a group
        return q, k, v, pos, (8, 16), pos >= 0
    if name == "gqa4_suffix_behind_restored":   # T < S, an offset a row
        q, k, v = _operands(2, 16, 48, 12, (8, 2), (16, 16))
        pos = jnp.asarray([[32], [5]]) + jnp.arange(16)[None]
        return q, k, v, pos, (8, 16), pos >= 0
    if name == "gqa8_padded_tail_ragged":   # off the tiles, padding at -1
        q, k, v = _operands(2, 27, 43, 13, (16, 2), (16, 16))
        real = jnp.arange(27)[None] < jnp.asarray([[21], [6]])
        pos = jnp.where(real, jnp.asarray([[13], [0]])
                        + jnp.arange(27)[None], -1)
        return q, k, v, pos, (8, 16), real
    if name == "gqa2_three_kv_heads":       # 6 / 3: a step is one group
        q, k, v = _operands(1, 16, 32, 14, (6, 3), (16, 16))
        pos = 9 + jnp.arange(16)[None]
        return q, k, v, pos, (8, 8), pos >= 0
    raise KeyError(name)


CASES = ("whole", "suffix_rows_differ", "ragged", "padded_tail",
         "nan_past_the_prefix", "gqa4_whole", "gqa8_whole",
         "gqa4_suffix_behind_restored", "gqa8_padded_tail_ragged",
         "gqa2_three_kv_heads")


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("execution", list(EXECUTIONS))
def test_blockwise_is_the_dense_masked_softmax(execution, case):
    """Online softmax over key blocks at or under the diagonal against
    the dense form: the same float32 sums in another order (2e-6 on
    outputs of size ~1). A query that is no token gets zeros; NaN past
    the visible prefix reaches nothing. Grouped, the dense form is the
    decode cache's own routine, which repeats nothing either."""
    q, k, v, pos, (bq, bk), real = _case(case)
    scale = q.shape[-1] ** -0.5
    got = pa._in_whole_tiles(EXECUTIONS[execution], q, k, v, pos,
                             scale=scale, block_q=bq, block_k=bk)
    finite = jnp.nan_to_num(k), jnp.nan_to_num(v)
    dense = _dense if k.shape[1] == q.shape[1] else _dense_grouped
    want = dense(q, *finite, jnp.maximum(pos, 0), scale)
    assert got.shape == want.shape and got.dtype == q.dtype
    assert bool(jnp.isfinite(got).all())
    gap = jnp.abs(got - want).max(axis=(1, 3))
    assert float(jnp.where(real, gap, 0).max()) < 2e-6
    assert float(jnp.where(real, 0, jnp.abs(got).max(axis=(1, 3))).max()) == 0


@pytest.mark.parametrize("heads", [(H, H), (8, 2), (8, 1)],
                         ids=["g1", "g4", "g8"])
@pytest.mark.parametrize("tiles", [(8, 8), (16, 8), (8, 32), (32, 16)])
def test_kernel_is_the_recurrence_in_bf16(tiles, heads):
    """The two executions on bf16 operands (float32 maximum, denominator
    and accumulator in both): the oracle's numbers to bf16's rounding
    of an output of size ~1."""
    q, k, v = (x.astype(jnp.bfloat16)
               for x in _operands(1, 32, 64, 5, heads))
    pos = 30 + jnp.arange(32)[None]
    got, want = (run(q, k, v, pos, scale=SCALE, block_q=tiles[0],
                     block_k=tiles[1]) for run in EXECUTIONS.values())
    assert got.dtype == jnp.bfloat16
    assert float(jnp.abs(got.astype(jnp.float32)
                         - want.astype(jnp.float32)).max()) < 2e-2


def _round_operands(dtype=jnp.float32, key=20):
    """A block decoder's round at a small size: five slots of a cache of
    64 rows, 4 query heads over 2 K/V heads of 16, 8 fed positions a
    row (two blocks of 4). Row 0 owes (all 8 real, from 20), row 1 owes
    nothing (4 real, from 36), row 2 is idle, row 3 stands at the
    cache's last block, row 4 in its first; the rows past what a slot
    has filled hold NaN. ``(q (B, T, H, D), k, v (B, S, Hkv * D), seen
    (B, T), lengths (B,))``."""
    B, T, S, heads, kv, d, blk = 5, 8, 64, 4, 2, 16, 4
    ks = jax.random.split(jax.random.key(key), 3)
    starts = jnp.asarray([20, 36, 0, 56, 0])
    lengths = jnp.asarray([8, 4, 0, 8, 4])
    fed = starts[:, None] + jnp.arange(T)[None]
    seen = fed // blk * blk + blk - 1
    filled = jnp.where(lengths > 0, starts + lengths, 0)
    rows = jnp.arange(S)[None, :, None] < filled[:, None, None]
    k, v = (jnp.where(rows, jax.random.normal(kk, (B, S, kv * d)), jnp.nan)
            for kk in ks[1:])
    q = jax.random.normal(ks[0], (B, T, heads, d))
    return tuple(x.astype(dtype) for x in (q, k, v)) + (seen, lengths)


@pytest.mark.parametrize("block_k", [8, 16, 64])
def test_round_attention_is_the_dense_routine_on_what_a_row_sees(block_k):
    """The round's kernel (interpret mode) on the cache as it lies, a
    position's heads side by side, against the decode cache's dense
    routine: the same float32 sums in another order. A row's key blocks
    past its last visible position are not read (they hold NaN), a
    query that is not real gets zeros, a row with none costs nothing."""
    q, k, v, seen, lengths = _round_operands()
    B, T, heads, d = q.shape
    S, kv = k.shape[1], k.shape[2] // d
    G = heads // kv
    real = jnp.arange(T)[None] < lengths[:, None]
    grouped = q.reshape(B, T, kv, G, d).transpose(0, 2, 1, 3, 4)
    got = pa.round_attention(
        grouped.reshape(B, kv, T * G, d), k, v,
        jnp.repeat(jnp.where(real, seen, -1), G, axis=1), scale=d ** -0.5,
        block_k=block_k, interpret=True)
    got = got.reshape(B, kv, T, G, d).transpose(0, 2, 1, 3, 4) \
        .reshape(q.shape)
    by_head = lambda x: jnp.nan_to_num(x).reshape(B, S, kv, d)  # noqa: E731
    want = attention._cache_attention(
        q, by_head(k), by_head(v),
        jnp.arange(S)[None, None, :] <= seen[:, :, None], q.dtype)
    assert bool(jnp.isfinite(got).all())
    gap = jnp.abs(got - want).max(axis=(2, 3))
    assert float(jnp.where(real, gap, 0).max()) < 2e-6
    assert float(jnp.where(real, 0, jnp.abs(got).max(axis=(2, 3))).max()) == 0


def test_a_round_reads_the_key_blocks_up_to_what_a_row_sees():
    """The arithmetic of ``attn_rows_read_total`` for a round through
    the kernel: a real query is charged the key blocks up to the last
    position its row's real queries see; a row with none reads none."""
    _, _, _, seen, lengths = _round_operands()
    real = jnp.arange(8)[None] < lengths[:, None]
    # the five rows see up to 27, 39, nothing, 63 and 3
    assert int(pa.round_rows_read(seen, real, 64, 16)) \
        == 8 * 32 + 4 * 48 + 0 + 8 * 64 + 4 * 16
    assert int(pa.round_rows_read(seen, real, 64, 64)) == 24 * 64


def _through_the_kernel(monkeypatch, block_k=16):
    """``nn/attention`` told that the kernel is the routine, in key
    blocks of ``block_k`` and in interpret mode; returns the (S, d) it
    was asked about."""
    asked = []
    monkeypatch.setattr(
        attention, "round_key_block",
        lambda S, heads, d, dtype: asked.append((S, d)) or block_k)
    monkeypatch.setattr(attention, "round_attention", functools.partial(
        pa.round_attention, interpret=True))
    return asked


def test_a_block_round_takes_the_kernel_on_a_tpu_in_bf16(monkeypatch):
    """``nn/attention._round_attention`` asks the backend, the dtype and
    the tiles (``round_key_block``): on the CPU the dense routine; told
    it is a TPU, bf16 operands go through the kernel (here in interpret
    mode, in key blocks of 16) and agree with the dense routine to
    bf16's rounding."""
    q, k, v, seen, lengths = _round_operands(jnp.bfloat16, 21)
    q = jnp.repeat(q, 2, axis=2)      # 32 query rows a K/V head
    k, v = jnp.nan_to_num(k), jnp.nan_to_num(v)
    assert pa.round_key_block(64, 2, 128, jnp.bfloat16) == 0
    dense = attention._round_attention(q, k, v, seen, lengths, q.dtype)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(pa, "ROUND_KEY_BLOCKS", (16,))
    assert pa.round_key_block(64, 2, 128, jnp.bfloat16) == 16
    assert pa.round_key_block(64, 2, 128, jnp.float32) == 0   # not bf16
    assert pa.round_key_block(64, 2, 64, jnp.bfloat16) == 0   # lane tiles
    assert pa.round_key_block(72, 2, 128, jnp.bfloat16) == 0  # key blocks
    # one K/V head a position: the longer block first, where it divides
    monkeypatch.setattr(pa, "ROUND_KEY_BLOCKS_ONE_HEAD", (32, 16))
    assert pa.round_key_block(64, 1, 128, jnp.bfloat16) == 32
    assert pa.round_key_block(48, 1, 128, jnp.bfloat16) == 16
    asked = _through_the_kernel(monkeypatch)
    got = attention._round_attention(q, k, v, seen, lengths, q.dtype)
    assert asked == [(64, 16)] and got.dtype == jnp.bfloat16
    real = jnp.arange(8)[None] < lengths[:, None]
    gap = jnp.abs(got.astype(jnp.float32) - dense.astype(jnp.float32))
    assert float(jnp.where(real[..., None, None], gap, 0).max()) < 2e-2


# a round of one position a row, (query heads, K/V heads, head width):
# Mistral's and LFM2's four query heads a K/V head, K-EXAONE's eight,
# Jamba's twenty to one; heads of 128, and heads of 64 that lie two a
# lane tile and are read as one head of 128
_ONE_POSITION = {"g4_d128": (8, 2, 128), "g8_d128": (16, 2, 128),
                 "g20_d128": (20, 1, 128), "g4_d64_packed": (16, 4, 64),
                 "g2_d64_packed": (4, 2, 64)}
# each slot's depth in a cache of 64 rows: ragged, the last row, the
# first, and (negative) a slot that is not live
_DEPTHS = (20, 36, -1, 63, 0, 17)


def _one_position_operands(shape, dtype, key=30):
    """``(q (B, 1, H, D), k, v (B, S, Hkv * D) with NaN past what a slot
    has filled, seen (B, 1), lengths (B,))``."""
    heads, kv, d = _ONE_POSITION[shape]
    B, S = len(_DEPTHS), 64
    ks = jax.random.split(jax.random.key(key), 3)
    depth = jnp.asarray(_DEPTHS)
    lengths = (depth >= 0).astype(jnp.int32)
    seen = jnp.maximum(depth, 0)[:, None]
    rows = (jnp.arange(S)[None, :] <= depth[:, None])[..., None]
    k, v = (jnp.where(rows, jax.random.normal(kk, (B, S, kv * d)), jnp.nan)
            for kk in ks[1:])
    q = jax.random.normal(ks[0], (B, 1, heads, d))
    return tuple(x.astype(dtype) for x in (q, k, v)) + (seen, lengths)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-6),
                                       (jnp.bfloat16, 2e-2)])
@pytest.mark.parametrize("shape", list(_ONE_POSITION))
def test_a_round_of_one_position_a_row_is_the_dense_routine(
        monkeypatch, shape, dtype, tol):
    """One fed position a row through ``_round_attention``'s kernel
    (interpret mode, key blocks of 16): the few query rows a K/V head
    are padded to a register's with rows that see nothing, heads of 64
    are laid into their own half of a lane tile, and every live row, at
    depth 0 and at the cache's last row too, gets what the dense routine
    over its rows by head gives; the rows past a slot's depth (NaN) are
    not read, and a slot that is not live gets zeros."""
    q, k, v, seen, lengths = _one_position_operands(shape, dtype)
    heads, kv, d = _ONE_POSITION[shape]
    B, S = k.shape[:2]
    by_head = lambda x: jnp.nan_to_num(x).reshape(B, S, kv, d)  # noqa: E731
    want = attention._cache_attention(
        q, by_head(k), by_head(v),
        jnp.arange(S)[None, None, :] <= seen[:, :, None], dtype)
    asked = _through_the_kernel(monkeypatch)
    got = attention._round_attention(q, k, v, seen, lengths, dtype)
    assert asked == [(S, 128)]
    assert got.shape == q.shape and got.dtype == dtype
    assert bool(jnp.isfinite(got).all())
    live = (lengths > 0)[:, None, None, None]
    gap = jnp.abs(got.astype(jnp.float32) - want.astype(jnp.float32))
    assert float(jnp.where(live, gap, 0).max()) < tol
    assert float(jnp.abs(jnp.where(live, want, 0)).max()) > 0.5
    assert float(jnp.where(live, 0, jnp.abs(got)).max()) == 0


def test_a_shared_index_round_sees_one_depth_in_every_row(monkeypatch):
    """``inference/generate``'s shared-index decode hands the routine
    ``seen`` (1, T) and no ``lengths``: every row at that depth."""
    q, k, v, _, _ = _one_position_operands("g4_d128", jnp.float32, 31)
    k, v = jnp.nan_to_num(k), jnp.nan_to_num(v)
    seen = jnp.asarray([[41]])
    dense = attention._round_attention(q, k, v, seen, None, q.dtype)
    _through_the_kernel(monkeypatch)
    got = attention._round_attention(q, k, v, seen, None, q.dtype)
    assert float(jnp.abs(got - dense).max()) < 2e-6


@pytest.mark.parametrize("shape", ["g4_d128", "g20_d128", "g4_d64_packed"])
def test_a_round_is_counted_as_the_routine_reads(monkeypatch, shape):
    """``cache_rows_read``, the counter behind ``attn_rows_read_total``:
    for one position a row it is ``round_rows_read`` (a live row's key
    blocks up to its depth, none for a slot that is not live) when the
    kernel is the routine, and every live row's whole length when the
    dense routine is: off a TPU, off bf16, off whole key blocks, or for
    a few tokens a row that are neither a round nor worth tiling."""
    heads, kv, d = _ONE_POSITION[shape]
    attn = attention.MultiHeadAttention(
        num_heads=heads, head_dim=d, num_kv_heads=kv, causal=True,
        use_bias=False, dtype=jnp.bfloat16)
    S = 256
    bound = attn.bind(jax.eval_shape(
        lambda: attn.init(jax.random.key(0), jnp.zeros((6, S, 32)),
                          decode=True)))
    assert bound.get_variable("cache", "cached_key").shape == (6, S, kv * d)
    depth = jnp.asarray([20, 136, 5, 255, 0, 130])
    real = jnp.asarray([1, 1, 0, 1, 1, 1], bool)[:, None]
    seen = depth[:, None]
    assert int(attention.cache_rows_read(bound, 1, seen, real)) == 5 * S
    def blocks(*rows):
        monkeypatch.setattr(pa, "ROUND_KEY_BLOCKS", rows)
        monkeypatch.setattr(pa, "ROUND_KEY_BLOCKS_ONE_HEAD", rows)

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    blocks(128)
    want = int(pa.round_rows_read(seen, real, S, 128))
    assert want == 128 + 256 + 0 + 256 + 128 + 256
    assert int(attention.cache_rows_read(bound, 1, seen, real)) == want
    # three tokens a row: not a round
    assert int(attention.cache_rows_read(
        bound, 3, seen + jnp.arange(3), jnp.broadcast_to(real, (6, 3)))) \
        == 15 * S
    blocks(96)      # not whole blocks
    assert int(attention.cache_rows_read(bound, 1, seen, real)) == 5 * S
    blocks(128)
    f32 = attn.clone(dtype=jnp.float32)
    f32 = f32.bind(jax.eval_shape(
        lambda: f32.init(jax.random.key(0), jnp.zeros((6, S, 32)),
                         decode=True)))
    assert int(attention.cache_rows_read(f32, 1, seen, real)) == 5 * S


def _latent_operands(B, T, S, dn=16, dr=8, dv=16, r=16, key=6):
    ks = jax.random.split(jax.random.key(key), 5)
    return (jax.random.normal(ks[0], (B, T, H, dn)),
            jax.random.normal(ks[1], (B, T, H, dr)),
            jax.random.normal(ks[2], (B, S, r)),
            jax.random.normal(ks[3], (B, S, dr)),
            jax.random.normal(ks[4], (r, H, dn + dv)) / 4)


@pytest.mark.parametrize("tiles", [(512, 1024), (8, 16), (5, 7)])
def test_expanded_attention_is_the_two_term_dense_form(tiles):
    """``expanded_attention`` (one product over ``[nope | rope]``, K and
    V expanded once) against the two score terms written out, for a
    suffix at a different offset a row with a padded tail."""
    q_nope, q_rope, latent, rope_key, w_kvb = _latent_operands(2, 12, 40)
    real = jnp.arange(12)[None] < jnp.asarray([[12], [7]])
    pos = jnp.asarray([[25], [4]]) + jnp.arange(12)[None]
    got = mla.expanded_attention(
        q_nope, q_rope, latent, rope_key, w_kvb, pos, scale=24 ** -0.5,
        real=real, query_block=tiles[0], key_block=tiles[1])
    kv = jnp.einsum("bsr,rhk->bshk", latent, w_kvb)
    s = jnp.einsum("bthk,bshk->bhts", q_nope, kv[..., :16]) \
        + jnp.einsum("bthk,bsk->bhts", q_rope, rope_key)
    seen = jnp.arange(40)[None, None, None, :] <= pos[:, None, :, None]
    p = jax.nn.softmax(jnp.where(seen, s * 24 ** -0.5, -1e30), axis=-1)
    want = jnp.einsum("bhts,bshk->bthk", p, kv[..., 16:])
    assert got.shape == (2, 12, H, 16)
    assert float(jnp.where(real[..., None, None],
                           jnp.abs(got - want), 0).max()) < 2e-6
    assert float(jnp.where(real[..., None, None], 0, got).max()) == 0


def test_a_whole_prompt_visits_136_of_256_tile_pairs():
    """The arithmetic of the counter ``attn_rows_read_total`` for a
    prefill: 8,192 positions in tiles of 512 x 512 visit the tiles at
    or under the diagonal; at 1,024 keys a tile a query block reads to
    the end of the tile its last position lies in; a suffix behind
    6,144 restored rows reads those and its own tile; padding reads
    nothing."""
    whole = jnp.arange(8192)[None]
    assert int(pa.rows_visited(whole, 8192, 512, 512).sum()) \
        == 136 * 512 * 512
    rows = np.asarray(pa.rows_visited(whole, 8192, 512, 1024))[0]
    assert rows[0] == rows[1023] == 1024 and rows[1024] == 2048
    assert rows[-1] == 8192
    suffix = 6144 + jnp.arange(512)[None]
    assert set(np.asarray(pa.rows_visited(suffix, 8192, 512, 1024))[0]) \
        == {7168}
    # a prompt of 6,524 in a bucket of 8,192: the blocks of padding
    # visit nothing, the last real block reads up to its last token
    real = whole < 6524
    read = int(pa.rows_read(whole, real, 8192))
    assert read == 512 * 1024 * (1 + 1 + 2 + 2 + 3 + 3 + 4 + 4 + 5 + 5
                                 + 6 + 6) + (6524 - 6144) * 7168
    attended = 6524 * 6525 // 2
    assert 0.85 < attended / read < 0.87
    # a row cache shorter than a key tile is read whole, no further
    assert int(pa.rows_read(jnp.arange(16)[None],
                                      jnp.arange(16)[None] < 9, 64)) \
        == 9 * 64


def test_which_execution_a_shape_takes(monkeypatch):
    """The dispatcher picks by what it can observe: off a TPU the
    recurrence; on one, the kernel where the tiles lay out (the served
    shapes) and the recurrence where they do not."""
    assert pa._kernel_tiles(192, 128, 512, 1024)       # the served form
    assert pa._kernel_tiles(128, 128, 512, 1024)       # grouped heads
    assert pa._kernel_tiles(192, 128, 256, 256)        # a bucket of 256
    assert not pa._kernel_tiles(192, 128, 50, 50)      # T = 50, uncached
    assert not pa._kernel_tiles(24, 16, 8, 8)          # this file's sizes
    calls = []
    monkeypatch.setattr(pa, "_pallas", lambda *a, **k: calls.append(
        "kernel") or pa._blockwise(*a, **k))
    q, k, v = _operands(1, 8, 8)
    pa.prefix_attention(q, k, v, jnp.arange(8)[None], scale=SCALE,
                        block_q=512, block_k=1024)
    assert calls == []
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    pa.prefix_attention(q, k, v, jnp.arange(8)[None], scale=SCALE,
                        block_q=512, block_k=1024)
    assert calls == []
    ks = jax.random.split(jax.random.key(7), 3)
    pa.prefix_attention(
        jax.random.normal(ks[0], (1, 1, 32, 8)),
        jax.random.normal(ks[1], (1, 1, 128, 8)),
        jax.random.normal(ks[2], (1, 1, 128, 128)),
        96 + jnp.arange(32)[None], scale=1.0, block_q=16, block_k=128)
    assert calls == ["kernel"]
    # one level up, which routine a cached call over rows by position
    # takes, by its shape alone: a decode round and a bucket whose dense
    # scores are small (up to 512 x 512 a head: where the dense routine
    # was not slower on the chip) keep the dense routine
    for T, S, tiles in [(1, 4096, False), (1, 1 << 20, False),
                        (32, 32, False), (64, 64, False),
                        (512, 512, False), (16, 4096, False),
                        (1024, 1024, True), (512, 4096, True),
                        (4096, 4096, True), (16, 32768, True)]:
        assert attention.prefill_in_tiles(T, S) == tiles, (T, S)


@pytest.mark.skipif(jax.default_backend() != "tpu",
                    reason="the compiled kernel needs the chip")
@pytest.mark.parametrize("heads,dk,S,T,first", [
    ((64, 64), 192, 8192, 8192, 0), ((64, 64), 192, 8192, 512, 6144),
    ((32, 8), 128, 4096, 4096, 0), ((64, 8), 128, 4096, 2048, 1024)])
def test_compiled_kernel_is_the_recurrence_at_the_served_size(heads, dk, S,
                                                              T, first):
    """In bf16 at the served tiles: 64 heads of 192 / 128 against 8,192
    rows, a whole prompt and a suffix behind 6,144 restored rows; 32 and
    64 query heads of 128 / 128 to 8 K/V heads against 4,096 rows."""
    ks = jax.random.split(jax.random.key(8), 3)
    q = jax.random.normal(ks[0], (1, heads[0], T, dk), jnp.bfloat16)
    k = jax.random.normal(ks[1], (1, heads[1], S, dk), jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, heads[1], S, 128), jnp.bfloat16)
    pos = first + jnp.arange(T)[None]
    kw = dict(scale=dk ** -0.5, block_q=pa.QUERY_BLOCK,
              block_k=pa.KEY_BLOCK)
    got = jax.jit(functools.partial(pa._pallas, **kw))(q, k, v, pos)
    want = jax.jit(functools.partial(pa._blockwise, **kw))(q, k, v, pos)
    assert float(jnp.abs(got.astype(jnp.float32)
                         - want.astype(jnp.float32)).max()) < 2e-2
