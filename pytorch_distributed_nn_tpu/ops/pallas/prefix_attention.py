"""Attention over each query's visible prefix, blockwise.

``softmax(q k^T) v`` where key ``s`` is visible to query ``t`` iff
``s <= q_pos[b, t]``: a prefill against a row cache (``q_pos`` is where
each fed token stands, so a suffix behind restored rows is an offset)
and the uncached causal forward (``q_pos = arange(T)``) alike. Scores
are computed a ``(block_q, block_k)`` tile at a time under a running
maximum and denominator (online softmax), and a query block visits only
the key blocks at or under its largest position: nothing past the
visible prefix is multiplied, and a tile of scores never outlives the
step that made it. The qk width may differ from the v width (MLA: 192
and 128), and K and V may have fewer heads than q (grouped queries: H =
G x Hkv, K/V head ``h // G`` serves query head ``h``, nothing is
repeated). Forward only.

One routine in two executions: :func:`_pallas` (a TPU kernel: the tile
lives in VMEM, memory sees q, K, V and the output only) and
:func:`_blockwise` (``jax.numpy``, the same recurrence: the CPU's path,
the path of a shape the kernel cannot tile, and the kernel's oracle).
:func:`prefix_attention` picks by what it can observe, the backend and
the shapes.

:func:`round_attention` is the same recurrence for a decode round that
feeds every row of a batched cache a few positions (one; a block
decoder's block or two): the keys and values are read where the cache
holds them, rows by position with a position's heads side by side, a
grid step a row and a key block, and a row's key blocks past its last
visible position are not read (none of a row that is not live), where
the dense routine scores every row's whole padded length and writes
the float32 scores out.
"""

from __future__ import annotations

import functools
import logging

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

log = logging.getLogger(__name__)

NEG_INF = -1e30
STAT_LANES = 128   # minor dim of the m / l scratch: one whole lane tile
# the tiles (queries x keys a head), chosen on the chip at 64 heads of
# 192 / 128 and checked again at 32 / 8 heads of 128 (PERF.md sec. 6 has
# the tables)
QUERY_BLOCK = 512
KEY_BLOCK = 1024
# query heads a grid step: a tile of one head is a few microseconds of
# matrix work, little over the step's own overhead (1 -> 4 heads: -18 %
# at 512 x 512 on the chip, PERF.md sec. 6). Grouped, they are whole
# groups or a part of one (four of Mistral's: one K/V head a step)
HEADS_A_STEP = 4
# four heads' double-buffered tiles at 512 x 1,024 pass the compiler's
# 16 MiB default; a v5e core has 128 MiB
VMEM_LIMIT_BYTES = 64 * 2 ** 20
# key rows a grid step of :func:`round_attention`: a step moves a
# block of K and of V for every head, 2 KB a row at 4 heads of 128 in
# bf16, so 512 rows are 1 MB and ~1.3 us of the chip's bandwidth over a
# step's ~0.35 us, and a row reads half a block past what it has filled
ROUND_KEY_BLOCKS = (512, 256, 128)
# where a position holds one K/V head of 128 (Jamba), 512 rows are 128
# KB a step, under the step's own overhead: 1,024 first (206 -> 172 us a
# layer at 64 slots x 4,096 on the chip, the dense routine 182; at 4 and
# 8 heads a position 512 is ahead of 1,024 by 2-17 % and of 256 by 6-38
# %: PERF.md sec. 6)
ROUND_KEY_BLOCKS_ONE_HEAD = (1024,) + ROUND_KEY_BLOCKS
# query rows a K/V head that kernel takes in whole: a bf16 register's
# sublanes
QUERY_ROWS = 16


def tiles(T: int, S: int, block_q: int, block_k: int) -> tuple:
    """``(block_q, block_k)`` as run for T queries against S keys."""
    return min(block_q, T), min(block_k, S)


def block_bounds(q_pos, S: int, block_q: int):
    """``(lo, hi)``, each (B, blocks): the smallest and the largest
    position of every query block, cut to the S keys there are. A
    negative position marks a query that sees nothing (padding), so a
    block of such queries has ``hi`` < 0 and visits no key block. The
    queries are padded to whole blocks with such positions."""
    B, T = q_pos.shape
    nq = -(-T // block_q)
    pos = jnp.minimum(q_pos.astype(jnp.int32), S - 1)
    pos = jnp.pad(pos, ((0, 0), (0, nq * block_q - T)), constant_values=-1)
    pos = pos.reshape(B, nq, block_q)
    return pos.min(-1), pos.max(-1)


def rows_visited(q_pos, S: int, block_q: int, block_k: int):
    """(B, T): the key rows of the blocks each query's block visits
    (what the routine reads for that query; the rows inside its mask
    are ``q_pos + 1``). Arithmetic on positions alone."""
    T = q_pos.shape[1]
    bq, bk = tiles(T, S, block_q, block_k)
    _, hi = block_bounds(q_pos, S, bq)
    rows = jnp.where(hi < 0, 0, jnp.minimum((hi // bk + 1) * bk, S))
    return jnp.repeat(rows, bq, axis=1)[:, :T]


def seen_from(q_pos, real):
    """Positions with the padding's at -1: a query that sees nothing."""
    return q_pos if real is None else jnp.where(real, q_pos, -1)


def rows_read(q_pos, real, S: int, block_q: int = QUERY_BLOCK,
              block_k: int = KEY_BLOCK):
    """Key rows the routine reads for the ``real`` (B, T) queries of a
    call whose other queries stand at -1 (:func:`seen_from`), summed:
    for each, the rows of the key blocks its query block visits (the
    rows inside its mask are ``q_pos + 1``)."""
    return jnp.where(real, rows_visited(seen_from(q_pos, real), S, block_q,
                                        block_k), 0).sum()


def _update(s, v, m_prev, l_prev, acc_prev):
    """One key block into the running softmax: float32 scores ``s``
    (..., tq, tk) (masked entries at :data:`NEG_INF`), ``v`` (..., tk,
    dv); the probabilities are cast to v's type before ``p v``."""
    m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + p.sum(-1, keepdims=True)
    pv = jax.lax.dot_general(
        p.astype(v.dtype), v,
        (((p.ndim - 1,), (v.ndim - 2,)),
         (tuple(range(p.ndim - 2)),) * 2),
        preferred_element_type=jnp.float32)
    return m_new, l_new, acc_prev * corr + pv


def _blockwise(q, k, v, q_pos, *, scale: float, block_q: int, block_k: int):
    """The recurrence in ``jax.numpy``: a scan over key blocks inside a
    map over query blocks, a key block past the query block's largest
    position skipped. T and S in whole blocks, positions under S. The
    G query heads of a K/V head are G times the query rows of that
    head, each block of them at the same positions."""
    B, Hq, Tq, _ = q.shape
    H, S, dv = k.shape[1], k.shape[2], v.shape[-1]
    G = Hq // H
    T = G * Tq
    q = q.reshape(B, H, T, -1)
    nq = T // block_q
    pos = jnp.concatenate([q_pos.astype(jnp.int32)] * G, axis=1)
    _, his = block_bounds(pos, S, block_q)

    def query_block(args):
        qb, pb, hi = args   # (B, H, bq, dk), (B, bq), (B,)

        def key_block(carry, first):
            def visit(carry):
                kb = jax.lax.dynamic_slice_in_dim(k, first, block_k, 2)
                vb = jax.lax.dynamic_slice_in_dim(v, first, block_k, 2)
                s = jnp.einsum("bhtd,bhsd->bhts", qb, kb,
                               preferred_element_type=jnp.float32) * scale
                k_pos = first + jnp.arange(block_k)
                s = jnp.where(k_pos[None, None, None, :]
                              <= pb[:, None, :, None], s, NEG_INF)
                # a row past every query of the block has weight 0;
                # zeroed, so that whatever lies there (0 x NaN) stays out
                vb = jnp.where((k_pos[None, :] <= hi[:, None])
                               [:, None, :, None], vb, 0)
                return _update(s, vb, *carry)

            return jax.lax.cond(first <= hi.max(), visit, lambda c: c,
                                carry), None

        init = (jnp.full((B, H, block_q, 1), NEG_INF, jnp.float32),
                jnp.zeros((B, H, block_q, 1), jnp.float32),
                jnp.zeros((B, H, block_q, dv), jnp.float32))
        (_, l, acc), _ = jax.lax.scan(key_block, init,
                                      jnp.arange(0, S, block_k))
        out = jnp.where(pb[:, None, :, None] >= 0,
                        acc / jnp.maximum(l, 1e-30), 0)
        return out.astype(q.dtype)

    out = jax.lax.map(query_block, (
        jnp.moveaxis(q.reshape(B, H, nq, block_q, -1), 2, 0),
        jnp.moveaxis(pos.reshape(B, nq, block_q), 1, 0), his.T))
    return jnp.moveaxis(out, 0, 2).reshape(B, Hq, Tq, dv)


def _kernel(lo_ref, hi_ref, q_ref, k_ref, v_ref, pos_ref, o_ref,
            m_scr, l_scr, acc_scr, *, scale: float, block_k: int):
    """One (b, head group, qi, kj) grid step. The kj dimension runs in
    order, so the float32 running maximum, denominator and accumulator
    live in VMEM scratch across the key blocks of a query block. ``lo``
    and ``hi`` (scalar prefetch, (B, blocks)) bound the query block's
    positions: a key block past ``hi`` is neither fetched (the index
    map repeats the last visited block) nor multiplied; one at or under
    ``lo`` is visible whole and needs no mask. The step's query heads
    share its K/V heads in order, ``group`` to one."""
    b, qi, kj = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    lo, hi = lo_ref[b, qi], hi_ref[b, qi]
    first = kj * block_k
    heads, block_q = q_ref.shape[0], q_ref.shape[1]
    group = heads // k_ref.shape[0]

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def visit(masked: bool):
        if masked:
            k_pos = first + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            visible = k_pos <= pos_ref[...]
            v_seen = first + jax.lax.broadcasted_iota(
                jnp.int32, (block_k, 1), 0) <= hi
        for h in range(heads):
            v = v_ref[h // group]
            s = jax.lax.dot_general(
                q_ref[h], k_ref[h // group], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            if masked:
                s = jnp.where(visible, s, NEG_INF)
                v = jnp.where(v_seen, v, jnp.zeros_like(v))
            m, l, acc = _update(s, v, m_scr[h][:, :1], l_scr[h][:, :1],
                                acc_scr[h])
            m_scr[h] = jnp.broadcast_to(m, m_scr.shape[1:])
            l_scr[h] = jnp.broadcast_to(l, l_scr.shape[1:])
            acc_scr[h] = acc

    last = first + block_k - 1
    pl.when((first <= hi) & (last <= lo))(lambda: visit(False))
    pl.when((first <= hi) & (last > lo))(lambda: visit(True))

    @pl.when(kj == pl.num_programs(3) - 1)
    def _emit():
        sees = pos_ref[...] >= 0
        for h in range(heads):
            out = acc_scr[h] / jnp.maximum(l_scr[h][:, :1], 1e-30)
            o_ref[h] = jnp.where(sees, out, 0).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "block_q", "block_k",
                                             "heads", "interpret"))
def _pallas(q, k, v, q_pos, *, scale: float, block_q: int, block_k: int,
            heads: int = HEADS_A_STEP, interpret: bool = False):
    """The kernel. T and S in whole blocks, positions under S. Jitted,
    so that the attentions of one program (7 or 8, the same shapes) are
    traced and lowered once: each costs ~0.2 s of a process's set-up
    otherwise, compile cache or not."""
    B, H, T, dk = q.shape
    S, dv = k.shape[2], v.shape[-1]
    G = H // k.shape[1]
    # whole groups a step, or a part of one: its K/V heads are whole
    heads = next(n for n in range(min(heads, H), 0, -1)
                 if H % n == 0 and (n % G == 0 or G % n == 0))
    kv_heads = max(heads // G, 1)
    steps = G * kv_heads // heads    # grid steps that share K/V heads
    nq, nk = T // block_q, S // block_k
    lo, hi = block_bounds(q_pos, S, block_q)
    pos = q_pos.astype(jnp.int32)[..., None]

    def q_map(b, h, qi, kj, lo_ref, hi_ref):
        return (b, h, qi, 0)

    def kv_map(b, h, qi, kj, lo_ref, hi_ref):
        seen = jnp.maximum(hi_ref[b, qi], 0) // block_k
        return (b, h // steps if steps > 1 else h, jnp.minimum(kj, seen), 0)

    visited = T * S // (2 if T == S else 1)   # score entries, roughly
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, block_k=block_k),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, H // heads, nq, nk),
            in_specs=[
                pl.BlockSpec((None, heads, block_q, dk), q_map),
                pl.BlockSpec((None, kv_heads, block_k, dk), kv_map),
                pl.BlockSpec((None, kv_heads, block_k, dv), kv_map),
                pl.BlockSpec((None, block_q, 1),
                             lambda b, h, qi, kj, lo_ref, hi_ref:
                             (b, qi, 0)),
            ],
            out_specs=pl.BlockSpec((None, heads, block_q, dv), q_map),
            scratch_shapes=[
                pltpu.VMEM((heads, block_q, STAT_LANES), jnp.float32),
                pltpu.VMEM((heads, block_q, STAT_LANES), jnp.float32),
                pltpu.VMEM((heads, block_q, dv), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((B, H, T, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        cost_estimate=pl.CostEstimate(
            flops=2 * B * H * visited * (dk + dv),
            bytes_accessed=(B * H * T * (dk + dv)   # a K/V tile a step
                            + B * H // heads * kv_heads
                            * visited // block_q * (dk + dv))
            * q.dtype.itemsize,
            transcendentals=B * H * visited),
        interpret=interpret,
        name="prefix_attention",
    )(lo, hi, q, k, v, pos)


def _kernel_tiles(dk: int, dv: int, bq: int, bk: int) -> bool:
    """Whether the kernel can tile these shapes: a score tile of whole
    (8, 128) registers, sublanes whole in bf16 too, and heads of whole
    lane tiles or of half a one (64: LFM2's; the core pads a head's
    tile to 128 lanes, memory sees the 64)."""
    return bq % 16 == 0 and bk % 128 == 0 and dk % 8 == 0 and dv % 64 == 0


@functools.lru_cache(maxsize=None)
def _log_execution(execution: str, shape: tuple, kv_heads: int, S: int,
                   bq: int, bk: int):
    log.info("prefix_attention: %s, q %s against %d keys of %d heads in "
             "tiles of %d x %d", execution, shape, S, kv_heads, bq, bk)


def _in_whole_tiles(run, q, k, v, q_pos, *, scale: float, block_q: int,
                    block_k: int):
    """``run`` (either execution) on T and S padded to whole tiles:
    queries that see nothing, keys no query can see."""
    T, S = q.shape[2], k.shape[2]
    pad_t, pad_s = -T % block_q, -S % block_k
    rows = lambda x, n: jnp.pad(  # noqa: E731
        x, ((0, 0), (0, 0), (0, n), (0, 0)))
    pos = jnp.pad(jnp.minimum(q_pos.astype(jnp.int32), S - 1),
                  ((0, 0), (0, pad_t)), constant_values=-1)
    return run(rows(q, pad_t), rows(k, pad_s), rows(v, pad_s), pos,
               scale=scale, block_q=block_q, block_k=block_k)[:, :, :T]


def prefix_attention(q, k, v, q_pos, *, scale: float,
                     block_q: int = QUERY_BLOCK, block_k: int = KEY_BLOCK):
    """q (B, H, T, dk), k (B, Hkv, S, dk), v (B, Hkv, S, dv) with H a
    multiple of Hkv (K/V head ``h // (H / Hkv)`` is query head h's),
    q_pos (B, T): key s is visible to query t iff ``s <= q_pos[b, t]``;
    a query at a negative position sees nothing and gets zeros. Returns
    (B, H, T, dv) in q's dtype. On a TPU with tiles the kernel can lay
    out, the kernel; otherwise the same recurrence in ``jax.numpy``.
    Logs once a shape which of the two a program lowered with."""
    T, S = q.shape[2], k.shape[2]
    if q.shape[1] % k.shape[1] or k.shape[:3] != v.shape[:3]:
        raise ValueError(f"{q.shape[1]} query heads over K {k.shape} and "
                         f"V {v.shape}: not whole groups")
    bq, bk = tiles(T, S, block_q, block_k)
    kernel = jax.default_backend() == "tpu" \
        and _kernel_tiles(q.shape[-1], v.shape[-1], bq, bk)
    _log_execution("Pallas kernel" if kernel else "jax.numpy",
                   tuple(q.shape), k.shape[1], S, bq, bk)
    return _in_whole_tiles(_pallas if kernel else _blockwise, q, k, v,
                           q_pos, scale=scale, block_q=bq, block_k=bk)


def _round_kernel(hi_ref, at_ref, q_ref, k_ref, v_ref, pos_ref, o_ref,
                  m_scr, l_scr, acc_scr, *, scale: float, block_k: int):
    """One (row, kj) grid step of :func:`round_attention`: every K/V
    head's tile of the key block, cut from the block's lanes, against
    that head's query rows. ``hi`` (scalar prefetch, (B,)) is the last
    position any query of the row sees: a key block past it is neither
    fetched (``at``, the index map's) nor multiplied, and a row with
    none (``hi`` < 0) costs its steps' overhead alone."""
    b, kj = pl.program_id(0), pl.program_id(1)
    hi = hi_ref[b]
    first = kj * block_k
    heads, rows, d = q_ref.shape

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(first <= hi)
    def _visit():
        k_pos = first + jax.lax.broadcasted_iota(
            jnp.int32, (rows, block_k), 1)
        visible = k_pos <= pos_ref[...]
        v_seen = first + jax.lax.broadcasted_iota(
            jnp.int32, (block_k, 1), 0) <= hi
        for h in range(heads):
            v = v_ref[:, h * d:(h + 1) * d]
            s = jax.lax.dot_general(
                q_ref[h], k_ref[:, h * d:(h + 1) * d],
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            m, l, acc = _update(
                jnp.where(visible, s, NEG_INF),
                jnp.where(v_seen, v, jnp.zeros_like(v)),
                m_scr[h][:, :1], l_scr[h][:, :1], acc_scr[h])
            m_scr[h] = jnp.broadcast_to(m, m_scr.shape[1:])
            l_scr[h] = jnp.broadcast_to(l, l_scr.shape[1:])
            acc_scr[h] = acc

    @pl.when(kj == pl.num_programs(1) - 1)
    def _emit():
        sees = pos_ref[...] >= 0
        for h in range(heads):
            out = acc_scr[h] / jnp.maximum(l_scr[h][:, :1], 1e-30)
            o_ref[h] = jnp.where(sees, out, 0).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "block_k",
                                             "interpret"))
def round_attention(q, k, v, q_pos, *, scale: float, block_k: int,
                    interpret: bool = False):
    """Attention of a decode round over a batched cache, as a kernel.
    q (B, Hkv, R, d): a row's R query rows a K/V head (its fed
    positions times the query heads of the group, in any order); k, v
    (B, S, Hkv * d): the cache as it lies, rows by position, a
    position's K/V heads side by side, S in whole blocks of ``block_k``
    (:func:`round_key_block`); q_pos (B, R): key s is visible to query
    row r iff ``s <= q_pos[b, r]``, a negative position sees nothing and
    gets zeros. Returns (B, Hkv, R, d) in q's dtype. R that is not
    whole registers (one fed position a row: the group's few query
    heads) is padded to them with query rows that see nothing: a few KB
    beside the key blocks."""
    rows = q.shape[2]
    pad = -rows % QUERY_ROWS
    if pad:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0)))
        q_pos = jnp.pad(q_pos, ((0, 0), (0, pad)), constant_values=-1)
    B, H, R, d = q.shape
    S = k.shape[1]
    nk = S // block_k
    pos = jnp.minimum(q_pos.astype(jnp.int32), S - 1)
    hi = pos.max(axis=-1)
    # the key block a grid step holds: its own where the step visits one,
    # else the one the last visiting step before it held (the pipeline
    # fetches a block only when the index moves, so a step that visits
    # nothing, be it past a row's depth or in a row that is not live,
    # moves no byte), as a step's number in the grid's order
    steps = jnp.arange(B * nk, dtype=jnp.int32).reshape(B, nk)
    at = jax.lax.cummax(
        jnp.where(steps % nk * block_k <= hi[:, None], steps, 0).reshape(-1),
        axis=0).reshape(B, nk)

    def row_map(b, kj, hi_ref, at_ref):
        return (b, 0, 0, 0)

    def kv_map(b, kj, hi_ref, at_ref):
        return (at_ref[b, kj] // nk, at_ref[b, kj] % nk, 0)

    out = pl.pallas_call(
        functools.partial(_round_kernel, scale=scale, block_k=block_k),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, nk),
            in_specs=[
                pl.BlockSpec((None, H, R, d), row_map),
                pl.BlockSpec((None, block_k, H * d), kv_map),
                pl.BlockSpec((None, block_k, H * d), kv_map),
                pl.BlockSpec((None, R, 1),
                             lambda b, kj, hi_ref, at_ref: (b, 0, 0)),
            ],
            out_specs=pl.BlockSpec((None, H, R, d), row_map),
            scratch_shapes=[
                pltpu.VMEM((H, R, STAT_LANES), jnp.float32),
                pltpu.VMEM((H, R, STAT_LANES), jnp.float32),
                pltpu.VMEM((H, R, d), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((B, H, R, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="round_attention",
    )(hi, at, q, k, v, pos[..., None])
    return out[:, :, :rows] if pad else out


def round_key_block(S: int, heads: int, d: int, dtype) -> int:
    """The key rows a grid step of :func:`round_attention` takes for
    ``heads`` K/V heads of width d a position against S cached rows, or
    0 where the kernel is not the routine: off a TPU, off bf16, or off
    the tiles (whole lane tiles of a head, whole blocks of keys)."""
    if jax.default_backend() != "tpu" or d % 128 \
            or jnp.dtype(dtype) != jnp.bfloat16:
        return 0
    blocks = ROUND_KEY_BLOCKS_ONE_HEAD if heads == 1 else ROUND_KEY_BLOCKS
    return next((n for n in blocks if S % n == 0), 0)


def round_rows_read(q_pos, real, S: int, block_k: int):
    """Key rows :func:`round_attention` reads for the ``real`` (B, T)
    queries of a round, summed: for each, the key blocks up to the last
    position its row's real queries see (the rows inside its own mask
    are ``q_pos + 1``)."""
    hi = jnp.where(real, jnp.minimum(q_pos, S - 1), -1).max(axis=-1)
    return (real.sum(axis=-1)
            * jnp.where(hi < 0, 0, (hi // block_k + 1) * block_k)).sum()
