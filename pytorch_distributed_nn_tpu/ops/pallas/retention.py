"""Power retention's matrix-valued state, read once and written once.

The state of one key-value head of one sequence is ``S = sum_s decay
phi(k_s) v_s^T``, ``(D, head_dim)`` float32, ``phi`` the symmetric
square (``nn/retention.py`` has the equations). Two kernels, each with
its ``jax.numpy`` form beside it in :mod:`nn.retention` as oracle and as
the CPU's path:

- :func:`step`, the op ``retention_step``: a decode round's ``S <- g S
  + phi(k) v^T`` and the group's outputs ``phi(q_h)^T S`` from the
  updated tile while it is on the core. A grid step moves one head of
  one row in and out, 4.5 MB each way at a head of 128, in place (the
  cache is donated and the state aliased). A row that is not active is
  not moved: its grid steps are given the block of the active row
  before it, which the pipeline holds already, and compute nothing.
- :func:`chunk`, the op ``retention_chunk``: a prefill's sequential
  part. A head's state stays on the core through the chunks of a call
  (read before the first, written after the last); a grid step takes a
  chunk's queries against the state before the chunk (``phi(Q) S``,
  on the matrix unit) and then advances the state by the chunk's keys
  and decayed values (``G S + phi(K)^T V``). What a chunk's positions
  see of each other is ordinary products, left to XLA outside.

**The layout of** ``phi`` (:func:`layout`). ``phi(a)`` holds ``a_i a_j``
for ``i`` in order and, for each ``i``, ``j`` from the start of ``i``'s
tile of 8 to the end: a *slab* of ``J_i = head_dim - 8 (i // 8)`` rows,
whole (8, 128) tiles. At a head of 128 that is 8,704 rows: the 8,256
distinct monomials and, twice, the 448 below the diagonal of the sixteen
8 x 8 diagonal tiles. A pair inside a diagonal tile (held as ``(i, j)``
and as ``(j, i)``) weighs 1 and a pair beyond it ``sqrt 2``, so that
``phi(a) . phi(b) = (a . b)^2``: the weight follows the tile and not
the row, and a slab's coefficients are its block's. Slab ``i`` against
``a`` is then ``a_i`` times a column ``c_j a_j``, which is how both
kernels build ``phi`` without ever laying it out: from ``a`` spread over
the lanes (the step) or from a column of the chunk's queries spread
over them (the chunk).

The chunk's product takes a slab in a *window* of ``head_dim`` rows that
ends with it, so that every product is ``(rows, 128) x (128, 128)`` off
whole lanes: the rows of the window before the slab belong to the slab
before and meet coefficients that are 0.

Forward only: no VJP.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE = 8           # rows a register
LANE_TILE = 128
SQRT2 = math.sqrt(2.0)
# a head's state, in and out and twice buffered, is 17.8 MiB at a head
# of 128, over the compiler's 16 MiB default; a v5e core has 128 MiB
VMEM_LIMIT_BYTES = 64 * 2 ** 20


@functools.lru_cache(maxsize=None)
def layout(hd: int) -> dict:
    """Where ``phi`` keeps what, for a head of ``hd`` (whole tiles of
    8): ``i``, ``j`` (D,) the pair of each row, ``coef`` (D,) its
    weight, ``offsets`` (hd,) where each slab starts, ``D``; ``at`` and
    ``held`` (hd, hd) the row and the weight of each pair."""
    if hd % TILE:
        raise ValueError(f"a head of {hd} is not whole tiles of {TILE}")
    ii, jj, cc, offsets = [], [], [], []
    for i in range(hd):
        lo = i // TILE * TILE
        offsets.append(len(ii))
        for j in range(lo, hd):
            ii.append(i)
            jj.append(j)
            cc.append(1.0 if j < lo + TILE else SQRT2)
    i, j = np.asarray(ii, np.int32), np.asarray(jj, np.int32)
    coef = np.asarray(cc, np.float32)
    # the way back: where pair (i, j) lies, and its weight (0: not held)
    at, held = np.zeros((hd, hd), np.int32), np.zeros((hd, hd), np.float32)
    at[i, j], held[i, j] = np.arange(len(ii)), coef
    return dict(i=i, j=j, coef=coef, offsets=tuple(offsets), D=len(ii),
                at=at, held=held)


def state_rows(hd: int) -> int:
    """``D``: rows of a head's state."""
    return layout(hd)["D"]


def kernel_tiles(hd: int) -> bool:
    """Whether the kernels can lay a head out: whole lanes."""
    return hd % LANE_TILE == 0


def _block_coef(rows: int, lanes: int):
    """A block's coefficients down a slab's rows, the same in every
    lane: 1 in the diagonal tile, ``sqrt 2`` beyond it."""
    at = jax.lax.broadcasted_iota(jnp.int32, (rows, lanes), 0)
    return jnp.where(at < TILE, 1.0, SQRT2).astype(jnp.float32)


# -- the round ---------------------------------------------------------------

def _step_kernel(rowmap_ref, act_ref, s_ref, g_ref, ks_ref, kv_ref, qs_ref,
                 y_ref, o_ref):
    """One (head, row) grid step. ``s_ref``/``o_ref`` (D, hd): the state
    of the row the step was mapped to; ``g_ref`` (8, hd) the gate in
    every entry; ``ks_ref`` (hd, hd) ``k_j`` down the rows, the same in
    every lane; ``kv_ref`` (hd, hd) ``k_i v``; ``qs_ref`` (G, hd, hd)
    the group's queries as ``ks_ref``; ``y_ref`` (G, hd) out."""
    del rowmap_ref   # the index maps' alone
    b = pl.program_id(1)
    G, hd = qs_ref.shape[0], s_ref.shape[1]
    offsets = layout(hd)["offsets"]

    @pl.when(act_ref[b] == 0)
    def _idle():
        y_ref[...] = jnp.zeros_like(y_ref)

    # a round's first step finds the output block as memory left it; if
    # its row is idle the block is still written back (by the step that
    # leaves it), so it is made the input
    @pl.when((act_ref[b] == 0) & (b == 0))
    def _keep():
        o_ref[...] = s_ref[...]

    @pl.when(act_ref[b] != 0)
    def _move():
        g = g_ref[0:1, :]
        acc = [jnp.zeros((TILE, hd), jnp.float32) for _ in range(G)]
        for lo in range(0, hd, TILE):
            J = hd - lo
            coef = _block_coef(J, hd)
            kc = ks_ref[lo:, :] * coef
            qc = [qs_ref[h, lo:, :] * coef for h in range(G)]
            for i in range(lo, lo + TILE):
                at = slice(offsets[i], offsets[i] + J)
                new = s_ref[at, :] * g + kc * kv_ref[i:i + 1, :]
                o_ref[at, :] = new
                for h in range(G):
                    inner = (new * qc[h]).reshape(J // TILE, TILE, hd).sum(0)
                    acc[h] = acc[h] + inner * qs_ref[h, i:i + 1, :]
        for h in range(G):
            y_ref[h:h + 1, :] = acc[h].sum(axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",),
                   donate_argnums=(0,))
def step(state, g, k, v, q, active, *, interpret: bool = False):
    """The kernel. state (B, kv, D, hd) float32, donated; g (B, kv), k,
    v (B, kv, hd), q (B, kv, G, hd) float32; active (B,) bool. Returns
    ``(phi(q)^T S' (B, kv, G, hd) float32, S')`` with ``S' = g S +
    phi(k) v^T`` in the rows that are active and ``S`` itself, not
    moved, in the others (their outputs are zeros)."""
    B, kv, D, hd = state.shape
    G = q.shape[2]
    f32 = jnp.float32
    spread = lambda x: jnp.broadcast_to(  # noqa: E731
        x[..., None].astype(f32), x.shape + (hd,))
    act = active.astype(jnp.int32)
    rows = jnp.arange(B, dtype=jnp.int32)
    # an idle row's steps take the block of the active row before it
    # (the first active row's, before any): the pipeline moves a block
    # only when its index changes
    last = jax.lax.cummax(jnp.where(active, rows, -1))
    first = jnp.argmax(active).astype(jnp.int32)
    rowmap = jnp.where(last < 0, first, last)
    by_row = lambda *dims: pl.BlockSpec(  # noqa: E731
        (None, None) + dims,
        lambda h, b, rowmap, act: (b, h) + (0,) * len(dims))
    moved = pl.BlockSpec((None, None, D, hd),
                         lambda h, b, rowmap, act: (rowmap[b], h, 0, 0))
    y, out = pl.pallas_call(
        _step_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(kv, B),
            in_specs=[moved, by_row(TILE, hd), by_row(hd, hd),
                      by_row(hd, hd), by_row(G, hd, hd)],
            out_specs=[by_row(G, hd), moved]),
        out_shape=[jax.ShapeDtypeStruct((B, kv, G, hd), f32),
                   jax.ShapeDtypeStruct(state.shape, f32)],
        input_output_aliases={2: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        cost_estimate=pl.CostEstimate(
            flops=(3 + 2 * G) * B * kv * D * hd,
            bytes_accessed=2 * B * kv * D * hd * 4
            + B * kv * (2 + G) * hd * hd * 4,
            transcendentals=0),
        interpret=interpret,
        name="retention_step",
    )(rowmap, act, state,
      jnp.broadcast_to(g.astype(f32)[..., None, None], (B, kv, TILE, hd)),
      spread(k), k.astype(f32)[..., :, None] * v.astype(f32)[..., None, :],
      spread(q))
    return y, out


# -- the prefill's chunk -----------------------------------------------------

def _chunk_kernel(s0_ref, q_ref, kt_ref, vd_ref, g_ref, p_ref, s_ref):
    """One (row, head, chunk) grid step. ``s_ref`` (D, hd) is the output
    block of the head's state, the same over the chunks: the carry.
    ``q_ref`` (G * C, hd) the group's queries, a head after a head;
    ``kt_ref`` (hd, C) the keys, a position a lane; ``vd_ref`` (C, hd)
    the values decayed to the chunk's end; ``g_ref`` (8, hd) the
    chunk's whole decay in every entry; ``p_ref`` (G * C, hd) out:
    ``phi(Q) S`` against the state before the chunk."""
    hd = s_ref.shape[1]
    offsets = layout(hd)["offsets"]
    mm = q_ref.dtype

    @pl.when(pl.program_id(2) == 0)
    def _start():
        s_ref[...] = s0_ref[...]

    q = q_ref[...].astype(jnp.float32)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, hd), 1)
    p = jnp.zeros(p_ref.shape, jnp.float32)
    for lo in range(0, hd, TILE):
        # the block's coefficients along the lanes, 0 before the block
        qc = q * jnp.where(lane < lo, 0.0,
                           jnp.where(lane < lo + TILE, 1.0, SQRT2))
        for i in range(lo, lo + TILE):
            window = slice(offsets[i] - lo, offsets[i] - lo + hd)
            p = p + jnp.dot((qc * q[:, i:i + 1]).astype(mm),
                            s_ref[window, :].astype(mm),
                            preferred_element_type=jnp.float32)
    p_ref[...] = p
    g = g_ref[0:1, :]
    kt = kt_ref[...].astype(jnp.float32)
    vd = vd_ref[...]
    for lo in range(0, hd, TILE):
        J = hd - lo
        kc = kt[lo:, :] * _block_coef(J, kt.shape[1])
        for i in range(lo, lo + TILE):
            at = slice(offsets[i], offsets[i] + J)
            s_ref[at, :] = s_ref[at, :] * g + jnp.dot(
                (kc * kt[i:i + 1, :]).astype(mm), vd,
                preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("interpret",),
                   donate_argnums=(0,))
def chunk(state, q, k, v_decayed, g_chunk, *, interpret: bool = False):
    """The kernel. state (B, kv, D, hd) float32, donated; q (B, kv, n,
    G, C, hd), k (B, kv, n, C, hd) and v_decayed (B, kv, n, C, hd) in
    one float type, n chunks of C positions (``v_decayed`` is ``v_s
    exp(sum_{r > s} log g_r)`` to its chunk's end; a position that is no
    token has ``k = 0``); g_chunk (B, kv, n) float32 a chunk's whole
    decay. Returns ``(phi(Q) S (B, kv, n, G, C, hd) float32, each chunk
    against the state before it; the state after the last)``."""
    B, kv, D, hd = state.shape
    n, G, C = q.shape[2:5]
    f32 = jnp.float32
    by_chunk = lambda *dims: pl.BlockSpec(  # noqa: E731
        (None, None, None) + dims,
        lambda b, h, c: (b, h, c) + (0,) * len(dims))
    held = pl.BlockSpec((None, None, D, hd), lambda b, h, c: (b, h, 0, 0))
    p, out = pl.pallas_call(
        _chunk_kernel,
        grid=(B, kv, n),
        in_specs=[held, by_chunk(G * C, hd), by_chunk(hd, C),
                  by_chunk(C, hd), by_chunk(TILE, hd)],
        out_specs=[by_chunk(G * C, hd), held],
        out_shape=[jax.ShapeDtypeStruct((B, kv, n, G * C, hd), f32),
                   jax.ShapeDtypeStruct(state.shape, f32)],
        input_output_aliases={0: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        cost_estimate=pl.CostEstimate(
            flops=2 * B * kv * n * (G + 1) * C * D * hd,
            bytes_accessed=2 * B * kv * D * hd * 4
            + B * kv * n * C * hd * (G + 2) * q.dtype.itemsize
            + B * kv * n * G * C * hd * 4,
            transcendentals=0),
        interpret=interpret,
        name="retention_chunk",
    )(state, q.reshape(B, kv, n, G * C, hd), jnp.swapaxes(k, -1, -2),
      v_decayed,
      jnp.broadcast_to(g_chunk.astype(f32)[..., None, None],
                       (B, kv, n, TILE, hd)))
    return p.reshape(B, kv, n, G, C, hd), out
