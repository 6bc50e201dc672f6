"""The held experts' SwiGLU over the token-expert pairs, grouped.

    y_r = (silu(x_r W_gate[e(r)]) * (x_r W_up[e(r)])) W_down[e(r)]

for rows ``r`` sorted by expert, the experts' kernels laid side by side
as :class:`parallel.expert.HeldExpertsMoE` holds them: ``W_gate`` and
``W_up`` ``(d, held * ff)``, ``W_down`` ``(ff, held * d)``, expert j the
column block ``[j * ff, (j + 1) * ff)`` (``[j * d, (j + 1) * d)``).

The real pairs held here are sorted by expert (one stable sort, so an
expert's rows keep their token order) and each expert's group is padded
to whole tiles of ``tm`` rows (:func:`layout`): a tile belongs to one
expert, the tiles of an expert follow each other, and the live tiles
come first. ``ceil(pairs / tm) + held`` tiles bound their number
(:func:`tile_bound`); which expert a tile is and how many are live is
known on the device alone. A pair's result is read back from its row
and the ``k`` of a token are summed under their weights in float32.

One routine in two executions, as ``prefix_attention`` and
``selective_scan`` have: :func:`_pallas` (a TPU kernel: a grid step
takes a tile of rows and a chunk of ``fc`` of its expert's ``ff``
columns, the index maps read the tile's expert from scalar memory and
hand the step that expert's blocks of the three parameters *where they
lie*, so nothing is sliced out or copied and an expert no pair picked
is never read; consecutive tiles of one expert find its blocks already
on the core when ``fc`` is the whole ``ff``) and :func:`_loop`
(``jax.numpy``: a ``fori_loop`` over the live tiles that takes each
tile's column block by ``dynamic_slice``: the CPU's path, the path of
shapes the kernel cannot lay out, and the kernel's oracle).
:func:`grouped_experts` picks by what it can observe, the backend, the
shapes and the type. bf16 (or the layer's type) operands, float32
accumulation in each product, the gate and the product with ``up`` in
float32, rounded once to the layer's type before the down product.
Forward only.
"""

from __future__ import annotations

import functools
import logging

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

log = logging.getLogger(__name__)

LANE_TILE = 128
# rows a tile, least and most: two bf16 registers' sublanes; the matrix
# unit's side, past which a tile is no cheaper a row
ROWS_MIN, ROWS_MAX = 32, 128
# an expert's three blocks at SDAR's widths (2048 x 768 twice, 768 x
# 2048, bf16) are 9.4 MB, twice buffered 18.9: over the compiler's
# 16 MiB default; a v5e core has 128 MiB
VMEM_LIMIT_BYTES = 48 * 2 ** 20
# what the blocks of one step may take of it, twice buffered
WEIGHT_BLOCKS_BYTES = 24 * 2 ** 20


def tiles(N: int, k: int, num_experts: int, d: int, ff: int,
          itemsize: int = 2) -> tuple:
    """``(tm, fc)`` as run for N tokens of k picks over ``num_experts``
    routed experts: rows a tile and ``ff`` columns a grid step.

    ``tm``: the mean rows an expert gets, in whole bf16 registers (16
    rows), between 32 and 128. A tile costs the matrix unit the same
    whatever it holds under 128 rows (the expert's weights pass through
    it once), so a tile too small for an expert's rows costs a second
    pass that no fetch hides, and one too large rows of padding that
    are gathered, computed and written: on the chip 32 was best at a
    round's 256 positions and at a bucket of 512, 64 at 1,024, each by
    2 to 12 % of the layer over the next size (PERF.md sec. 6, PR 43).

    ``fc``: the whole ``ff`` where an expert's three blocks, twice
    buffered, fit :data:`WEIGHT_BLOCKS_BYTES` (then the next tile of
    the same expert fetches nothing: in chunks a second tile read the
    expert again, 4.9 against 3.1 ms a layer at 1,024); else the widest
    whole lane tiles that divide ``ff`` and fit."""
    mean = -(-N * k // num_experts)
    tm = min(ROWS_MAX, max(ROWS_MIN, -(-mean // 16) * 16))
    fc = next((n for n in range(ff, 0, -LANE_TILE) if ff % n == 0
               and 2 * 3 * d * n * itemsize <= WEIGHT_BLOCKS_BYTES), 0)
    return tm, fc


def tile_bound(pairs: int, tm: int, held: int) -> int:
    """Tiles that hold any ``pairs`` rows over ``held`` experts: the
    whole tiles of the rows, and a ragged one an expert."""
    return -(-pairs // tm) + min(held, pairs)


def kernel_tiles(d: int, ff: int, fc: int, dtype, param_dtype) -> bool:
    """Whether the kernel can lay the blocks out: whole lane tiles, the
    rows and the parameters both bf16 (the rows are whole registers of
    it by :func:`tiles`, and a parameter is read as it lies, not cast)."""
    return fc > 0 and d % LANE_TILE == 0 and ff % fc == 0 \
        and fc % LANE_TILE == 0 \
        and jnp.dtype(dtype) == jnp.dtype(param_dtype) == jnp.bfloat16


def layout(expert, counts, tm: int, bound: int):
    """Where each pair's row lies. ``expert`` (P,) int32: the held
    expert of each pair in ``[0, held)``, or ``held`` for a pair that is
    not computed here; ``counts`` (held,) int32, the pairs of each.
    Returns ``(tile_expert (bound,), live (), pair_of_row (bound * tm,),
    row_of_pair (P,))``: the expert of every tile (a tile past the live
    ones repeats the last live tile's), the number of live tiles, the
    pair a row holds (P for a row of padding) and the row a pair's
    result is in (``bound * tm``, one past the rows, for a pair not
    computed here)."""
    P, held = expert.shape[0], counts.shape[0]
    order = jnp.argsort(expert, stable=True).astype(jnp.int32)
    tiles_of = (counts + tm - 1) // tm
    tile_end = jnp.cumsum(tiles_of)
    live = tile_end[-1]
    first_tile = tile_end - tiles_of
    first_pair = jnp.cumsum(counts) - counts
    t = jnp.arange(bound, dtype=jnp.int32)
    of_tile = jnp.sum(t[:, None] >= tile_end[None, :], axis=1)
    tile_expert = jnp.minimum(
        of_tile[jnp.minimum(t, jnp.maximum(live - 1, 0))],
        held - 1).astype(jnp.int32)
    # row r of tile t is the expert's pair number ``at``
    at = (t - first_tile[tile_expert])[:, None] * tm \
        + jnp.arange(tm, dtype=jnp.int32)[None, :]
    holds = (t < live)[:, None] & (at < counts[tile_expert][:, None])
    sorted_pair = jnp.where(holds, first_pair[tile_expert][:, None] + at, 0)
    pair_of_row = jnp.where(holds, order[sorted_pair], P).reshape(-1)
    # a pair's place in the sorted order, then in its expert's tiles
    place = jnp.zeros((P,), jnp.int32).at[order].set(
        jnp.arange(P, dtype=jnp.int32))
    mine = jnp.minimum(expert, held - 1)
    row_of_pair = jnp.where(
        expert < held,
        first_tile[mine] * tm + place - first_pair[mine], bound * tm)
    return tile_expert, live.astype(jnp.int32), pair_of_row, row_of_pair


def _swiglu(x, wg, wu, wd):
    """One tile through one expert's (chunk of) blocks, float32 out."""
    g = jnp.dot(x, wg, preferred_element_type=jnp.float32)
    u = jnp.dot(x, wu, preferred_element_type=jnp.float32)
    h = (jax.nn.silu(g) * u).astype(x.dtype)
    return jnp.dot(h, wd, preferred_element_type=jnp.float32)


def _loop(tile_expert, live, xs, w_gate, w_up, w_down, *, tm: int, fc: int):
    """The recurrence in ``jax.numpy``: a loop over the live tiles, each
    tile's column blocks taken by ``dynamic_slice`` (which copies them:
    what the kernel is for). The rows of a tile past the live ones are
    left at zero."""
    del fc
    d, ff = xs.shape[1], w_down.shape[0]

    def one_tile(t, ys):
        e = tile_expert[t]
        cols = lambda w, width: jax.lax.dynamic_slice_in_dim(  # noqa: E731
            w, e * width, width, axis=1).astype(xs.dtype)
        x = jax.lax.dynamic_slice_in_dim(xs, t * tm, tm, axis=0)
        y = _swiglu(x, cols(w_gate, ff), cols(w_up, ff), cols(w_down, d))
        return jax.lax.dynamic_update_slice_in_dim(ys, y, t * tm, axis=0)

    return jax.lax.fori_loop(0, live, one_tile,
                             jnp.zeros(xs.shape, jnp.float32))


def _kernel(expert_ref, live_ref, x_ref, wg_ref, wu_ref, wd_ref, y_ref,
            *scratch, chunks: int):
    """One (tile, chunk) grid step. The chunks of a tile run in order,
    so with more than one the float32 ``(tm, d)`` sum lives in VMEM
    scratch across them and is written once a tile. A tile past the
    live ones does nothing: its index maps repeat the last live step's
    blocks, so nothing is fetched for it and nothing written."""
    del expert_ref
    t, c = pl.program_id(0), pl.program_id(1)

    @pl.when(t < live_ref[0])
    def _tile():
        y = _swiglu(x_ref[...], wg_ref[...], wu_ref[...], wd_ref[...])
        if chunks == 1:
            y_ref[...] = y
            return
        acc, = scratch

        @pl.when(c == 0)
        def _first():
            acc[...] = y

        @pl.when(c > 0)
        def _next():
            acc[...] += y

        @pl.when(c == chunks - 1)
        def _emit():
            y_ref[...] = acc[...]


@functools.partial(jax.jit, static_argnames=("tm", "fc", "interpret"))
def _pallas(tile_expert, live, xs, w_gate, w_up, w_down, *, tm: int,
            fc: int, interpret: bool = False):
    """The kernel. xs (bound * tm, d); returns float32 of that shape,
    the rows of the tiles past the live ones unwritten (whatever lay
    there). Jitted, so that the layers of one program (7, the same
    shapes) are traced and lowered once."""
    rows, d = xs.shape
    ff = w_down.shape[0]
    held = w_down.shape[1] // d
    bound, chunks = rows // tm, ff // fc

    def at(t, c, live_ref):
        """The step whose blocks step (t, c) takes: itself, or for a
        tile past the live ones the last live step."""
        dead = t >= live_ref[0]
        return (jnp.where(dead, jnp.maximum(live_ref[0] - 1, 0), t),
                jnp.where(dead, chunks - 1, c))

    def rows_map(t, c, expert_ref, live_ref):
        return (at(t, c, live_ref)[0], 0)

    def up_map(t, c, expert_ref, live_ref):
        t, c = at(t, c, live_ref)
        return (0, expert_ref[t] * chunks + c)

    def down_map(t, c, expert_ref, live_ref):
        t, c = at(t, c, live_ref)
        return (c, expert_ref[t])

    touched = min(held, bound)    # experts read, at most
    return pl.pallas_call(
        functools.partial(_kernel, chunks=chunks),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(bound, chunks),
            in_specs=[
                pl.BlockSpec((tm, d), rows_map),
                pl.BlockSpec((d, fc), up_map),
                pl.BlockSpec((d, fc), up_map),
                pl.BlockSpec((fc, d), down_map),
            ],
            out_specs=pl.BlockSpec((tm, d), rows_map),
            scratch_shapes=[pltpu.VMEM((tm, d), jnp.float32)]
            if chunks > 1 else []),
        out_shape=jax.ShapeDtypeStruct((rows, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        cost_estimate=pl.CostEstimate(
            flops=2 * 3 * rows * d * ff,
            bytes_accessed=3 * touched * d * ff * w_gate.dtype.itemsize
            + rows * d * (xs.dtype.itemsize + 4),
            transcendentals=rows * ff),
        interpret=interpret,
        name="grouped_experts",
    )(tile_expert, live.reshape(1), xs, w_gate, w_up, w_down)


@functools.lru_cache(maxsize=None)
def _log_execution(execution: str, held: int, N: int, k: int, tm: int,
                   fc: int, bound: int):
    log.info("grouped_experts: %s, %d tokens of %d picks over %d held "
             "experts in tiles of %d rows x %d columns, %d tiles at most",
             execution, N, k, held, tm, fc, bound)


def execution(N: int, k: int, num_experts: int, d: int, ff: int, dtype,
              param_dtype) -> tuple:
    """``(how, tm, fc)`` for a call of these shapes and types:
    ``grouped_kernel`` on a TPU where the blocks lay out, else
    ``grouped_loop``, and the tiles of :func:`tiles`."""
    tm, fc = tiles(N, k, num_experts, d, ff, jnp.dtype(param_dtype).itemsize)
    on_core = jax.default_backend() == "tpu" \
        and kernel_tiles(d, ff, fc, dtype, param_dtype)
    return "grouped_kernel" if on_core else "grouped_loop", tm, fc


def grouped_experts(a, expert, weight, counts, w_gate, w_up, w_down, *,
                    num_experts: int):
    """a (N, d) in the layer's type; ``expert`` (N, k) int32, the held
    expert of each pick in ``[0, held)`` or ``held`` for a pick that is
    not computed here (another rank's, a zero expert's, a token that is
    not real); ``weight`` (N, k) float32; ``counts`` (held,) the picks
    of each held expert; the three parameters side by side;
    ``num_experts`` the routed experts over all ranks (what the tiles'
    size follows). Returns (N, d) float32: each token's picks computed
    here, summed under their weights. On a TPU with blocks the kernel
    can lay out, the kernel; otherwise the same recurrence in
    ``jax.numpy``. Logs once a shape which of the two a program lowered
    with."""
    N, d = a.shape
    k, held, ff = expert.shape[1], counts.shape[0], w_down.shape[0]
    how, tm, fc = execution(N, k, num_experts, d, ff, a.dtype, w_gate.dtype)
    bound = tile_bound(N * k, tm, held)
    _log_execution(how, held, N, k, tm, fc, bound)
    tile_expert, live, pair_of_row, row_of_pair = layout(
        expert.reshape(-1), counts, tm, bound)
    # a row of padding holds token 0: computed with its tile, never read
    xs = a[jnp.minimum(pair_of_row, N * k - 1) // k]
    run = _pallas if how == "grouped_kernel" else _loop
    ys = run(tile_expert, live, xs, w_gate, w_up, w_down, tm=tm, fc=fc)
    here = (row_of_pair < bound * tm).reshape(N, k)
    picked = ys[jnp.minimum(row_of_pair, bound * tm - 1)].reshape(N, k, d)
    # what lies in a row no pair is in may be anything: left out, not
    # multiplied by 0
    return jnp.sum(jnp.where(here[:, :, None],
                             picked * weight[:, :, None], 0.0), axis=1)
