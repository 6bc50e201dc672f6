"""A Mamba prefill's recurrence with the state held on the core.

    h_t = exp(dt_t a) * h_{t-1} + (dt_t c_t) b_t ;   y_t = h_t . c_out_t

a position after a position, as ``nn/mamba.selective_scan`` defines it
(the same products in float32, the same ``exp``; only the sum over
``d_state`` adds its 16 terms in another order). As a ``lax.scan`` the
state ``(d_state, d_inner)`` float32 goes through memory every loop
iteration; here a grid step takes a tile of ``d_inner`` lanes through a
chunk of positions with the tile's state in registers, and memory sees
the state once a call: read before the first chunk, written after the
last (the output block is revisited over the chunks, which run in
order). ``dt`` and ``c`` come in and ``y`` leaves a chunk a block.

``b_t`` and ``c_out_t`` are ``d_state`` values a position, which the
state's layout wants one a sublane and the same in every lane. They
come in spread over a lane tile, ``(chunk, d_state, 128)`` blocks that
XLA broadcasts outside (33 MB an operand at 4,096 positions, read once
a chunk whatever the tile), and a step loads row ``n`` of position
``t`` into every sublane. (Spread inside instead, from ``(d_state,
chunk)`` blocks a column at a time, the kernel ran no faster and its
text was 512 more slices and stores to trace a program: PERF.md sec.
6.)

A position with ``dt = 0`` holds the state bit for bit (``exp(0) = 1``
and ``0 c b = 0``), so a call is padded to whole chunks with such
positions and a prefill bucket's padding needs no mask here. Forward
only: no VJP.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# positions a grid step and d_inner lanes a grid step, chosen on the chip
# at Jamba2-3B's widths (PERF.md sec. 4 has the table)
CHUNK = 256
LANES = 1024
# positions loaded, multiplied and stored together: a bf16 tile's rows
GROUP = 16
LANE_TILE = 128
# a chunk of 256's blocks are 14 MiB twice buffered (8 of them the two
# spread operands) and a chunk of 512's pass the compiler's 16 MiB
# default; a v5e core has 128 MiB
VMEM_LIMIT_BYTES = 48 * 2 ** 20


def tiles(T: int, D: int) -> tuple:
    """``(chunk, lanes)`` as run for T positions of D channels: a call
    shorter than a chunk is one chunk of whole groups; the widest tile
    of whole lane tiles that divides D."""
    chunk = CHUNK if T >= CHUNK else -(-T // GROUP) * GROUP
    lanes = next((n for n in range(min(LANES, D), 0, -LANE_TILE)
                  if D % n == 0), 0)
    return chunk, lanes


def kernel_tiles(N: int, D: int) -> bool:
    """Whether the kernel can lay the state out: whole (8, 128)
    registers."""
    return N % 8 == 0 and D % LANE_TILE == 0


def _kernel(h0_ref, dt_ref, c_ref, b_ref, co_ref, a_ref, y_ref, h_ref,
            dtc_scr):
    """One (row, chunk, lane tile) grid step. ``h_ref`` is the output
    block of the row's whole state, the same block over the chunks and
    the tiles: the carry between chunks."""
    chunk, lanes = dt_ref.shape
    N = a_ref.shape[0]
    k, d = pl.program_id(1), pl.program_id(2)
    packed = (lanes // LANE_TILE, LANE_TILE)

    @pl.when((k == 0) & (d == 0))
    def _start():
        h_ref[...] = h0_ref[...]

    tile = pl.ds(pl.multiple_of(d * lanes, lanes), lanes)
    # a row of the tile in one register, a lane tile a sublane
    row = lambda ref, *at: ref[at].reshape(packed)  # noqa: E731
    # one value of a spread block in every sublane and lane
    every = lambda ref, t, n: jnp.broadcast_to(  # noqa: E731
        ref[t, pl.ds(n, 1), :], packed)
    a = [row(a_ref, pl.ds(n, 1), slice(None)) for n in range(N)]

    def group(g, h):
        first = pl.multiple_of(g * GROUP, GROUP)
        at = pl.ds(first, GROUP)
        dtc_scr[...] = dt_ref[at, :] * c_ref[at, :].astype(jnp.float32)

        def position(s, h):
            t = first + s
            dt = row(dt_ref, pl.ds(t, 1), slice(None))
            dtc = row(dtc_scr, pl.ds(s, 1), slice(None))
            h = [jnp.exp(dt * a[n]) * h[n] + dtc * every(b_ref, t, n)
                 for n in range(N)]
            y = h[0] * every(co_ref, t, 0)
            for n in range(1, N):
                y = y + h[n] * every(co_ref, t, n)
            y_ref[pl.ds(t, 1), :] = y.reshape(1, lanes)
            return h

        # traced once, lowered GROUP times over
        return jax.lax.fori_loop(0, GROUP, position, h, unroll=True)

    h = jax.lax.fori_loop(0, chunk // GROUP, group,
                          [row(h_ref, pl.ds(n, 1), tile) for n in range(N)])
    for n in range(N):
        h_ref[pl.ds(n, 1), tile] = h[n].reshape(1, lanes)


@functools.partial(jax.jit,
                   static_argnames=("chunk", "lanes", "interpret"))
def scan(h, dt, c, b, c_out, a, *, chunk: int, lanes: int,
         interpret: bool = False):
    """The kernel. h (B, N, D) float32; dt (B, T, D) float32 and c (B,
    T, D) in any float type, T in whole chunks; b, c_out (B, T, N)
    float32; a (N, D) float32. Returns ``(y (B, T, D) float32, h after
    the last position)``. Jitted, so that the scans of one program (26,
    the same shapes) are traced and lowered once."""
    B, T, D = dt.shape
    N = a.shape[0]
    by_position = pl.BlockSpec((None, chunk, lanes),
                               lambda i, k, d: (i, k, d))
    by_state = pl.BlockSpec((None, chunk, N, LANE_TILE),
                            lambda i, k, d: (i, k, 0, 0))
    state = pl.BlockSpec((None, N, D), lambda i, k, d: (i, 0, 0))
    spread = lambda x: jnp.broadcast_to(  # noqa: E731
        x[..., None], x.shape + (LANE_TILE,))
    return pl.pallas_call(
        _kernel,
        grid=(B, T // chunk, D // lanes),
        in_specs=[state, by_position, by_position, by_state, by_state,
                  pl.BlockSpec((N, lanes), lambda i, k, d: (0, d))],
        out_specs=[by_position, state],
        out_shape=[jax.ShapeDtypeStruct((B, T, D), jnp.float32),
                   jax.ShapeDtypeStruct((B, N, D), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((GROUP, lanes), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        cost_estimate=pl.CostEstimate(
            flops=7 * B * T * N * D,
            bytes_accessed=B * T * D * (8 + c.dtype.itemsize)
            + B * T * N * LANE_TILE * 8 + 2 * B * N * D * 4,
            transcendentals=B * T * N * D),
        interpret=interpret,
        name="selective_scan",
    )(h, dt, c, spread(b), spread(c_out), a)
