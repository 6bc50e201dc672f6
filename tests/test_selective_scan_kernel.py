"""The prefill recurrence's Pallas kernel (``ops/pallas/selective_scan``)
in interpret mode against ``lax.scan`` a position an iteration, and the
rule by which ``nn/mamba.selective_scan`` sends a call to one or the
other. The dispatcher asks for the backend and these tests answer for
the chip; the kernel compiled for the chip at Jamba2-3B's widths is in
``tests/test_chip_compile.py`` (no chip), and on the chip
``scripts/sweep_mamba_scan.py`` makes the same comparison at 1,024 and
4,096 positions.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_nn_tpu import obs
from pytorch_distributed_nn_tpu.nn import mamba
from pytorch_distributed_nn_tpu.ops.pallas import selective_scan as kernel

COUNTER = "selective_scan_calls_total"


def _operands(B, T, D, N=16, c_dtype=jnp.float32, zero_state=False):
    ks = jax.random.split(jax.random.key(7), 6)
    dt = jax.nn.softplus(jax.random.normal(ks[0], (B, T, D)) - 3.0)
    c = jax.random.normal(ks[1], (B, T, D)).astype(c_dtype)
    b = jax.random.normal(ks[2], (B, T, N))
    c_out = jax.random.normal(ks[3], (B, T, N))
    a = -jnp.exp(jax.random.normal(ks[4], (N, D)))
    h = jax.random.normal(ks[5], (B, N, D))
    return (jnp.zeros_like(h) if zero_state else h), dt, c, b, c_out, a


@pytest.fixture
def on_chip(monkeypatch):
    """The dispatcher sees a TPU, and the kernel runs interpreted."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(kernel, "scan", functools.partial(
        kernel.scan, interpret=True))
    obs.reset_registry()
    yield
    obs.reset_registry()


def _calls(execution):
    return obs.get_registry().counter(COUNTER, labels=("execution",)).value(
        execution=execution)


def _by_position(h, dt, c, b, c_out, a):
    return mamba.selective_scan(h, dt, c, b, c_out, a, unroll=1)


def _served(*xs):
    """As a prefill calls it: through the dispatcher, padded there."""
    out = mamba.selective_scan(*xs, differentiable=False)
    assert _calls("Pallas kernel") == 1 and _calls("lax.scan") == 0
    return out


# name -> (operands, the kernel's run of them): Jamba2-3B's widths with
# ``c`` in the serving type; whole chunks over two lane tiles; one chunk,
# a ragged one and a call shorter than a group of positions, each through
# the dispatcher's padding; a start from zeros (every other case starts
# from a state that is not); two rows
CASES = {
    "jamba_widths_bf16_c": (
        dict(B=1, T=32, D=5120, c_dtype=jnp.bfloat16),
        functools.partial(kernel.scan, chunk=16, lanes=1024,
                          interpret=True)),
    "three_chunks_two_lane_tiles": (
        dict(B=1, T=96, D=256),
        functools.partial(kernel.scan, chunk=32, lanes=128,
                          interpret=True)),
    "one_chunk": (dict(B=1, T=48, D=128), _served),
    "ragged_21": (dict(B=1, T=21, D=128), _served),
    "ragged_5": (dict(B=1, T=5, D=128), _served),
    "from_zeros": (dict(B=1, T=32, D=128, zero_state=True), _served),
    "batch_2_eight_states": (
        dict(B=2, T=64, D=128, N=8),
        functools.partial(kernel.scan, chunk=32, lanes=128,
                          interpret=True)),
}


@pytest.mark.parametrize("name", list(CASES))
def test_the_kernel_is_the_recurrence_a_position_a_step(on_chip, name):
    """Outputs and the carried-out state against ``lax.scan`` one
    position an iteration, to float32 rounding (the sum over ``d_state``
    adds in another order; every product is the same)."""
    shape, run = CASES[name]
    xs = _operands(**shape)
    y, h = run(*xs)
    want_y, want_h = _by_position(*xs)
    assert y.shape == want_y.shape and y.dtype == jnp.float32
    size = float(jnp.abs(want_y).max())
    assert size > 1.0
    assert np.abs(np.asarray(y - want_y)).max() < 1e-5 * size
    assert np.abs(np.asarray(h - want_h)).max() < 1e-5
    assert np.abs(np.asarray(want_h - xs[0])).max() > 0.1   # it moved


def test_two_calls_in_a_row_are_one_call_over_the_joined_positions():
    """The state handed from a call to the next, as a suffix prefill
    behind a prefill would: bit for bit the one call's."""
    run = functools.partial(kernel.scan, chunk=32, lanes=128,
                            interpret=True)
    h, *by_position, a = _operands(1, 64, 128)
    y, h2 = run(h, *by_position, a)
    y_first, h1 = run(h, *(x[:, :32] for x in by_position), a)
    y_second, h12 = run(h1, *(x[:, 32:] for x in by_position), a)
    assert (jnp.concatenate([y_first, y_second], axis=1) == y).all()
    assert (h12 == h2).all()


@pytest.mark.parametrize("T,real", [(64, 32), (32, 11), (21, 21)])
def test_positions_at_step_zero_hold_the_state_bit_for_bit(on_chip, T,
                                                           real):
    """A bucket's padding (``dt = 0`` past the ``real`` positions, over
    chunks, inside one, and the dispatcher's own padding of a ragged
    call) leaves the state the real positions left, exactly."""
    h, dt, c, b, c_out, a = _operands(2, T, 128)
    dt = dt.at[:, real:].set(0.0)
    _, held = mamba.selective_scan(h, dt, c, b, c_out, a,
                                   differentiable=False)
    _, want = kernel.scan(
        h, *(jnp.pad(x[:, :real], ((0, 0), (0, -real % 16), (0, 0)))
             for x in (dt, c, b, c_out)), a,
        chunk=-(-real // 16) * 16, lanes=128)
    assert (held == want).all()
    assert np.abs(np.asarray(held - h)).max() > 0.1


@pytest.mark.parametrize("T,D,tiles", [
    (4096, 5120, (256, 1024)),    # Jamba's largest bucket
    (128, 5120, (128, 1024)),     # and its smallest: one chunk
    (21, 256, (32, 256)),         # whole groups of positions
    (300, 640, (256, 640)),       # the widest tile that divides D
    (64, 1536, (64, 768)),
])
def test_tiles_as_run(T, D, tiles):
    assert kernel.tiles(T, D) == tiles
    assert kernel.kernel_tiles(16, D)


@pytest.mark.parametrize("N,D", [(4, 128), (16, 100), (16, 192)])
def test_a_shape_off_the_tiling_stays_with_lax_scan(on_chip, N, D):
    """``d_state`` not in whole sublanes, ``d_inner`` not in whole lane
    tiles: the dispatcher keeps the loop, on a TPU too."""
    xs = _operands(1, 32, D, N)
    y, h = mamba.selective_scan(*xs, differentiable=False)
    assert _calls("Pallas kernel") == 0 and _calls("lax.scan") == 1
    want_y, want_h = _by_position(*xs)
    assert np.abs(np.asarray(y - want_y)).max() < 1e-4
    assert np.abs(np.asarray(h - want_h)).max() < 1e-5


def test_the_mixer_sends_only_cached_prefills_to_the_kernel(on_chip):
    """``MambaMixer`` on a TPU: an uncached call (``decode=False``,
    which may be differentiated: the kernel brings no VJP) and a decode
    round (``T == 1``) lower no kernel, and the counter says so; a
    cached call over more than one position is the kernel's."""
    mixer = mamba.MambaMixer(d_inner=128, dt_rank=8)
    u = jnp.zeros((2, 32, 64))
    variables = mixer.init(jax.random.key(0), u, decode=True)
    obs.reset_registry()

    def lowered(u, **kw):
        return jax.jit(lambda v, u: mixer.apply(v, u, **kw)).lower(
            variables, u).as_text()

    assert "tpu_custom_call" not in lowered(u)
    assert (_calls("Pallas kernel"), _calls("lax.scan")) == (0, 1)
    # differentiated: only ever the loop
    jax.eval_shape(jax.grad(lambda v: mixer.apply(v, u).sum()), variables)
    assert (_calls("Pallas kernel"), _calls("lax.scan")) == (0, 2)
    assert "tpu_custom_call" not in lowered(u[:, :1], decode=True,
                                            mutable=["cache"])
    assert (_calls("Pallas kernel"), _calls("lax.scan")) == (0, 2)
    jax.eval_shape(lambda v, u: mixer.apply(v, u, decode=True,
                                            mutable=["cache"]),
                   variables, u)
    assert (_calls("Pallas kernel"), _calls("lax.scan")) == (1, 2)


def test_off_a_tpu_a_cached_prefill_is_the_loop():
    """The CPU's path, whatever the caller promises."""
    obs.reset_registry()
    xs = _operands(1, 32, 128)
    y, _ = mamba.selective_scan(*xs, differentiable=False)
    assert _calls("Pallas kernel") == 0 and _calls("lax.scan") == 1
    assert (y == mamba.selective_scan(*xs)[0]).all()
    obs.reset_registry()
