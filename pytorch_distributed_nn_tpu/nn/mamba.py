"""A Mamba-1 mixer whose recurrent state lives in the decode cache.

The selective state-space layer of Gu & Dao 2023 (arXiv:2312.00752) as
Jamba builds it (``transformers`` 4.57.6 ``models/jamba/modeling_jamba.py``
``JambaMambaMixer.slow_forward``, lines 725-808): three RMSNorms over the
step, ``B`` and ``C`` that Mamba-1 itself lacks. For one row, ``u_t`` the
input at position t::

    [x_t | z_t] = u_t W_in
    c_t   = silu(b_conv + sum_j w_conv[j] * x_{t-3+j})     depthwise, causal
    [r_t | B_t | C_t] = c_t W_x;  r, B, C = N_dt(r), N_b(B), N_c(C)
    dt_t  = softplus(r_t W_dt + b_dt)
    h_t   = exp(dt_t A) * h_{t-1} + (dt_t c_t) B_t         A = -exp(A_log)
    y_t   = h_t C_t + D c_t
    out_t = (y_t * silu(z_t)) W_out

What a sequence carries from one call to the next is ``h`` (``d_inner`` x
``d_state``, float32) and the last ``d_conv - 1`` values of ``x``. Under
``decode=True`` both live in the ``cache`` collection, as an attention's
rows do (:class:`nn.attention.MultiHeadAttention`), but they are *state*:
one value a sequence whatever its length, which no absolute position
addresses. A call continues from the leaves it is given: a prefill from
position 0 is a call on zeroed leaves, a suffix or a decode round a call
on filled ones. There is one rule.

``real`` (B, T) bool marks the fed tokens that are tokens, a left-aligned
prefix of each row (the rest is a prefill bucket's padding, or a decode
round's inactive row). A position that is not real changes neither leaf,
bit for bit: its step is 0, and ``exp(0 A) = 1``, ``0 c B = 0``; the tail
is the ``d_conv - 1`` values before the row's first unreal position. Its
row of the output means nothing.

The state is laid out ``(B, d_state, d_inner)``: the chip tiles an array's
last two axes by (8, 128), and ``d_state`` 16 as the last axis would be
padded to 128, eight times the memory and the traffic.
"""

from __future__ import annotations

import functools
import logging
import math

import jax
import jax.numpy as jnp
from flax import linen as nn

from pytorch_distributed_nn_tpu.obs.registry import get_registry
from pytorch_distributed_nn_tpu.ops.pallas import selective_scan as kernel

log = logging.getLogger(__name__)

# positions of the recurrence unrolled into one iteration of ``lax.scan``
# (the state stays on the core between them): read on the chip at
# Jamba2-3B's widths (PERF.md sec. 4)
SCAN_UNROLL = 16


def dt_bias_init(dt_min: float = 1e-3, dt_max: float = 0.1,
                 dt_floor: float = 1e-4):
    """Mamba's initialiser of the step's bias: ``softplus(bias)`` is
    log-uniform in ``[dt_min, dt_max]`` (the reference implementation's
    ``dt_init``; Gu & Dao 2023 sec. 3.6)."""
    def init(key, shape, dtype=jnp.float32):
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32)
                     * (math.log(dt_max) - math.log(dt_min))
                     + math.log(dt_min))
        dt = jnp.maximum(dt, dt_floor)
        # the inverse of softplus
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    return init


def a_log_init(key, shape, dtype=jnp.float32):
    """``A = -(1 .. d_state)`` in every channel (S4D-real)."""
    del key
    return jnp.log(jnp.broadcast_to(
        jnp.arange(1, shape[1] + 1, dtype=jnp.float32), shape)).astype(dtype)


@functools.lru_cache(maxsize=None)
def _log_execution(execution: str, state: tuple, T: int, how: str):
    log.info("selective_scan: %s, a state %s through %d positions, %s",
             execution, state, T, how)


def selective_scan(h, dt, c, b, c_out, a, unroll: int = SCAN_UNROLL,
                   differentiable: bool = True):
    """``h_t = exp(dt_t a) h_{t-1} + (dt_t c_t) b_t``; ``y_t = h_t . c_out_t``.

    h (B, N, D) float32, the state before the first position; dt (B, T,
    D) float32; c (B, T, D) in the serving type (widened here: the same
    values); b, c_out (B, T, N) float32; a (N, D) float32. Returns ``(y
    (B, T, D) float32, h after the last position)``. A position whose
    ``dt`` is 0 holds ``h`` exactly.

    One recurrence, a position after a position, in two executions
    picked by what the call can observe. On a TPU, for more than one
    position, shapes the kernel can lay out and a caller that will not
    differentiate the call (the kernel brings no VJP): the Pallas kernel
    of :mod:`ops.pallas.selective_scan`, the state on the core through a
    chunk of positions, the call padded to whole chunks with ``dt = 0``.
    Otherwise ``lax.scan``, ``unroll`` positions an iteration: the CPU's
    path and the kernel's oracle, ~0.9 us a position on the chip, the
    state through memory every iteration. One position, the decode
    round, is the step itself with no loop. (The parallel form, an
    associative scan over the pairs ``(exp(dt a), dt c b)`` inside
    chunks of 64 to 1,024 positions, measured 2 to 18 times slower than
    the loop, several passes over ``(chunk, 16, 5120)`` float32: PERF.md
    sec. 4.) Logs once a shape which execution a program lowered with,
    and counts the calls of each in ``selective_scan_calls_total``."""
    def step(h, xs):
        dt_t, c_t, b_t, co_t = xs              # (B, D) twice, (B, N) twice
        h = jnp.exp(dt_t[:, None, :] * a) * h \
            + (dt_t * c_t)[:, None, :] * b_t[:, :, None]
        return h, jnp.einsum("bnd,bn->bd", h, co_t)

    T, D = dt.shape[1:]
    if T == 1:
        h, y = step(h, (dt[:, 0], c[:, 0].astype(jnp.float32), b[:, 0],
                        c_out[:, 0]))
        return y[:, None], h
    chunk, lanes = kernel.tiles(T, D)
    on_core = not differentiable and jax.default_backend() == "tpu" \
        and kernel.kernel_tiles(a.shape[0], D)
    execution = "Pallas kernel" if on_core else "lax.scan"
    _log_execution(execution, tuple(h.shape), T,
                   f"chunks of {chunk} x {lanes} lanes" if on_core
                   else f"{unroll} an iteration")
    get_registry().counter(
        "selective_scan_calls_total", "prefill recurrences a program was "
        "traced with, by execution", ("execution",)).inc(execution=execution)
    if on_core:
        rows = lambda x: jnp.pad(  # noqa: E731
            x, ((0, 0), (0, -T % chunk), (0, 0)))
        y, h = kernel.scan(h, rows(dt), rows(c), rows(b), rows(c_out), a,
                           chunk=chunk, lanes=lanes)
        return y[:, :T], h
    h, y = jax.lax.scan(
        step, h, tuple(jnp.moveaxis(x, 1, 0) for x in
                       (dt, c.astype(jnp.float32), b, c_out)),
        unroll=unroll)
    return jnp.moveaxis(y, 0, 1), h


class CausalConv1d(nn.Module):
    """Depthwise causal convolution over time with a carried tail:
    ``out_t = bias + sum_j kernel[j] * x_{t - (K-1) + j}``, the ``K - 1``
    values before the call's first position given as ``tail``; without
    ``use_bias`` there is no such leaf and the sum stands alone."""

    width: int            # K
    use_bias: bool = True
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, tail):
        """x (B, T, C), tail (B, K - 1, C). Returns ``(out (B, T, C)
        float32, the tail and x joined (B, K - 1 + T, C))``."""
        C = x.shape[-1]
        kernel = self.param("kernel", nn.initializers.lecun_normal(),
                            (self.width, C), self.param_dtype)
        out = self.param("bias", nn.initializers.zeros, (C,),
                         self.param_dtype).astype(jnp.float32) \
            if self.use_bias else 0.0
        T = x.shape[1]
        joined = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
        for j in range(self.width):
            out = out + kernel[j].astype(jnp.float32) \
                * joined[:, j:j + T].astype(jnp.float32)
        return out, joined


def carried_tail(joined, before, real):
    """The tail a call leaves behind: the ``K - 1`` values before each
    row's first unreal position. ``joined`` (B, K - 1 + T, C) is the
    tail the call was given, ``before`` (B, K - 1, C), and its T inputs
    behind it; ``real`` (B, T) bool a left-aligned prefix of each row.
    A row with no real position keeps ``before`` bit for bit. (A
    round's row has one position or none: a select, where the general
    rule is a gather.)"""
    K1 = before.shape[1]
    if real.shape[1] == 1:
        return jnp.where(real[:, :, None], joined[:, 1:], before)
    at = real.sum(axis=-1)[:, None] + jnp.arange(K1)[None]
    return jnp.take_along_axis(joined, at[:, :, None], axis=1)


class MambaMixer(nn.Module):
    d_inner: int
    d_state: int = 16
    d_conv: int = 4
    dt_rank: int = 160
    norm_eps: float = 1e-6
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, u, decode: bool = False, real=None):
        """u (B, T, d). ``decode=True`` keeps ``ssm_state`` (B, d_state,
        d_inner) float32 and ``conv_tail`` (B, d_conv - 1, d_inner) in
        the ``cache`` collection (``model.init`` with ``decode=True``
        sizes them), continues from them and leaves them one call on;
        without it the sequence starts from zeros and nothing is kept.
        ``real`` (B, T) bool: see the module's docstring (default: every
        fed token)."""
        # (the models package imports this module)
        from pytorch_distributed_nn_tpu.models.llama import RMSNorm

        B, T, d = u.shape
        D, N, K = self.d_inner, self.d_state, self.d_conv
        dense = lambda f, name, **kw: nn.Dense(  # noqa: E731
            f, param_dtype=self.param_dtype, name=name,
            **{"use_bias": False, "dtype": self.dtype, **kw})
        norm = lambda name: RMSNorm(  # noqa: E731
            eps=self.norm_eps, dtype=jnp.float32,
            param_dtype=self.param_dtype, name=name)
        in_proj = dense(2 * D, "in_proj")
        conv = CausalConv1d(K, param_dtype=self.param_dtype, name="conv1d")
        x_proj = dense(self.dt_rank + 2 * N, "x_proj")
        # the step is small (1e-3 .. 0.1) and is exponentiated: float32
        dt_proj = dense(D, "dt_proj", use_bias=True, dtype=jnp.float32,
                        kernel_init=nn.initializers.variance_scaling(
                            1.0 / 3.0, "fan_in", "uniform"),
                        bias_init=dt_bias_init())
        a_log = self.param("A_log", a_log_init, (D, N), self.param_dtype)
        skip = self.param("D", nn.initializers.ones, (D,), self.param_dtype)
        out_proj = dense(d, "out_proj")

        if decode:
            state = self.variable("cache", "ssm_state", jnp.zeros,
                                  (B, N, D), jnp.float32)
            tail = self.variable("cache", "conv_tail", jnp.zeros,
                                 (B, K - 1, D), self.dtype)
            h, before = state.value, tail.value
        else:
            h = jnp.zeros((B, N, D), jnp.float32)
            before = jnp.zeros((B, K - 1, D), self.dtype)
        if real is None:
            real = jnp.ones((B, T), bool)

        x, z = jnp.split(in_proj(u), 2, axis=-1)
        conv_out, joined = conv(x, before)
        c = nn.silu(conv_out).astype(self.dtype)
        r, b, c_out = jnp.split(x_proj(c), [self.dt_rank, self.dt_rank + N],
                                axis=-1)
        r, b, c_out = norm("dt_norm")(r), norm("b_norm")(b), \
            norm("c_norm")(c_out)
        dt = jnp.where(real[:, :, None], nn.softplus(dt_proj(r)), 0.0)
        a = -jnp.exp(a_log.astype(jnp.float32)).T                # (N, D)
        c32 = c.astype(jnp.float32)
        # a decode round (T = 1) is one step over every row; a call that
        # keeps a cache is a served one, which nothing differentiates
        y, h = selective_scan(h, dt, c, b, c_out, a,
                              differentiable=not decode)
        y = y + skip.astype(jnp.float32) * c32
        if decode and not self.is_initializing():
            state.value = h
            with jax.named_scope("cache_write"):
                tail.value = carried_tail(joined, before, real)
        return out_proj((y * nn.silu(z.astype(jnp.float32)))
                        .astype(self.dtype))
