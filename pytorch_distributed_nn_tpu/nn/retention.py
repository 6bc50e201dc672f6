"""Gated power retention: an attention-free mixer whose whole cache is a
matrix-valued state.

Manifest AI's power retention (Gelada, Buckman, Zhang, Bach, "Scaling
Context Requires Rethinking Attention", arXiv:2507.04239; the
``retention`` package's ``power_retention(Q, K, V, log_G, deg, ...)``)
at degree 2, as Brumby-14B-Base uses it in place of attention. For one
sequence, ``u_t`` the input at position t, key-value head ``j`` and a
query head ``i`` of its group::

    q_t = Rot_t(N_q(u_t W_q^i))   k_t = Rot_t(N_k(u_t W_k^j))   v_t = u_t W_v^j
    log g_t = logsigmoid(u_t W_g^j)                     float32, a gate a kv head
    a_ts = (q_t . k_s)^2 exp(sum_{r=s+1..t} log g_r)    s <= t
    y_t  = sum_s a_ts v_s / (sum_s a_ts + eps)
    out_t = concat_i(y_t^i) W_o

and, with ``phi`` the symmetric square (``phi(a) . phi(b) = (a . b)^2``,
:func:`phi`), its recurrent form, which is what is served::

    S_t = g_t S_{t-1} + phi(k_t) v_t^T        z_t = g_t z_{t-1} + phi(k_t)
    y_t = phi(q_t)^T S_t / (phi(q_t)^T z_t + eps)

What a sequence carries from one call to the next is ``S`` ``(kv, D,
head_dim)`` and ``z`` ``(kv, D)``, float32, ``D`` 8,704 at a head of 128
(``ops/pallas/retention.py`` says which monomial lies where): 35.7 MB a
layer a sequence whatever its length. Under ``decode=True`` both live in
the ``cache`` collection (``ret_state``, ``ret_norm``) as a Mamba
mixer's state does (:mod:`nn.mamba`), and the same one rule holds: a
call continues from the leaves it is given, and a position that is not
``real`` (a prefill bucket's padding, a decode round's inactive row)
changes neither leaf, bit for bit: its gate is 1 and its key 0. Its row
of the output means nothing.

One recurrence in two forms picked by what the call can observe
(:func:`power_retention`): one position, the decode round, is the step
with no loop; more is the chunked form, inside a chunk the ``a_ts`` form
under the decay mask, across chunks the state (``phi(Q) S`` decayed to
each position, then ``S <- G S + phi(K)^T (decayed V)``). Each form in
two executions: the Pallas kernels of :mod:`ops.pallas.retention` on a
TPU at shapes they can lay out, ``jax.numpy`` otherwise (the CPU's path
and the kernels' oracle). ``z`` is small (it is ``sum_s decay k_s
k_s^T``, a matrix of ``head_dim`` squared, kept in ``phi``'s layout) and
stays with XLA in both.

Serving only: the kernels bring no VJP, and a call without a cache
(``decode=False``) runs the ``jax.numpy`` form from zeros.
"""

from __future__ import annotations

import functools
import logging

import jax
import jax.numpy as jnp
from flax import linen as nn

from pytorch_distributed_nn_tpu.nn.attention import rotary_embedding
from pytorch_distributed_nn_tpu.obs.registry import get_registry
from pytorch_distributed_nn_tpu.ops.pallas import retention as kernel

log = logging.getLogger(__name__)

# positions a chunk of the prefill's form, read on the chip at
# Brumby-14B's widths (benchmark/tests/probe_brumby_kernels.py; PERF.md
# sec. 6, PR 50): a mixer over 4,096 positions of one row takes 5.70,
# 5.26 and 5.39 ms at chunks of 64, 128 and 256 (the kernel alone 4.75,
# 4.29, 4.33; the jax.numpy form 84, 44 and 50)
CHUNK = 128
# the scores' sum can be 0 (a first position whose query is orthogonal
# to its key): the quotient is then 0 and not 0 / 0
EPS = 1e-6
_HIGHEST = jax.lax.Precision.HIGHEST


def phi(x):
    """The symmetric square in the state's layout: ``phi(a) . phi(b) =
    (a . b)^2``. x (..., hd) -> (..., D) in x's type."""
    lay = kernel.layout(x.shape[-1])
    return x[..., lay["i"]] * x[..., lay["j"]] \
        * jnp.asarray(lay["coef"], x.dtype)


def _quadratic(z, q):
    """``phi(q) . z`` without laying ``phi(q)`` out: ``z`` holds a
    symmetric form's coefficients. z (..., D) and q (..., X, hd) float32
    under the same leading axes -> (..., X)."""
    lay = kernel.layout(q.shape[-1])
    zm = z[..., lay["at"]] * lay["held"]
    return (jnp.einsum("...xi,...ij->...xj", q, zm, precision=_HIGHEST)
            * q).sum(axis=-1)


@functools.lru_cache(maxsize=None)
def _log_execution(form: str, execution: str, state: tuple, T: int):
    log.info("power_retention: %s as %s, a state %s through %d "
             "position(s)", form, execution, state, T)


def _count(form: str, execution: str, state: tuple, T: int) -> None:
    _log_execution(form, execution, state, T)
    get_registry().counter(
        "retention_programs_total", "power retentions a program was traced "
        "with, by form and execution",
        ("execution",)).inc(execution=f"{form}, {execution}")


def step_xla(S, g, k, v, q):
    """The round in ``jax.numpy``: ``S' = g S + phi(k) v^T`` and
    ``phi(q)^T S'``. S (B, kv, D, hd), g (B, kv), k, v (B, kv, hd), q
    (B, kv, G, hd), float32. A row with ``g = 1`` and ``k = 0`` keeps
    ``S`` bit for bit."""
    S = g[..., None, None] * S + phi(k)[..., None] * v[..., None, :]
    return jnp.einsum("bhgD,bhDv->bhgv", phi(q), S, precision=_HIGHEST), S


def chunk_xla(S, q, k, vd, g_chunk):
    """The prefill's sequential part in ``jax.numpy``, as
    ``ops.pallas.retention.chunk`` defines it: q (B, kv, n, G, C, hd),
    k, vd (B, kv, n, C, hd), g_chunk (B, kv, n). A ``lax.scan`` over the
    chunks; the products in the operands' type, accumulated in
    float32."""
    def one(S, xs):
        q, k, vd, g = xs
        p = jnp.einsum("bhgtD,bhDv->bhgtv", phi(q.astype(jnp.float32))
                       .astype(q.dtype), S.astype(q.dtype),
                       preferred_element_type=jnp.float32,
                       precision=_HIGHEST)
        S = g[..., None, None] * S + jnp.einsum(
            "bhsD,bhsv->bhDv", phi(k.astype(jnp.float32)).astype(k.dtype),
            vd, preferred_element_type=jnp.float32, precision=_HIGHEST)
        return S, p
    chunks_first = lambda x: jnp.moveaxis(x, 2, 0)  # noqa: E731
    S, p = jax.lax.scan(one, S, tuple(chunks_first(x)
                                      for x in (q, k, vd, g_chunk)))
    return jnp.moveaxis(p, 0, 2), S


def power_retention(S, z, q, k, v, log_g, real, *, eps: float = EPS,
                    chunk=None, on_core=None, interpret=False):
    """Continue a retention from ``(S, z)`` through T positions.

    S (B, kv, D, hd) and z (B, kv, D) float32, the state before the
    first position; q (B, T, kv, G, hd), k, v (B, T, kv, hd) in the
    serving type; log_g (B, T, kv) float32; real (B, T) bool, a
    left-aligned prefix of each row. Returns ``(y (B, T, kv, G, hd)
    float32, S, z after the last real position)``.

    T = 1 is the step; T > 1 the chunked form, ``chunk`` positions a
    chunk (default :data:`CHUNK`; a call that is not whole chunks is
    padded to them with positions that are no tokens). ``on_core`` picks
    the kernels (default: on a TPU, at a head they can lay out); it
    logs once a shape which form and execution a program lowered with
    and counts them in ``retention_programs_total``."""
    B, T, kv, G, hd = q.shape
    f32 = jnp.float32
    if on_core is None:
        on_core = jax.default_backend() == "tpu" and kernel.kernel_tiles(hd)
    execution = "Pallas kernel" if on_core else "jax.numpy"
    # a position that is no token: gate 1, nothing added
    log_g = jnp.where(real[..., None], log_g, 0.0)
    k = jnp.where(real[..., None, None], k, 0)
    if T == 1:
        _count("step", execution, tuple(S.shape), T)
        g = jnp.exp(log_g[:, 0])
        q1, k1, v1 = (x[:, 0].astype(f32) for x in (q, k, v))
        z = g[..., None] * z + phi(k1)
        if on_core:
            y, S = kernel.step(S, g, k1, v1, q1, real[:, 0],
                               interpret=interpret)
        else:
            y, S = step_xla(S, g, k1, v1, q1)
        den = _quadratic(z, q1)
        return (y / (den[..., None] + eps))[:, None], S, z
    C = min(chunk or CHUNK, T)
    if T % C:
        # whole chunks, the rest positions that are no tokens
        rows = lambda x: jnp.pad(  # noqa: E731
            x, ((0, 0), (0, -T % C)) + ((0, 0),) * (x.ndim - 2))
        y, S, z = power_retention(
            S, z, rows(q), rows(k), rows(v), rows(log_g), rows(real),
            eps=eps, chunk=chunk, on_core=on_core, interpret=interpret)
        return y[:, :T], S, z
    n = T // C
    _count(f"chunks of {C}", execution, tuple(S.shape), T)
    # (B, kv, n, ..., C, hd): a head's chunks side by side
    by_chunk = lambda x: jnp.moveaxis(  # noqa: E731
        x.reshape((B, n, C) + x.shape[2:]), 2, -2)
    qc = jnp.moveaxis(by_chunk(q), 1, 2)              # (B, kv, n, G, C, hd)
    kc = jnp.moveaxis(by_chunk(k), 1, 2)              # (B, kv, n, C, hd)
    vc = jnp.moveaxis(by_chunk(v), 1, 2)
    cum = jnp.cumsum(jnp.moveaxis(
        log_g.reshape(B, n, C, kv), 3, 1), axis=-1)   # (B, kv, n, C)
    total = cum[..., -1:]
    # inside a chunk: scores squared under the decay mask
    s = jnp.einsum("bhngtd,bhnsd->bhngts", qc, kc,
                   preferred_element_type=f32, precision=_HIGHEST)
    causal = jnp.tril(jnp.ones((C, C), bool))
    a = s * s * jnp.exp(jnp.where(
        causal, cum[..., :, None] - cum[..., None, :], -jnp.inf))[:, :, :,
                                                                 None]
    num = jnp.einsum("bhngts,bhnsv->bhngtv", a.astype(v.dtype), vc,
                     preferred_element_type=f32, precision=_HIGHEST)
    den = a.sum(axis=-1)
    # across chunks: the state before each chunk, decayed to the position
    vd = (vc.astype(f32) * jnp.exp(total - cum)[..., None]).astype(v.dtype)
    g_chunk = jnp.exp(total[..., 0])
    run = functools.partial(kernel.chunk, interpret=interpret) if on_core \
        else chunk_xla
    p, S = run(S, qc, kc, vd, g_chunk)
    # the normaliser is ``sum decay k k^T`` in phi's layout: before each
    # chunk by a scan over ``n`` small states, against q by its form
    k32 = kc.astype(f32)
    kk = jnp.einsum("bhnsi,bhnsj->bhnij", k32 * jnp.exp(total - cum)[
        ..., None], k32, precision=_HIGHEST)
    lay = kernel.layout(hd)
    added = kk[..., lay["i"], lay["j"]] * lay["coef"]   # (B, kv, n, D)

    def before(z, xs):
        g, add = xs
        return g[..., None] * z + add, z
    z, z_before = jax.lax.scan(
        before, z, (jnp.moveaxis(g_chunk, 2, 0), jnp.moveaxis(added, 2, 0)))
    z_before = jnp.moveaxis(z_before, 0, 2)            # (B, kv, n, D)
    q32 = qc.astype(f32)
    den_before = _quadratic(z_before, q32.reshape(B, kv, n, G * C, hd)) \
        .reshape(B, kv, n, G, C)
    decay = jnp.exp(cum)[:, :, :, None]                # (B, kv, n, 1, C)
    y = (num + p * decay[..., None]) \
        / (den + den_before * decay + eps)[..., None]
    # (B, kv, n, G, C, hd) -> (B, T, kv, G, hd)
    y = jnp.moveaxis(y, (2, 4), (1, 2)).reshape(B, T, kv, G, hd)
    return y, S, z


class PowerRetention(nn.Module):
    """The mixer. ``num_heads`` query heads over ``num_kv_heads``
    key-value heads of ``head_dim``; no bias anywhere."""

    num_heads: int
    num_kv_heads: int
    head_dim: int
    rope_theta: float = 1000000.0
    norm_eps: float = 1e-6
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, u, decode: bool = False, positions=None, real=None):
        """u (B, T, d). ``decode=True`` keeps ``ret_state`` (B, kv, D,
        head_dim) and ``ret_norm`` (B, kv, D), float32, in the ``cache``
        collection (``model.init`` with ``decode=True`` sizes them),
        continues from them and leaves them one call on; without it the
        sequence starts from zeros and nothing is kept. ``positions``
        (B, T) where each fed token stands (default: from 0); ``real``
        (B, T) bool: see the module's docstring (default: every fed
        token)."""
        # (the models package imports this module)
        from pytorch_distributed_nn_tpu.models.llama import RMSNorm

        B, T, _ = u.shape
        H, kv, hd = self.num_heads, self.num_kv_heads, self.head_dim
        D = kernel.state_rows(hd)
        heads = lambda n, name: nn.DenseGeneral(  # noqa: E731
            (n, hd), axis=-1, use_bias=False, dtype=self.dtype,
            param_dtype=self.param_dtype, name=name)
        norm = lambda name: RMSNorm(  # noqa: E731
            eps=self.norm_eps, dtype=self.dtype,
            param_dtype=self.param_dtype, name=name)
        q, k, v = heads(H, "query")(u), heads(kv, "key")(u), \
            heads(kv, "value")(u)
        q, k = norm("q_norm")(q), norm("k_norm")(k)
        q, k = rotary_embedding(q, k, theta=self.rope_theta,
                                positions=positions)
        q, k = q.astype(self.dtype), k.astype(self.dtype)
        # a gate a key-value head a position, in float32
        log_g = nn.log_sigmoid(nn.Dense(
            kv, use_bias=False, dtype=jnp.float32,
            param_dtype=self.param_dtype, name="gate")(u))
        if decode:
            state = self.variable("cache", "ret_state", jnp.zeros,
                                  (B, kv, D, hd), jnp.float32)
            total = self.variable("cache", "ret_norm", jnp.zeros,
                                  (B, kv, D), jnp.float32)
            S, z = state.value, total.value
        else:
            S = jnp.zeros((B, kv, D, hd), jnp.float32)
            z = jnp.zeros((B, kv, D), jnp.float32)
        if real is None:
            real = jnp.ones((B, T), bool)
        if self.is_initializing():
            y = jnp.zeros((B, T, H, hd), self.dtype)
        else:
            y, S, z = power_retention(
                S, z, q.reshape(B, T, kv, H // kv, hd), k, v, log_g, real)
            y = y.reshape(B, T, H, hd).astype(self.dtype)
            if decode:
                state.value, total.value = S, z
        return nn.DenseGeneral(
            u.shape[-1], axis=(-2, -1), use_bias=False, dtype=self.dtype,
            param_dtype=self.param_dtype, name="out")(y)
