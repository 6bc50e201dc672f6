"""A gated short convolution whose carried inputs live in the decode cache.

LFM2's operator (``transformers`` 4.57.6 ``models/lfm2/modeling_lfm2.py``
``Lfm2ShortConv.slow_forward``, lines 417-525). For one row, ``u_t`` the
input at position t, ``K`` the convolution's width (``conv_L_cache``)::

    [B_t | C_t | x_t] = u_t W_in                      no bias
    z_t   = B_t * x_t
    c_t   = sum_{j=0..K-1} w[j] * z_{t-(K-1)+j}       depthwise, causal, no bias
    out_t = (C_t * c_t) W_out                         no activation anywhere

Two gates by projections of the same input around a convolution of a few
positions, and no recurrence behind it: what a sequence carries from one
call to the next is the last ``K - 1`` values of ``z`` and nothing else.
Under ``decode=True`` they live in the ``cache`` collection as
``conv_tail`` (B, K - 1, d) in the layer's type, *state* as
:class:`nn.mamba.MambaMixer`'s (one value a sequence whatever its length,
which no absolute position addresses), under that module's one rule: a
call continues from the leaf it is given, a prefill from position 0 is a
call on a zeroed leaf, and a position that is not ``real`` (a prefill
bucket's padding, a decode round's retired row) changes nothing, bit for
bit: the tail is the ``K - 1`` values before the row's first unreal
position (:func:`nn.mamba.carried_tail`). The convolution is
:class:`nn.mamba.CausalConv1d` without its bias: products and sums in
float32, the gate ``C`` applied there too, rounded once to the layer's
type before ``W_out`` (the source computes each in the model's type).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from flax import linen as nn

from pytorch_distributed_nn_tpu.nn.mamba import CausalConv1d, carried_tail


class ShortConv(nn.Module):
    width: int = 3                 # K: the convolution's taps
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, u, decode: bool = False, real=None):
        """u (B, T, d). ``decode=True`` keeps ``conv_tail`` (B, K - 1,
        d) in the ``cache`` collection (``model.init`` with
        ``decode=True`` sizes it), continues from it and leaves it one
        call on; without it the sequence starts from zeros and nothing
        is kept. ``real`` (B, T) bool: see the module's docstring
        (default: every fed token)."""
        B, T, d = u.shape
        dense = lambda f, name: nn.Dense(  # noqa: E731
            f, use_bias=False, dtype=self.dtype,
            param_dtype=self.param_dtype, name=name)
        in_proj = dense(3 * d, "in_proj")
        conv = CausalConv1d(self.width, use_bias=False,
                            param_dtype=self.param_dtype, name="conv")
        out_proj = dense(d, "out_proj")
        if decode:
            tail = self.variable("cache", "conv_tail", jnp.zeros,
                                 (B, self.width - 1, d), self.dtype)
            before = tail.value
        else:
            before = jnp.zeros((B, self.width - 1, d), self.dtype)
        gate_in, gate_out, x = jnp.split(in_proj(u), 3, axis=-1)
        c, joined = conv(gate_in * x, before)
        if decode and not self.is_initializing():
            with jax.named_scope("cache_write"):
                tail.value = carried_tail(
                    joined, before,
                    jnp.ones((B, T), bool) if real is None else real)
        return out_proj((gate_out.astype(jnp.float32) * c)
                        .astype(self.dtype))
