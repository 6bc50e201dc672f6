"""Brumby's language model (``manifestai/Brumby-14B-Base``'s
``config.json``, ``model_type`` ``brumby``): a 14B grouped-query decoder
retrained with every attention replaced by gated power retention
(:mod:`nn.retention`; Manifest AI, arXiv:2507.04239).

A pre-norm block, every layer the same::

    h   = x + Ret(N(x))
    out = h + SwiGLU(N(h))

The block is Llama's: RMSNorm with gain computed in float32
(:class:`models.llama.RMSNorm`), a dense SwiGLU, rotation of q and k at
their positions and an RMSNorm over each head's dims of q and k (one
gain a projection, as :mod:`models.sdar_moe` switches on in attention),
no bias anywhere, final RMSNorm, an untied head. The mixer is
:class:`nn.retention.PowerRetention`, and nothing in the model attends:
the whole cache is *state*, ``ret_state`` and ``ret_norm`` of every
layer, one value a sequence whatever its length, which
:meth:`Brumby.leaves_not_by_position` declares (serve/engine.py says
what follows: no prefix store). ``cache_index`` and ``device_counters``
(:data:`COUNTERS`) ride in the ``cache`` collection beside them, as in
:class:`models.jamba.Jamba`. The architecture, not the weights. Defaults
are the published sizes; tests shrink them through ``ModelConfig.extra``.

What the ``config.json`` does not carry (the degree, the gate, the
normaliser, the state's type) is written down in
``benchmark/configs/brumby_14b.json`` under ``assumed``, each with its
source; the published kernels' ``switch_over_seq_len`` (rows by position
below a length) is an execution choice and is not built.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from flax import linen as nn

from pytorch_distributed_nn_tpu.config import ModelConfig
from pytorch_distributed_nn_tpu.models import register
from pytorch_distributed_nn_tpu.models.llama import RMSNorm
from pytorch_distributed_nn_tpu.models.longcat_flash import KINDS, SwiGLU
from pytorch_distributed_nn_tpu.nn import head_input
from pytorch_distributed_nn_tpu.nn.dtypes import get_policy
from pytorch_distributed_nn_tpu.nn.retention import PowerRetention

# what a layer counts in one program execution, over real tokens only:
# its executions and the positions that advanced its state
COUNTERS = ("retention_calls_total", "retention_tokens_total")


class BrumbyBlock(nn.Module):
    num_heads: int
    num_kv_heads: int
    head_dim: int
    mlp_dim: int
    rope_theta: float
    norm_eps: float
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, decode: bool = False, positions=None, real=None):
        """``positions`` (B, T) and ``real`` (B, T) bool: where each fed
        token stands and whether it is one (decode only)."""
        norm = lambda name: RMSNorm(  # noqa: E731
            eps=self.norm_eps, dtype=self.dtype,
            param_dtype=self.param_dtype, name=name)
        with jax.named_scope("brumby/retention"):
            h = x + PowerRetention(
                num_heads=self.num_heads, num_kv_heads=self.num_kv_heads,
                head_dim=self.head_dim, rope_theta=self.rope_theta,
                norm_eps=self.norm_eps, dtype=self.dtype,
                param_dtype=self.param_dtype, name="ret",
            )(norm("input_norm")(x), decode=decode, positions=positions,
              real=real)
        with jax.named_scope("brumby/mlp"):
            return h + SwiGLU(self.mlp_dim, dtype=self.dtype,
                              param_dtype=self.param_dtype,
                              name="mlp")(norm("mlp_norm")(h))


class Brumby(nn.Module):
    vocab_size: int = 151936
    num_layers: int = 40
    d_model: int = 5120
    num_heads: int = 40
    num_kv_heads: int = 8
    head_dim: int = 128
    mlp_dim: int = 17408
    rope_theta: float = 1000000.0
    norm_eps: float = 1e-6
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32

    # the serving engine tells such a model which fed tokens are real
    takes_token_mask = True
    # where in the ``cache`` collection the running totals live
    device_counter_leaf = ("device_counters",)

    def device_counter_names(self) -> tuple:
        """``(metric, labels)`` of each entry of that leaf."""
        return tuple((name, {"kind": kind, "layer": str(i)})
                     for kind in KINDS for i in range(self.num_layers)
                     for name in COUNTERS)

    def leaves_not_by_position(self) -> dict:
        """``{what they are: their paths in the ``cache`` collection}`` of
        the leaves that are not rows by absolute position: here every
        layer's two, ``(slots, kv, D, head_dim)`` and ``(slots, kv, D)``
        whatever the sequence's length, which is the whole cache. The
        serving engine keeps no prefix store for a model that has any
        (serve/engine.py says why)."""
        return {"recurrent state (one value a sequence, whatever its "
                "length)": tuple(
                    (f"layer{i}", "ret", leaf)
                    for i in range(self.num_layers)
                    for leaf in ("ret_state", "ret_norm"))}

    @nn.compact
    def __call__(self, tokens, *, train: bool = False,
                 decode: bool = False, last_only: bool = False,
                 return_hidden: bool = False, cache_positions=None,
                 token_mask=None, head_rows=None):
        """As :class:`models.llama.Llama` (``last_only``,
        ``return_hidden``, ``cache_positions``). ``token_mask`` (B, T)
        bool marks the real tokens, a left-aligned prefix of each row:
        the rest advance no state and reach no counter (their rows of
        the result mean nothing). ``head_rows`` (B, K) int32: which of a
        sequence's T rows reach the final norm and the head
        (``nn.head_input``; all of them by default)."""
        del train   # no dropout, no auxiliary loss: the forward is one
        B, T = tokens.shape
        x = nn.Embed(self.vocab_size, self.d_model,
                     param_dtype=self.param_dtype,
                     name="tok_embed")(tokens).astype(self.dtype)
        positions = real = None
        if decode:
            cache_index = self.variable(
                "cache", "cache_index", lambda: jnp.zeros((), jnp.int32))
            counters = self.variable(
                "cache", "device_counters", jnp.zeros,
                (len(KINDS) * self.num_layers * len(COUNTERS),), jnp.uint32)
            if cache_positions is None:
                cache_positions = jnp.full((B,), cache_index.value)
                if not self.is_initializing():
                    cache_index.value = cache_index.value + T
            positions = cache_positions[:, None] + jnp.arange(T)[None]
            real = jnp.ones((B, T), bool) if token_mask is None \
                else token_mask
        for i in range(self.num_layers):
            x = BrumbyBlock(
                num_heads=self.num_heads, num_kv_heads=self.num_kv_heads,
                head_dim=self.head_dim, mlp_dim=self.mlp_dim,
                rope_theta=self.rope_theta, norm_eps=self.norm_eps,
                dtype=self.dtype, param_dtype=self.param_dtype,
                name=f"layer{i}")(x, decode, positions, real)
        if decode and not self.is_initializing():
            kind = KINDS.index("decode" if T == 1 else "prefill")
            per_kind = self.num_layers * len(COUNTERS)
            counters.value = counters.value.at[
                kind * per_kind:(kind + 1) * per_kind].add(jnp.tile(
                    jnp.stack([jnp.ones((), jnp.uint32),
                               real.sum().astype(jnp.uint32)]),
                    self.num_layers))
        with jax.named_scope("head"):
            x = head_input(x, last_only, head_rows)
            x = RMSNorm(eps=self.norm_eps, dtype=self.dtype,
                        param_dtype=self.param_dtype, name="final_norm")(x)
            if return_hidden:
                return x
            return nn.Dense(self.vocab_size, use_bias=False, dtype=jnp.float32,
                            param_dtype=self.param_dtype, name="lm_head")(x)


@register("brumby")
def build_brumby(cfg: ModelConfig) -> Brumby:
    """The family as published. ``extra`` overrides any size by its
    field's name; a key that is no field is dropped."""
    policy = get_policy(cfg.dtype, cfg.compute_dtype)
    sizes = {k: v for k, v in cfg.extra.items()
             if k in Brumby.__dataclass_fields__}
    return Brumby(**sizes, dtype=policy.compute_dtype,
                  param_dtype=policy.param_dtype)
