"""Jamba's language model (``ai21labs/AI21-Jamba2-3B``'s ``config.json``,
``model_type`` ``jamba``; the layers are ``transformers`` 4.57.6
``models/jamba/modeling_jamba.py``).

A pre-norm block whose mixer is a Mamba-1 layer, or attention where
``i % attn_layer_period == attn_layer_offset`` (``configuration_jamba.py``
``layers_block_type``: layers 7 and 21 of 28)::

    h   = x + Mixer(N(x))
    out = h + SwiGLU(N(h))

The Mamba mixer is :class:`nn.mamba.MambaMixer`. Attention is
grouped-query (20 query heads over 1 key-value head of 128) with **no
rotation or other position signal**: the state-space layers before it
carry the order. The feed-forward is a dense SwiGLU in every layer
(``num_experts`` 1; a Jamba with routed experts has no program here).
Final RMSNorm; the head is the embedding transposed. The architecture,
not the weights. Defaults are the published sizes; tests shrink them
through ``ModelConfig.extra``.

Served, an attention layer's cache is rows by position and a Mamba
layer's is *state*: ``ssm_state`` and ``conv_tail``, one value a sequence
whatever its length, which :meth:`Jamba.leaves_not_by_position`
declares (serve/engine.py says what follows). ``cache_index`` and
``device_counters`` (see :data:`SSM_COUNTERS`, :data:`ATTN_COUNTERS`) ride
in the ``cache`` collection beside them, as in
:class:`models.k_exaone.KExaone`.

The parameter tree names the Mamba layers ``layer<i>`` in model order
(every ``layer<i>`` has the same leaves) and the attention layers
``attn<j>``.
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
from pytorch_distributed_nn_tpu.nn.attention import (
    MultiHeadAttention,
    cache_rows_read,
)
from pytorch_distributed_nn_tpu.nn.dtypes import get_policy
from pytorch_distributed_nn_tpu.nn.mamba import MambaMixer

# what a layer counts in one program execution, over real tokens only. A
# Mamba layer: its executions and the positions that advanced a state. An
# attention layer: the key rows inside the real queries' masks and the key
# rows the program read for them (as models/k_exaone.py's full layers)
SSM_COUNTERS = ("ssm_calls_total", "ssm_tokens_total")
ATTN_COUNTERS = ("attn_rows_attended_total", "attn_rows_read_total")
PER_LAYER = 2   # entries of the counter leaf a layer, of either kind


class JambaBlock(nn.Module):
    attention: bool
    num_heads: int
    num_kv_heads: int
    head_dim: int
    mlp_dim: int
    d_inner: int
    d_state: int
    d_conv: int
    dt_rank: int
    norm_eps: float
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, decode: bool = False, positions=None, real=None):
        """``positions`` (B, T) and ``real`` (B, T) bool: where each fed
        token stands and whether it is one (decode only). Returns the
        block's output and its two counters."""
        norm = lambda name: RMSNorm(  # noqa: E731
            eps=self.norm_eps, dtype=self.dtype,
            param_dtype=self.param_dtype, name=name)
        T = x.shape[1]
        u = norm("input_norm")(x)
        if self.attention:
            attn = MultiHeadAttention(
                num_heads=self.num_heads, head_dim=self.head_dim,
                num_kv_heads=self.num_kv_heads, causal=True, rotary=False,
                impl="auto", use_bias=False, dtype=self.dtype,
                param_dtype=self.param_dtype, name="attn")
            with jax.named_scope("jamba/attn"):
                m = attn(u, decode=True, cache_positions=positions[:, 0],
                         lengths=real.sum(axis=-1)) if decode else attn(u)
        else:
            with jax.named_scope("jamba/mamba"):
                m = MambaMixer(
                    d_inner=self.d_inner, d_state=self.d_state,
                    d_conv=self.d_conv, dt_rank=self.dt_rank,
                    norm_eps=self.norm_eps, dtype=self.dtype,
                    param_dtype=self.param_dtype, name="mamba",
                )(u, decode=decode, real=real)
        h = x + m
        with jax.named_scope("jamba/mlp"):
            out = h + SwiGLU(self.mlp_dim, dtype=self.dtype,
                             param_dtype=self.param_dtype,
                             name="mlp")(norm("pre_ff_norm")(h))
        if not decode or self.is_initializing():
            return out, None
        if not self.attention:
            return out, jnp.stack([jnp.ones((), jnp.uint32),
                                   real.sum().astype(jnp.uint32)])
        return out, jnp.stack([
            jnp.where(real, positions + 1, 0).sum(),
            cache_rows_read(attn, T, positions, real)]).astype(jnp.uint32)


class Jamba(nn.Module):
    vocab_size: int = 65536
    num_layers: int = 28
    d_model: int = 2560
    num_heads: int = 20
    num_kv_heads: int = 1
    mlp_dim: int = 8192
    attn_layer_period: int = 14
    attn_layer_offset: int = 7
    mamba_expand: int = 2
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_dt_rank: int = 160
    norm_eps: float = 1e-6
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32

    # the serving engine tells such a model which fed tokens are real
    takes_token_mask = True
    # where in the ``cache`` collection the running totals live
    device_counter_leaf = ("device_counters",)

    def _layers(self) -> tuple:
        """``(name in the parameter tree, attention)`` of every layer,
        in order."""
        out, n_attn = [], 0
        for i in range(self.num_layers):
            attention = \
                i % self.attn_layer_period == self.attn_layer_offset
            out.append((f"attn{n_attn}" if attention
                        else f"layer{i - n_attn}", attention))
            n_attn += attention
        return tuple(out)

    def device_counter_names(self) -> tuple:
        """``(metric, labels)`` of each entry of that leaf."""
        return tuple(
            (name, {"kind": kind, "layer": str(i),
                    **({"attn": "full"} if attention else {})})
            for kind in KINDS
            for i, (_, attention) in enumerate(self._layers())
            for name in (ATTN_COUNTERS if attention else SSM_COUNTERS))

    def leaves_not_by_position(self) -> dict:
        """``{what they are: their paths in the ``cache`` collection}`` of
        the leaves that are not rows by absolute position. Here every
        Mamba layer's state, ``(slots, d_state, d_inner)`` and ``(slots,
        d_conv - 1, d_inner)`` whatever the sequence's length. The
        serving engine keeps no prefix store for a model that has any
        (serve/engine.py says why)."""
        return {"recurrent state (one value a sequence, whatever its "
                "length)": tuple(
                    (name, "mamba", leaf)
                    for name, attention in self._layers() if not attention
                    for leaf in ("ssm_state", "conv_tail"))}

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
        embed = nn.Embed(self.vocab_size, self.d_model,
                         param_dtype=self.param_dtype, name="tok_embed")
        x = embed(tokens).astype(self.dtype)
        layers = self._layers()
        positions = real = None
        if decode:
            cache_index = self.variable(
                "cache", "cache_index", lambda: jnp.zeros((), jnp.int32))
            counters = self.variable(
                "cache", "device_counters", jnp.zeros,
                (len(KINDS) * len(layers) * PER_LAYER,), jnp.uint32)
            if cache_positions is None:
                cache_positions = jnp.full((B,), cache_index.value)
                if not self.is_initializing():
                    cache_index.value = cache_index.value + T
            positions = cache_positions[:, None] + jnp.arange(T)[None]
            real = jnp.ones((B, T), bool) if token_mask is None \
                else token_mask
        counts = []
        for name, attention in layers:
            x, c = JambaBlock(
                attention=attention, num_heads=self.num_heads,
                num_kv_heads=self.num_kv_heads,
                head_dim=self.d_model // self.num_heads,
                mlp_dim=self.mlp_dim,
                d_inner=self.mamba_expand * self.d_model,
                d_state=self.mamba_d_state, d_conv=self.mamba_d_conv,
                dt_rank=self.mamba_dt_rank, norm_eps=self.norm_eps,
                dtype=self.dtype, param_dtype=self.param_dtype, name=name,
            )(x, decode, positions, real)
            if c is not None:
                counts.append(c)
        if counts:
            kind = KINDS.index("decode" if T == 1 else "prefill")
            per_kind = len(layers) * PER_LAYER
            counters.value = counters.value.at[
                kind * per_kind:(kind + 1) * per_kind].add(
                    jnp.concatenate(counts))
        with jax.named_scope("head"):
            x = head_input(x, last_only, head_rows)
            x = RMSNorm(eps=self.norm_eps, dtype=self.dtype,
                        param_dtype=self.param_dtype, name="final_norm")(x)
            if return_hidden:
                return x
            # the tied head, accumulated in float32
            return jnp.einsum("btd,vd->btv", x,
                              embed.embedding.astype(x.dtype),
                              preferred_element_type=jnp.float32)


@register("jamba")
def build_jamba(cfg: ModelConfig) -> Jamba:
    """The whole language model. ``extra`` overrides any size by its
    field's name; a key that is no field (the harness's ``rope_theta``:
    the model rotates nothing) is dropped."""
    policy = get_policy(cfg.dtype, cfg.compute_dtype)
    sizes = {k: v for k, v in cfg.extra.items()
             if k in Jamba.__dataclass_fields__}
    return Jamba(**sizes, dtype=policy.compute_dtype,
                 param_dtype=policy.param_dtype)
