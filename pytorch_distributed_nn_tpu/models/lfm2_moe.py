"""LFM2's mixture-of-experts language model (``LiquidAI/LFM2-8B-A1B``
``config.json``, ``model_type`` ``lfm2_moe``; the operators are the
dense sibling's, ``transformers`` 4.57.6 ``models/lfm2/modeling_lfm2.py``).

A pre-norm block whose operator is chosen from a *list*, ``layer_types``
(18 ``conv`` and 6 ``full_attention`` of 24, irregular at its end), and
whose feed-forward turns from dense to experts after
``num_dense_layers``::

    h   = x + Op_i(N(x))          Op_i: ShortConv | grouped-query attention
    out = h + F_i(N(h))           F_i:  SwiGLU (i < num_dense_layers) | experts

``conv`` is :class:`nn.short_conv.ShortConv`, two gates around a
depthwise causal convolution of ``conv_width`` positions.
``full_attention`` is grouped-query (32 query heads over 8 key-value
heads of 64) with an RMSNorm over each head's dims of q and k before
the rotation (:class:`nn.attention.MultiHeadAttention`, ``qk_norm``).
The expert layer picks ``moe_topk`` of ``num_experts`` by sigmoid scores
renormalised over the picks, a selection bias that chooses and does not
weigh (the ``buffers`` collection's ``selection_bias``, zeros when
absent), no shared expert, as one rank's share
(:class:`parallel.expert.HeldExpertsMoE`; ``ep_size`` 1 holds them all).
Its renormalisation adds 1e-20 where the family's adds 1e-6: under
sigmoid scores the four picked add up to more than 1e-2 in any case, so
a weight moves by under 1e-4 relative; the module's is kept. Final
RMSNorm; the head is the token table transposed (``tie_word_embeddings``
is the family's default). The architecture, not the weights. Defaults
are the published sizes; tests shrink them through ``ModelConfig.extra``.

Served, an attention layer's cache is rows by position and a
convolution layer's is *state*: ``conv_tail``, the last ``conv_width -
1`` gated inputs, one value a sequence whatever its length, which
:meth:`Lfm2Moe.leaves_not_by_position` declares (serve/engine.py says
what follows). ``cache_index`` and ``device_counters``
(:data:`CONV_COUNTERS` or :data:`ATTN_COUNTERS` a layer, then
:data:`MOE_COUNTERS` for a layer of experts) ride in the ``cache``
collection beside them, as in :class:`models.jamba.Jamba`.

The parameter tree names a layer by what it holds, counted in model
order within its kind: ``layer<j>`` a convolution before experts (every
``layer<j>`` has the same leaves), ``attn<j>`` attention before
experts, ``dense<j>`` a convolution before the dense feed-forward,
``dense_attn<j>`` attention before it (none as published).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from flax import linen as nn

from pytorch_distributed_nn_tpu.config import ModelConfig
from pytorch_distributed_nn_tpu.models import register
from pytorch_distributed_nn_tpu.models.jamba import ATTN_COUNTERS
from pytorch_distributed_nn_tpu.models.llama import RMSNorm
from pytorch_distributed_nn_tpu.models.longcat_flash import KINDS, SwiGLU
from pytorch_distributed_nn_tpu.models.sdar_moe import TokenTable
from pytorch_distributed_nn_tpu.nn import head_input
from pytorch_distributed_nn_tpu.nn.attention import (
    MultiHeadAttention,
    cache_rows_read,
)
from pytorch_distributed_nn_tpu.nn.dtypes import get_policy
from pytorch_distributed_nn_tpu.nn.short_conv import ShortConv
from pytorch_distributed_nn_tpu.parallel.expert import HeldExpertsMoE

# the published order of operators (``layer_types``)
LAYER_TYPES = ("conv", "conv", "full_attention", "conv", "conv", "conv",
               "full_attention", "conv", "conv", "conv", "full_attention",
               "conv", "conv", "conv", "full_attention", "conv", "conv",
               "conv", "full_attention", "conv", "conv", "full_attention",
               "conv", "conv")
# what a layer counts in one program execution, over real tokens only. A
# convolution: its executions and the positions that moved a tail. An
# attention: as models/jamba.py's. A layer of experts adds
# HeldExpertsMoE's routing counts (it has no zero experts)
CONV_COUNTERS = ("conv_calls_total", "conv_tokens_total")
MOE_COUNTERS = ("moe_calls_total", "moe_picks_total", "moe_held_pairs_total",
                "moe_held_experts_touched_total")
# a layer's name in the parameter tree, by (attention, experts)
_NAMES = {(False, True): "layer", (True, True): "attn",
          (False, False): "dense", (True, False): "dense_attn"}


class Lfm2MoeBlock(nn.Module):
    attention: bool
    sparse: bool
    num_heads: int
    num_kv_heads: int
    head_dim: int
    mlp_dim: int
    expert_mlp_dim: int
    num_experts: int
    moe_topk: int
    routed_scaling: float
    conv_width: int
    ep_size: int
    ep_rank: int
    rope_theta: float
    norm_eps: float
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, decode: bool = False, positions=None, real=None):
        """``positions`` (B, T) and ``real`` (B, T) bool: where each fed
        token stands and whether it is one (decode only). Returns the
        block's output and what it counted, in the order of
        :meth:`Lfm2Moe.device_counter_names`."""
        norm = lambda name: RMSNorm(  # noqa: E731
            eps=self.norm_eps, dtype=self.dtype,
            param_dtype=self.param_dtype, name=name)
        T = x.shape[1]
        u = norm("operator_norm")(x)
        if self.attention:
            attn = MultiHeadAttention(
                num_heads=self.num_heads, head_dim=self.head_dim,
                num_kv_heads=self.num_kv_heads, causal=True, rotary=True,
                rope_theta=self.rope_theta, impl="auto", use_bias=False,
                qk_norm=True, norm_eps=self.norm_eps, dtype=self.dtype,
                param_dtype=self.param_dtype, name="attn")
            with jax.named_scope("lfm2/attn"):
                m = attn(u, decode=True, cache_positions=positions[:, 0],
                         lengths=real.sum(axis=-1)) if decode else attn(u)
        else:
            with jax.named_scope("lfm2/conv"):
                m = ShortConv(self.conv_width, dtype=self.dtype,
                              param_dtype=self.param_dtype,
                              name="conv")(u, decode=decode, real=real)
        h = x + m
        f = norm("ffn_norm")(h)
        routing = None
        if self.sparse:
            with jax.named_scope("lfm2/moe"):
                f, routing = HeldExpertsMoE(
                    num_experts=self.num_experts, mlp_dim=self.expert_mlp_dim,
                    k=self.moe_topk, routed_scaling=self.routed_scaling,
                    scoring="sigmoid", renormalize=True,
                    ep_size=self.ep_size, ep_rank=self.ep_rank,
                    dtype=self.dtype, param_dtype=self.param_dtype,
                    name="moe")(f, token_mask=real)
        else:
            with jax.named_scope("lfm2/dense_ffn"):
                f = SwiGLU(self.mlp_dim, dtype=self.dtype,
                           param_dtype=self.param_dtype, name="ffn")(f)
        out = h + f
        if not decode or self.is_initializing():
            return out, None
        if self.attention:
            counts = [jnp.where(real, positions + 1, 0).sum(),
                      cache_rows_read(attn, T, positions, real)]
        else:
            counts = [jnp.ones((), jnp.uint32), real.sum()]
        if self.sparse:
            counts += [jnp.ones((), jnp.uint32), *routing[jnp.asarray(
                [0, 2, 3])]]
        return out, jnp.stack(counts).astype(jnp.uint32)


class Lfm2Moe(nn.Module):
    vocab_size: int = 65536
    num_layers: int = 24
    d_model: int = 2048
    num_heads: int = 32
    num_kv_heads: int = 8
    mlp_dim: int = 7168
    expert_mlp_dim: int = 1792
    num_experts: int = 32
    moe_topk: int = 4
    num_dense_layers: int = 2
    routed_scaling: float = 1.0
    conv_width: int = 3            # ``conv_L_cache``
    # the operator of each layer; a model of fewer layers takes the
    # list's head
    layer_types: tuple = LAYER_TYPES
    ep_size: int = 1
    ep_rank: int = 0
    rope_theta: float = 1000000.0
    norm_eps: float = 1e-5
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32

    # the serving engine tells such a model which fed tokens are real
    takes_token_mask = True
    # where in the ``cache`` collection the running totals live
    device_counter_leaf = ("device_counters",)

    def _layers(self) -> tuple:
        """``(name in the parameter tree, attention, experts)`` of every
        layer, in order."""
        kinds = tuple(self.layer_types)[:self.num_layers]
        if len(kinds) < self.num_layers \
                or set(kinds) - {"conv", "full_attention"}:
            raise ValueError(
                f"{self.num_layers} layers under layer_types {kinds}: one "
                f"of 'conv' and 'full_attention' a layer")
        out, seen = [], {}
        for i, kind in enumerate(kinds):
            key = (kind == "full_attention", i >= self.num_dense_layers)
            out.append((f"{_NAMES[key]}{seen.get(key, 0)}", *key))
            seen[key] = seen.get(key, 0) + 1
        return tuple(out)

    def device_counter_names(self) -> tuple:
        """``(metric, labels)`` of each entry of that leaf."""
        return tuple(
            (name, {"kind": kind, "layer": str(i),
                    **({"attn": "full"} if name.startswith("attn_")
                       else {})})
            for kind in KINDS
            for i, (_, attention, sparse) in enumerate(self._layers())
            for name in (ATTN_COUNTERS if attention else CONV_COUNTERS)
            + (MOE_COUNTERS if sparse else ()))

    def leaves_not_by_position(self) -> dict:
        """``{what they are: their paths in the ``cache`` collection}`` of
        the leaves that are not rows by absolute position. Here every
        convolution layer's carried inputs, ``(slots, conv_width - 1,
        d_model)`` whatever the sequence's length. The serving engine
        keeps no prefix store for a model that has any (serve/engine.py
        says why)."""
        return {"recurrent state (one value a sequence, whatever its "
                "length)": tuple(
                    (name, "conv", "conv_tail")
                    for name, attention, _ in self._layers()
                    if not attention)}

    @nn.compact
    def __call__(self, tokens, *, train: bool = False,
                 decode: bool = False, last_only: bool = False,
                 return_hidden: bool = False, cache_positions=None,
                 token_mask=None, head_rows=None):
        """As :class:`models.llama.Llama` (``last_only``,
        ``return_hidden``, ``cache_positions``). ``token_mask`` (B, T)
        bool marks the real tokens, a left-aligned prefix of each row:
        the rest move no tail and reach no expert and no counter (their
        rows of the result mean nothing). ``head_rows`` (B, K) int32:
        which of a sequence's T rows reach the final norm and the head
        (``nn.head_input``; all of them by default)."""
        del train   # no dropout, no auxiliary loss: the forward is one
        B, T = tokens.shape
        embed = TokenTable(self.vocab_size, self.d_model,
                           param_dtype=self.param_dtype, name="tok_embed")
        x = embed(tokens).astype(self.dtype)
        layers = self._layers()
        positions = real = None
        if decode:
            cache_index = self.variable(
                "cache", "cache_index", lambda: jnp.zeros((), jnp.int32))
            counters = self.variable(
                "cache", "device_counters", jnp.zeros,
                (len(self.device_counter_names()),), jnp.uint32)
            if cache_positions is None:
                cache_positions = jnp.full((B,), cache_index.value)
                if not self.is_initializing():
                    cache_index.value = cache_index.value + T
            positions = cache_positions[:, None] + jnp.arange(T)[None]
            real = jnp.ones((B, T), bool) if token_mask is None \
                else token_mask
        counts = []
        for name, attention, sparse in layers:
            x, c = Lfm2MoeBlock(
                attention=attention, sparse=sparse,
                num_heads=self.num_heads, num_kv_heads=self.num_kv_heads,
                head_dim=self.d_model // self.num_heads,
                mlp_dim=self.mlp_dim, expert_mlp_dim=self.expert_mlp_dim,
                num_experts=self.num_experts, moe_topk=self.moe_topk,
                routed_scaling=self.routed_scaling,
                conv_width=self.conv_width, ep_size=self.ep_size,
                ep_rank=self.ep_rank, rope_theta=self.rope_theta,
                norm_eps=self.norm_eps, dtype=self.dtype,
                param_dtype=self.param_dtype, name=name,
            )(x, decode, positions, real)
            if c is not None:
                counts.append(c)
        if counts:
            kind = KINDS.index("decode" if T == 1 else "prefill")
            per_kind = counters.value.shape[0] // len(KINDS)
            counters.value = counters.value.at[
                kind * per_kind:(kind + 1) * per_kind].add(
                    jnp.concatenate(counts))
        with jax.named_scope("head"):
            x = head_input(x, last_only, head_rows)
            x = RMSNorm(eps=self.norm_eps, dtype=self.dtype,
                        param_dtype=self.param_dtype,
                        name="embedding_norm")(x)
            if return_hidden:
                return x
            # the tied head, accumulated in float32
            table = embed.variables["params"]["table"]
            return jnp.einsum("btd,vd->btv", x, table.astype(x.dtype),
                              preferred_element_type=jnp.float32)


@register("lfm2_8b_a1b")
def build_lfm2_8b_a1b(cfg: ModelConfig) -> Lfm2Moe:
    """``LFM2-8B-A1B``: the published sizes are the fields' defaults
    (``benchmark/lib/serving.program_model`` passes eight sizes under
    another family's key names and nothing else: the expert width, the
    experts and their picks, the dense layers, the convolution's width
    and the order of operators come from here). ``extra`` overrides any
    size by its field's name; a key that is no field is dropped."""
    policy = get_policy(cfg.dtype, cfg.compute_dtype)
    sizes = {k: v for k, v in cfg.extra.items()
             if k in Lfm2Moe.__dataclass_fields__}
    if "layer_types" in sizes:
        sizes["layer_types"] = tuple(sizes["layer_types"])
    return Lfm2Moe(**sizes, dtype=policy.compute_dtype,
                   param_dtype=policy.param_dtype)
