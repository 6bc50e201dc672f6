"""A.X-K1's language model (``skt/A.X-K1``'s ``config.json``,
``model_type`` ``axk1``; every key is a key of the DeepSeek-V3 line, whose
layer is ``transformers`` 4.57 ``models/deepseek_v3/modeling_deepseek_v3.py``
and whose scaled rotation ``modeling_rope_utils._compute_yarn_parameters``).

The DeepSeek-V3 block itself, pre-norm::

    h   = x + MLA(N(x))
    out = h + F(N(h))

One latent attention a layer (:mod:`nn.mla`, the plain form: no
multiplier on q or on the latent) whose rotation is YaRN-scaled: the
rotated pairs turn by :func:`nn.attention.yarn_frequencies` (factor 32
over 4,096 original positions) and the softmax scale carries
``mscale**2`` (:func:`nn.attention.yarn_mscale` of ``mscale_all_dim``).
The first ``first_k_dense`` layers have a dense SwiGLU FFN; the others a
mixture of ``num_experts`` routed experts, ``moe_topk`` picks a token by
sigmoid scores from the ``topk_group`` best of ``n_group`` groups,
renormalised over the picks and scaled, plus shared experts every token
goes through. The routed part is one rank's share
(:class:`parallel.expert.HeldExpertsMoE`): ``ax_k1`` holds every expert,
``ax_k1_ep16`` the 12 of rank 0 of 16. The architecture, not the
weights. Defaults are the published sizes; tests shrink them through
``ModelConfig.extra``.

The parameter tree names the dense layers ``dense<j>`` and the sparse
ones ``layer<i>`` (model layer ``first_k_dense + i``), so that every
``layer<i>`` has the same leaves (as :mod:`models.k_exaone`).

Served through the engine's ordinary contract, as
:class:`models.longcat_flash.LongcatFlash`: ``cache_index`` and
``device_counters`` (see :data:`COUNTERS`) ride in the ``cache``
collection beside the attentions' latents. No cache leaf is a ring, so
the engine keeps its prefix cache and block store.
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
    yarn_frequencies,
    yarn_mscale,
)
from pytorch_distributed_nn_tpu.nn.dtypes import get_policy
from pytorch_distributed_nn_tpu.nn.mla import MLAttention
from pytorch_distributed_nn_tpu.ops.pallas.prefix_attention import rows_read
from pytorch_distributed_nn_tpu.parallel.expert import HeldExpertsMoE

# what a layer counts in one program execution, over real tokens only:
# HeldExpertsMoE's routing counts (none in a dense layer) with the
# groups a token's picks fell in, then the latent rows inside the real
# queries' masks and the latent rows the program read for them (a
# decode round the row's whole length, a prefill the key blocks each
# query's block visits)
COUNTERS = ("moe_calls_total", "moe_picks_total", "moe_held_pairs_total",
            "moe_held_experts_touched_total", "moe_pick_groups_total",
            "attn_rows_attended_total", "attn_rows_read_total")


class AXK1Block(nn.Module):
    num_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rope_theta: float
    rope_freqs: tuple
    score_factor: float
    mlp_dim: int           # the dense FFN's width; 0: a sparse layer
    expert_mlp_dim: int
    num_experts: int
    moe_topk: int
    n_group: int
    topk_group: int
    routed_scaling: float
    num_shared_experts: int
    ep_size: int
    ep_rank: int
    norm_eps: float
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, decode: bool = False, positions=None, real=None):
        """``positions`` (B, T) and ``real`` (B, T) bool: where each fed
        token stands and whether it is one (decode only). Returns the
        block's output and its :data:`COUNTERS` after the first."""
        norm = lambda name: RMSNorm(  # noqa: E731
            eps=self.norm_eps, dtype=self.dtype,
            param_dtype=self.param_dtype, name=name)
        T = x.shape[1]
        attn = MLAttention(
            num_heads=self.num_heads, q_lora_rank=self.q_lora_rank,
            kv_lora_rank=self.kv_lora_rank,
            qk_nope_head_dim=self.qk_nope_head_dim,
            qk_rope_head_dim=self.qk_rope_head_dim,
            v_head_dim=self.v_head_dim, rope_theta=self.rope_theta,
            norm_eps=self.norm_eps, rope_freqs=self.rope_freqs,
            score_factor=self.score_factor, dtype=self.dtype,
            param_dtype=self.param_dtype, name="attn")
        with jax.named_scope("axk1/mla_decode" if decode and T == 1
                             else "axk1/mla_prefill"):
            h = x + attn(
                norm("input_norm")(x), decode=decode,
                cache_positions=positions[:, 0] if decode else None,
                token_mask=real)
        u = norm("post_attn_norm")(h)
        if self.mlp_dim:
            with jax.named_scope("axk1/dense_ffn"):
                f = SwiGLU(self.mlp_dim, dtype=self.dtype,
                           param_dtype=self.param_dtype, name="ffn")(u)
            routing = jnp.zeros((4,), jnp.uint32)
        else:
            with jax.named_scope("axk1/moe"):
                f, c = HeldExpertsMoE(
                    num_experts=self.num_experts, mlp_dim=self.expert_mlp_dim,
                    k=self.moe_topk, routed_scaling=self.routed_scaling,
                    scoring="sigmoid", renormalize=True,
                    n_group=self.n_group, topk_group=self.topk_group,
                    ep_size=self.ep_size, ep_rank=self.ep_rank,
                    dtype=self.dtype, param_dtype=self.param_dtype,
                    name="moe")(u, token_mask=real)
            # every rank computes the shared experts alike, whole
            with jax.named_scope("axk1/shared_expert"):
                f = f + SwiGLU(
                    self.expert_mlp_dim * self.num_shared_experts,
                    dtype=self.dtype, param_dtype=self.param_dtype,
                    name="shared_expert")(u)
            # no zero experts here; ungrouped, no fifth count either
            routing = jnp.concatenate([c, jnp.zeros((1,), jnp.uint32)])[
                jnp.asarray([0, 2, 3, 4])]
        out = h + f
        if not decode or self.is_initializing():
            return out, None
        rows = attn.get_variable("cache", "cached_latent").shape[1]
        read = real.sum() * rows if T == 1 \
            else rows_read(positions, real, rows)
        return out, jnp.concatenate([routing, jnp.stack([
            jnp.where(real, positions + 1, 0).sum(), read,
        ]).astype(jnp.uint32)])


class AXK1(nn.Module):
    vocab_size: int = 163840
    num_layers: int = 61          # dense and sparse together
    d_model: int = 7168
    num_heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    mlp_dim: int = 18432          # the leading dense layers' FFN
    first_k_dense: int = 1
    expert_mlp_dim: int = 2048
    num_experts: int = 192
    moe_topk: int = 8
    n_group: int = 8
    topk_group: int = 4
    routed_scaling: float = 2.5
    num_shared_experts: int = 1
    ep_size: int = 1
    ep_rank: int = 0
    rope_theta: float = 10000.0
    # the published ``rope_scaling`` block (type yarn), a field a key;
    # ``rope_factor`` 1 is the unscaled rotation
    rope_factor: float = 32.0
    rope_original_positions: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 1.0
    norm_eps: float = 1e-6
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32

    # the serving engine tells such a model which fed tokens are real
    takes_token_mask = True
    # where in the ``cache`` collection the running totals live
    device_counter_leaf = ("device_counters",)

    def _layers(self) -> tuple:
        """``(name in the parameter tree, dense)`` of every layer."""
        return tuple(
            (f"dense{i}", True) if i < self.first_k_dense
            else (f"layer{i - self.first_k_dense}", False)
            for i in range(self.num_layers))

    def rotation(self) -> tuple:
        """``(per-pair frequencies or None, factor on the softmax
        scale)`` of the ``rope_*`` fields, as ``transformers`` reads a
        yarn ``rope_scaling``: the scale takes ``mscale_all_dim``'s
        correction squared. Cos and sin would carry the ratio of
        ``mscale``'s correction to it: 1 for the published block
        (``mscale`` = ``mscale_all_dim``), and a block where it is not
        has no program here."""
        if self.rope_factor <= 1:
            return None, 1.0
        if not self.rope_mscale \
                or self.rope_mscale != self.rope_mscale_all_dim:
            raise ValueError(
                f"rope_mscale {self.rope_mscale} and rope_mscale_all_dim "
                f"{self.rope_mscale_all_dim} put a factor other than 1 on "
                f"cos and sin, which nn/mla.py does not apply")
        return (yarn_frequencies(
            self.qk_rope_head_dim, self.rope_theta, self.rope_factor,
            self.rope_original_positions, self.rope_beta_fast,
            self.rope_beta_slow),
            yarn_mscale(self.rope_factor, self.rope_mscale_all_dim) ** 2)

    def device_counter_names(self) -> tuple:
        """``(metric, labels)`` of each entry of that leaf."""
        return tuple(
            (name, {"kind": kind, "layer": str(i),
                    **({"attn": "latent"} if name.startswith("attn_")
                       else {})})
            for kind in KINDS for i in range(self.num_layers)
            for name in COUNTERS)

    @nn.compact
    def __call__(self, tokens, *, train: bool = False,
                 decode: bool = False, last_only: bool = False,
                 return_hidden: bool = False, cache_positions=None,
                 token_mask=None, head_rows=None):
        """As :class:`models.llama.Llama` (``last_only``,
        ``return_hidden``, ``cache_positions``). ``token_mask`` (B, T)
        bool marks the real tokens, a left-aligned prefix of each row:
        the rest reach no expert and no counter (their rows of the
        result mean nothing). ``head_rows`` (B, K) int32: which of a
        sequence's T rows reach the final norm and the head
        (``nn.head_input``; all of them by default)."""
        del train   # no dropout, no auxiliary loss: the forward is one
        B, T = tokens.shape
        x = nn.Embed(self.vocab_size, self.d_model,
                     param_dtype=self.param_dtype,
                     name="tok_embed")(tokens).astype(self.dtype)
        layers = self._layers()
        freqs, score_factor = self.rotation()
        positions = real = None
        if decode:
            cache_index = self.variable(
                "cache", "cache_index", lambda: jnp.zeros((), jnp.int32))
            counters = self.variable(
                "cache", "device_counters", jnp.zeros,
                (len(KINDS) * len(layers) * len(COUNTERS),), jnp.uint32)
            if cache_positions is None:
                cache_positions = jnp.full((B,), cache_index.value)
                if not self.is_initializing():
                    cache_index.value = cache_index.value + T
            positions = cache_positions[:, None] + jnp.arange(T)[None]
            real = jnp.ones((B, T), bool) if token_mask is None \
                else token_mask
        counts = []
        for name, dense in layers:
            x, c = AXK1Block(
                num_heads=self.num_heads, q_lora_rank=self.q_lora_rank,
                kv_lora_rank=self.kv_lora_rank,
                qk_nope_head_dim=self.qk_nope_head_dim,
                qk_rope_head_dim=self.qk_rope_head_dim,
                v_head_dim=self.v_head_dim, rope_theta=self.rope_theta,
                rope_freqs=freqs, score_factor=score_factor,
                mlp_dim=self.mlp_dim if dense else 0,
                expert_mlp_dim=self.expert_mlp_dim,
                num_experts=self.num_experts, moe_topk=self.moe_topk,
                n_group=self.n_group, topk_group=self.topk_group,
                routed_scaling=self.routed_scaling,
                num_shared_experts=self.num_shared_experts,
                ep_size=self.ep_size, ep_rank=self.ep_rank,
                norm_eps=self.norm_eps, dtype=self.dtype,
                param_dtype=self.param_dtype, name=name,
            )(x, decode, positions, real)
            if c is not None:
                calls = jnp.full((1,), 0 if dense else 1, jnp.uint32)
                counts.append(jnp.concatenate([calls, c]))
        if counts:
            kind = KINDS.index("decode" if T == 1 else "prefill")
            per_kind = len(layers) * len(COUNTERS)
            counters.value = counters.value.at[
                kind * per_kind:(kind + 1) * per_kind].add(
                    jnp.concatenate(counts))
        with jax.named_scope("head"):
            x = head_input(x, last_only, head_rows)
            x = RMSNorm(eps=self.norm_eps, dtype=self.dtype,
                        param_dtype=self.param_dtype, name="final_norm")(x)
            if return_hidden:
                return x
            return nn.Dense(self.vocab_size, use_bias=False, dtype=jnp.float32,
                            param_dtype=self.param_dtype, name="lm_head")(x)


def _build(cfg: ModelConfig, ep_size: int) -> AXK1:
    """``extra`` overrides any size by its field's name."""
    policy = get_policy(cfg.dtype, cfg.compute_dtype)
    sizes = {k: v for k, v in cfg.extra.items()
             if k in AXK1.__dataclass_fields__}
    sizes.setdefault("ep_size", ep_size)
    return AXK1(**sizes, dtype=policy.compute_dtype,
                param_dtype=policy.param_dtype)


@register("ax_k1")
def build_ax_k1(cfg: ModelConfig) -> AXK1:
    """The whole language model: every expert held here."""
    return _build(cfg, ep_size=1)


@register("ax_k1_ep16")
def build_ax_k1_ep16(cfg: ModelConfig) -> AXK1:
    """Rank 0 of 16 chips that share each layer's 192 experts: 12 held;
    attention, shared expert, router and the dense layer whole
    (``extra`` cuts the depth and slices the vocabulary, which are the
    deployment's pipeline stage and its vocabulary shard)."""
    return _build(cfg, ep_size=16)
