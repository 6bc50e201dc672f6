"""SDAR's mixture-of-experts language model (``JetLM/SDAR-30B-A3B-Chat``
``config.json``, ``model_type`` ``sdar_moe``): the Qwen3-MoE block
(``transformers`` 4.57 ``models/qwen3_moe/modeling_qwen3_moe.py``) under
an attention mask that is *causal by blocks*, generated from by
diffusion over blocks.

A pre-norm block, every layer sparse::

    h   = x + Attn(N(x))
    out = h + MoE(N(h))

Attention is grouped-query with an RMSNorm over each head's dims of q
and k (one gain of ``head_dim`` for all heads) and rotates q and k at
their positions. A query at position ``p`` sees every key of its own
block of ``block_length`` positions and of every block before it
(``see(p) = p // B * B + B - 1``; ``B`` 1 is the causal mask): the one
departure from Qwen3-MoE in the forward pass
(:class:`nn.attention.MultiHeadAttention`, ``see_block``). The expert
layer picks ``moe_topk`` of ``num_experts`` by softmax scores
renormalised over the picks, no shared expert, as one rank's share
(:class:`parallel.expert.HeldExpertsMoE`; ``ep_size`` 1 holds them all).

The model does not predict the next token. Position ``p``'s row of
logits predicts position ``p`` itself, and a block of ``B`` positions
is written by *denoising*: its unknown positions are fed the embedding
of ``mask_token_id``, a forward of the block against the committed rows
before it proposes a token for each, some of them are taken, and the
forward repeats until none is masked. The finished block's keys and
values are those of a forward of its final tokens, the *commit*, which
the engine makes no forward of its own: the forward that takes the next
block's first step feeds the finished block before it. What a
served model of this kind tells its engine is :meth:`SdarMoe
.block_decoding`; the round that does it is the engine's
(``serve/engine.py``, ``_block_round``). The architecture, not the
weights. Defaults are the published sizes; tests shrink them through
``ModelConfig.extra``.

Served through the engine's ordinary contract, as
:class:`models.k_exaone.KExaone`: ``cache_index`` and
``device_counters`` (:data:`COUNTERS` a layer and a kind, then
:data:`BLOCK_COUNTERS`) ride in the ``cache`` collection beside the
attentions' rows, which are all rows by position: the prefix cache
stays on.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from flax import linen as nn

from pytorch_distributed_nn_tpu.config import ModelConfig
from pytorch_distributed_nn_tpu.models import register
from pytorch_distributed_nn_tpu.models.k_exaone import COUNTERS
from pytorch_distributed_nn_tpu.models.llama import RMSNorm
from pytorch_distributed_nn_tpu.models.longcat_flash import KINDS
from pytorch_distributed_nn_tpu.nn import head_input
from pytorch_distributed_nn_tpu.nn.attention import (
    MultiHeadAttention,
    cache_rows_read,
)
from pytorch_distributed_nn_tpu.nn.dtypes import get_policy
from pytorch_distributed_nn_tpu.parallel.expert import HeldExpertsMoE

# what the engine's round counts, over its live rows: forwards of a
# block (a row a round), those that unmasked nothing and only committed
# (none since a commit rides the next block's first step), masked
# positions that took a token, tokens handed to a request, forwards that
# wrote a finished block's rows beside a step of the next
BLOCK_COUNTERS = ("block_forwards_total", "block_commits_total",
                  "block_positions_unmasked_total",
                  "block_tokens_emitted_total",
                  "block_commits_fused_total")
REMASKING = ("sequential", "low_confidence_static",
             "low_confidence_dynamic")


class TokenTable(nn.Module):
    """The token embeddings, ``(vocab_size, d_model)``, initialised at
    the family's ``initializer_range`` (0.02: Qwen3-MoE draws every
    matrix at it, the embeddings too), about ``d_model ** -0.5``, the
    size of a projection's entries.

    The leaf is ``table`` and not flax's ``embedding`` for the sake of
    whoever fills it by name: a harness that gives a leaf called
    ``embedding`` unit variance (``benchmark/lib/weights.py``) makes
    the mask token's one row fifty times the published size, and that
    one row is the input at every position a block round decides. It
    then outweighs what attention brings from the context, the logits
    of every deciding position of every request are one row and a
    little, and how close bf16 comes to float32 is one draw a seed
    (``PERF.md`` sec. 6, PR 42). Under any other name the harness
    draws the table as it draws a kernel."""
    vocab_size: int
    d_model: int
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, tokens):
        table = self.param("table", nn.initializers.normal(0.02),
                           (self.vocab_size, self.d_model),
                           self.param_dtype)
        return jnp.take(table, tokens, axis=0)


class SdarMoeBlock(nn.Module):
    num_heads: int
    num_kv_heads: int
    head_dim: int
    expert_mlp_dim: int
    num_experts: int
    moe_topk: int
    ep_size: int
    ep_rank: int
    rope_theta: float
    norm_eps: float
    block_length: int
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
        attn = MultiHeadAttention(
            num_heads=self.num_heads, head_dim=self.head_dim,
            num_kv_heads=self.num_kv_heads, causal=True, rotary=True,
            rope_theta=self.rope_theta, impl="auto", use_bias=False,
            qk_norm=True, norm_eps=self.norm_eps,
            see_block=self.block_length, dtype=self.dtype,
            param_dtype=self.param_dtype, name="attn")
        a = norm("input_norm")(x)
        with jax.named_scope("sdar/attn"):
            if decode:
                a = attn(a, decode=True, cache_positions=positions[:, 0],
                         lengths=real.sum(axis=-1))
            else:
                a = attn(a)
        h = x + a
        with jax.named_scope("sdar/moe"):
            f, c = HeldExpertsMoE(
                num_experts=self.num_experts, mlp_dim=self.expert_mlp_dim,
                k=self.moe_topk, scoring="softmax", renormalize=True,
                ep_size=self.ep_size, ep_rank=self.ep_rank,
                dtype=self.dtype, param_dtype=self.param_dtype,
                name="moe")(norm("post_attn_norm")(h), token_mask=real)
        out = h + f
        if not decode or self.is_initializing():
            return out, None
        B = max(self.block_length, 1)
        seen = positions // B * B + B - 1
        return out, jnp.concatenate([c[jnp.asarray([0, 2, 3])], jnp.stack([
            jnp.where(real, seen + 1, 0).sum(),
            cache_rows_read(attn, T, seen, real),
        ]).astype(jnp.uint32)])


class SdarMoe(nn.Module):
    vocab_size: int = 151936
    num_layers: int = 48
    d_model: int = 2048
    num_heads: int = 32
    num_kv_heads: int = 4
    head_dim: int = 128
    expert_mlp_dim: int = 768
    num_experts: int = 128
    moe_topk: int = 8
    ep_size: int = 1
    ep_rank: int = 0
    rope_theta: float = 1000000.0
    norm_eps: float = 1e-6
    # generation (the family's ``generate.py``; the config has no key
    # for any of them): positions a block, forwards in which a whole
    # block is unmasked, which positions a forward unmasks, the
    # confidence over which ``low_confidence_dynamic`` takes them all,
    # the token a masked position is fed
    block_length: int = 4
    denoising_steps: int = 4
    remasking: str = "low_confidence_dynamic"
    confidence_threshold: float = 0.9
    mask_token_id: int = 151669
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32

    # the serving engine tells such a model which fed tokens are real
    takes_token_mask = True
    # where in the ``cache`` collection the running totals live
    device_counter_leaf = ("device_counters",)

    def block_decoding(self) -> dict:
        """What the serving engine needs to generate from this model a
        block at a time (its presence is what makes the engine's round
        a block round)."""
        if self.block_length < 1 or self.denoising_steps < 1 \
                or self.remasking not in REMASKING \
                or not 0 <= self.mask_token_id < self.vocab_size:
            raise ValueError(
                f"block_length {self.block_length}, denoising_steps "
                f"{self.denoising_steps}, remasking {self.remasking!r} "
                f"(of {REMASKING}), mask_token_id {self.mask_token_id} "
                f"under {self.vocab_size}")
        return dict(block_length=self.block_length,
                    denoising_steps=self.denoising_steps,
                    remasking=self.remasking,
                    confidence_threshold=self.confidence_threshold,
                    mask_token_id=self.mask_token_id)

    def device_counter_names(self) -> tuple:
        """``(metric, labels)`` of each entry of that leaf."""
        return tuple(
            (name, {"kind": kind, "layer": str(i),
                    **({"attn": "full"} if name.startswith("attn_")
                       else {})})
            for kind in KINDS for i in range(self.num_layers)
            for name in COUNTERS) + tuple(
                (name, {}) for name in BLOCK_COUNTERS)

    @staticmethod
    def add_block_counts(cache, counts):
        """``cache`` with the engine's :data:`BLOCK_COUNTERS` of one
        round, ``counts`` (one each), added to the leaf's last entries."""
        leaf = cache["device_counters"]
        n = len(BLOCK_COUNTERS)
        return {**cache, "device_counters": leaf.at[-n:].add(
            counts.astype(leaf.dtype))}

    @nn.compact
    def __call__(self, tokens, *, train: bool = False,
                 decode: bool = False, last_only: bool = False,
                 return_hidden: bool = False, cache_positions=None,
                 token_mask=None, block_round: bool = False,
                 head_rows=None):
        """As :class:`models.llama.Llama` (``last_only``,
        ``return_hidden``, ``cache_positions``), but row ``t`` of the
        result scores position ``t`` itself. ``token_mask`` (B, T) bool
        marks the real tokens, a left-aligned prefix of each row: the
        rest reach no expert and no counter. ``block_round`` says the
        call is the engine's round over every slot (counted as kind
        ``decode``; any other cached call is a ``prefill``), and
        ``head_rows`` (B, K) int32 which of a sequence's T rows reach
        the final norm and the head (all of them by default): a round
        scores a row's open block and not the finished one it feeds
        before it."""
        del train   # no dropout, no auxiliary loss: the forward is one
        B, T = tokens.shape
        x = TokenTable(self.vocab_size, self.d_model,
                       param_dtype=self.param_dtype,
                       name="tok_embed")(tokens).astype(self.dtype)
        positions = real = None
        if decode:
            cache_index = self.variable(
                "cache", "cache_index", lambda: jnp.zeros((), jnp.int32))
            counters = self.variable(
                "cache", "device_counters", jnp.zeros,
                (len(KINDS) * self.num_layers * len(COUNTERS)
                 + len(BLOCK_COUNTERS),), jnp.uint32)
            if cache_positions is None:
                cache_positions = jnp.full((B,), cache_index.value)
                if not self.is_initializing():
                    cache_index.value = cache_index.value + T
            positions = cache_positions[:, None] + jnp.arange(T)[None]
            real = jnp.ones((B, T), bool) if token_mask is None \
                else token_mask
        counts = []
        for i in range(self.num_layers):
            x, c = SdarMoeBlock(
                num_heads=self.num_heads, num_kv_heads=self.num_kv_heads,
                head_dim=self.head_dim, expert_mlp_dim=self.expert_mlp_dim,
                num_experts=self.num_experts, moe_topk=self.moe_topk,
                ep_size=self.ep_size, ep_rank=self.ep_rank,
                rope_theta=self.rope_theta, norm_eps=self.norm_eps,
                block_length=self.block_length, dtype=self.dtype,
                param_dtype=self.param_dtype, name=f"layer{i}",
            )(x, decode, positions, real)
            if c is not None:
                counts.append(jnp.concatenate(
                    [jnp.ones((1,), jnp.uint32), c]))
        if counts:
            kind = KINDS.index("decode" if block_round else "prefill")
            per_kind = self.num_layers * len(COUNTERS)
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


def _build(cfg: ModelConfig, **defaults) -> SdarMoe:
    """``extra`` overrides any size by its field's name; a key that is
    no field (the harness's ``mlp_dim``, the config's dense width: no
    layer of this model is dense) is dropped."""
    policy = get_policy(cfg.dtype, cfg.compute_dtype)
    sizes = {k: v for k, v in cfg.extra.items()
             if k in SdarMoe.__dataclass_fields__}
    return SdarMoe(**{**defaults, **sizes}, dtype=policy.compute_dtype,
                   param_dtype=policy.param_dtype)


@register("sdar_moe")
def build_sdar_moe(cfg: ModelConfig) -> SdarMoe:
    """The family as published: every expert held here, blocks of 4 in
    4 steps, ``low_confidence_dynamic``."""
    return _build(cfg)


@register("sdar_30b_a3b_seq2")
def build_sdar_30b_a3b_seq2(cfg: ModelConfig) -> SdarMoe:
    """``SDAR-30B-A3B-Chat`` stepped as the benchmark's cell steps it
    and not as published (that is ``sdar_moe``): two steps a block, the
    leftmost masked positions first, an order the plain reference can
    replay. ``benchmark/configs/sdar_30b_a3b.json`` says why, and its
    ``generation`` holds the same values
    (``benchmark/tests/test_costs_sdar.py`` holds the two together)."""
    return _build(cfg, denoising_steps=2, remasking="sequential")
