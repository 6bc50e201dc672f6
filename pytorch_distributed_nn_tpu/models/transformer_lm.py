"""Decoder-only Transformer-LM — BASELINE.json config 4's model
("Transformer-LM pipeline-parallel"; SURVEY.md §2a Models row).

GPT-style pre-LN blocks. The block stack is written as a single scanned
module when ``remat`` is on — ``nn.remat_scan`` gives O(1) compile-time in
depth and rematerialised activations (SURVEY.md §7 hard part (e)); the
pipeline strategy instead slices the stack into per-stage segments.
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
from flax import linen as nn

from pytorch_distributed_nn_tpu.config import ModelConfig
from pytorch_distributed_nn_tpu.models import register
from pytorch_distributed_nn_tpu.nn import head_input
from pytorch_distributed_nn_tpu.nn.attention import MultiHeadAttention
from pytorch_distributed_nn_tpu.nn.dtypes import get_policy


class DecoderBlock(nn.Module):
    num_heads: int
    mlp_dim: int
    dropout: float = 0.0
    ln_eps: float = 1e-5
    attn_impl: str = "auto"
    # FFN override hook: (block, y, train) -> y, creating its submodules in
    # the block's scope. None = the standard dense MLP. This is how the MoE
    # family (models/moe_lm.py) swaps in expert layers without duplicating
    # the block.
    ffn: Optional[Callable] = None
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = False, decode: bool = False,
                 cache_positions=None):
        d = x.shape[-1]
        # the scopes are metadata for obs/scopes.py
        with jax.named_scope("mixer"):
            y = nn.LayerNorm(epsilon=self.ln_eps, dtype=self.dtype,
                             param_dtype=self.param_dtype, name="ln1")(x)
            y = MultiHeadAttention(
                num_heads=self.num_heads, head_dim=d // self.num_heads,
                causal=True, impl=self.attn_impl, dtype=self.dtype,
                param_dtype=self.param_dtype, name="attn",
            )(y, decode=decode, cache_positions=cache_positions)
            if self.dropout:
                y = nn.Dropout(self.dropout, deterministic=not train)(y)
            x = x + y
        with jax.named_scope("ffn"):
            y = nn.LayerNorm(epsilon=self.ln_eps, dtype=self.dtype,
                             param_dtype=self.param_dtype, name="ln2")(x)
            if self.ffn is not None:
                y = self.ffn(self, y, train)
            else:
                y = nn.Dense(self.mlp_dim, dtype=self.dtype,
                             param_dtype=self.param_dtype, name="mlp_in")(y)
                y = nn.gelu(y)
                y = nn.Dense(d, dtype=self.dtype,
                             param_dtype=self.param_dtype,
                             name="mlp_out")(y)
            if self.dropout:
                y = nn.Dropout(self.dropout, deterministic=not train)(y)
            return x + y


class TransformerLM(nn.Module):
    vocab_size: int = 32000
    num_layers: int = 12
    d_model: int = 768
    num_heads: int = 12
    mlp_dim: int = 3072
    max_len: int = 2048
    dropout: float = 0.0
    # HF-conventional (GPT2Config.layer_norm_epsilon): converted
    # checkpoints reproduce the original's logits without an override.
    # COMPAT: the round-1 default was 1e-6 (bert/vit: 1e-12) — a round-1
    # checkpoint restored without extra={'ln_eps': 1e-6} sees slightly
    # different forward math (same caveat class as the resnet padding
    # note in models/resnet.py).
    ln_eps: float = 1e-5
    remat: bool = False
    attn_impl: str = "auto"
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32
    # subclasses whose routing is chunk-global (MoE) turn this off
    supports_decode: bool = True

    def block_kwargs(self) -> dict:
        return dict(num_heads=self.num_heads, mlp_dim=self.mlp_dim,
                    dropout=self.dropout, attn_impl=self.attn_impl,
                    ln_eps=self.ln_eps, dtype=self.dtype,
                    param_dtype=self.param_dtype)

    def layer_ffn(self, i: int) -> Optional[Callable]:
        """Per-layer FFN override for block i (see DecoderBlock.ffn).
        The base LM uses the dense MLP everywhere; the MoE subclass
        returns expert layers on its cadence."""
        return None

    @nn.compact
    def __call__(self, tokens, *, train: bool = False,
                 positions: Optional[jnp.ndarray] = None,
                 decode: bool = False, last_only: bool = False,
                 return_hidden: bool = False, cache_positions=None,
                 head_rows=None):
        """``head_rows`` (B, K) int32: which of a sequence's T rows reach
        the final norm and the head (``nn.head_input``; all of them by
        default, the last with ``last_only``)."""
        T = tokens.shape[1]
        if T > self.max_len:
            raise ValueError(
                f"sequence length {T} exceeds max_len {self.max_len}"
            )
        x = nn.Embed(self.vocab_size, self.d_model,
                     param_dtype=self.param_dtype, name="tok_embed")(tokens)
        if decode and not self.supports_decode:
            # MoE routing is group-global (capacity and prior-claim
            # counts depend on every token in the chunk), so cached
            # decode would silently break generate()'s token-identity
            # contract — reject like pipeline.py does.
            raise ValueError(
                f"{type(self).__name__} does not support decode caching"
            )
        if decode and positions is not None:
            raise ValueError(
                "decode mode derives positions from the cache counter; "
                "an explicit `positions` argument would be ignored"
            )
        if decode:
            # the learned positional table needs absolute positions, so
            # the model keeps its own running index next to the
            # attention layers' KV cache_index vars. In per-row mode
            # (cache_positions given) each row's position comes from its
            # own cache depth instead, and the shared counter is left
            # untouched — rows at different depths share one batch.
            pos_index = self.variable(
                "cache", "pos_index", lambda: jnp.zeros((), jnp.int32)
            )
            if not self.is_initializing():
                if cache_positions is not None:
                    positions = (cache_positions.astype(jnp.int32)[:, None]
                                 + jnp.arange(T)[None])
                else:
                    positions = pos_index.value + jnp.arange(T)[None]
                    pos_index.value = pos_index.value + T
        if positions is None:
            positions = jnp.arange(T)[None]
        pos = nn.Embed(self.max_len, self.d_model,
                       param_dtype=self.param_dtype,
                       name="pos_embed")(positions)
        x = (x + pos).astype(self.dtype)
        block_cls = DecoderBlock
        if self.remat:
            # static_argnums counts (self, x, train, decode) — train must
            # be static or `deterministic=not train` fails on a tracer
            block_cls = nn.remat(DecoderBlock, static_argnums=(2, 3))
        for i in range(self.num_layers):
            x = block_cls(**self.block_kwargs(), ffn=self.layer_ffn(i),
                          name=f"block{i}")(x, train, decode,
                                            cache_positions)
        with jax.named_scope("head"):
            x = head_input(x, last_only, head_rows)
            x = nn.LayerNorm(epsilon=self.ln_eps, dtype=self.dtype,
                             param_dtype=self.param_dtype, name="ln_f")(x)
            if return_hidden:
                return x
            return nn.Dense(self.vocab_size, use_bias=False, dtype=jnp.float32,
                            param_dtype=self.param_dtype, name="lm_head")(x)


@register("transformer_lm")
def build_transformer_lm(cfg: ModelConfig) -> TransformerLM:
    policy = get_policy(cfg.dtype, cfg.compute_dtype)
    e = cfg.extra
    return TransformerLM(
        vocab_size=e.get("vocab_size", 32000),
        num_layers=e.get("num_layers", 12),
        d_model=e.get("d_model", 768),
        num_heads=e.get("num_heads", 12),
        mlp_dim=e.get("mlp_dim", 3072),
        max_len=e.get("max_len", 2048),
        dropout=e.get("dropout", 0.0),
        ln_eps=e.get("ln_eps", 1e-5),
        remat=cfg.remat,
        attn_impl=e.get("attn_impl", "auto"),
        dtype=policy.compute_dtype,
        param_dtype=policy.param_dtype,
    )
