"""BERT-base — BASELINE.json config 3's model ("BERT-base pretraining,
large fused gradient buckets"; SURVEY.md §2a Models row).

Bidirectional encoder + masked-LM head. Pretraining uses the
``mlm_synthetic`` dataset (inputs with masked positions, labels -1 on
unmasked positions) with :func:`train.losses.masked_lm_xent`.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from flax import linen as nn

from pytorch_distributed_nn_tpu.config import ModelConfig
from pytorch_distributed_nn_tpu.models import register
from pytorch_distributed_nn_tpu.nn.attention import MultiHeadAttention
from pytorch_distributed_nn_tpu.nn.dtypes import get_policy


class EncoderBlock(nn.Module):
    num_heads: int
    mlp_dim: int
    dropout: float = 0.0
    ln_eps: float = 1e-12
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, mask=None, *, train: bool = False):
        # post-LN (original BERT): sublayer → add → LN
        d = x.shape[-1]
        # the scopes are metadata for obs/scopes.py
        with jax.named_scope("mixer"):
            y = MultiHeadAttention(
                num_heads=self.num_heads, head_dim=d // self.num_heads,
                causal=False, dtype=self.dtype,
                param_dtype=self.param_dtype, name="attn",
            )(x, mask=mask)
            if self.dropout:
                y = nn.Dropout(self.dropout, deterministic=not train)(y)
            x = nn.LayerNorm(epsilon=self.ln_eps, dtype=self.dtype,
                             param_dtype=self.param_dtype,
                             name="ln1")(x + y)
        with jax.named_scope("ffn"):
            y = nn.Dense(self.mlp_dim, dtype=self.dtype,
                         param_dtype=self.param_dtype, name="mlp_in")(x)
            y = nn.gelu(y)
            y = nn.Dense(d, dtype=self.dtype, param_dtype=self.param_dtype,
                         name="mlp_out")(y)
            if self.dropout:
                y = nn.Dropout(self.dropout, deterministic=not train)(y)
            return nn.LayerNorm(epsilon=self.ln_eps, dtype=self.dtype,
                                param_dtype=self.param_dtype,
                                name="ln2")(x + y)


class Bert(nn.Module):
    vocab_size: int = 30522
    num_layers: int = 12
    d_model: int = 768
    num_heads: int = 12
    mlp_dim: int = 3072
    max_len: int = 512
    type_vocab: int = 2
    dropout: float = 0.0
    # HF-conventional (BertConfig.layer_norm_eps): converted checkpoints
    # reproduce the original's logits without remembering an override
    ln_eps: float = 1e-12
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, tokens, *, train: bool = False,
                 attention_mask: Optional[jnp.ndarray] = None,
                 token_types: Optional[jnp.ndarray] = None):
        T = tokens.shape[1]
        if T > self.max_len:
            raise ValueError(
                f"sequence length {T} exceeds max_len {self.max_len}"
            )
        x = nn.Embed(self.vocab_size, self.d_model,
                     param_dtype=self.param_dtype, name="tok_embed")(tokens)
        pos = nn.Embed(self.max_len, self.d_model,
                       param_dtype=self.param_dtype,
                       name="pos_embed")(jnp.arange(T)[None])
        x = x + pos
        if token_types is not None:
            x = x + nn.Embed(self.type_vocab, self.d_model,
                             param_dtype=self.param_dtype,
                             name="type_embed")(token_types)
        x = nn.LayerNorm(epsilon=self.ln_eps, dtype=self.dtype,
                         param_dtype=self.param_dtype, name="ln_embed")(x.astype(self.dtype))
        for i in range(self.num_layers):
            x = EncoderBlock(
                num_heads=self.num_heads, mlp_dim=self.mlp_dim,
                dropout=self.dropout, ln_eps=self.ln_eps, dtype=self.dtype,
                param_dtype=self.param_dtype, name=f"layer{i}",
            )(x, mask=attention_mask, train=train)
        # MLM head: dense + gelu + LN, then decode to vocab
        with jax.named_scope("head"):
            x = nn.Dense(self.d_model, dtype=self.dtype,
                         param_dtype=self.param_dtype, name="mlm_dense")(x)
            x = nn.gelu(x)
            x = nn.LayerNorm(epsilon=self.ln_eps, dtype=self.dtype,
                             param_dtype=self.param_dtype, name="mlm_ln")(x)
            return nn.Dense(self.vocab_size, dtype=jnp.float32,
                            param_dtype=self.param_dtype,
                            name="mlm_decoder")(x)


@register("bert_base")
def build_bert_base(cfg: ModelConfig) -> Bert:
    policy = get_policy(cfg.dtype, cfg.compute_dtype)
    e = cfg.extra
    return Bert(
        vocab_size=e.get("vocab_size", 30522),
        num_layers=e.get("num_layers", 12),
        d_model=e.get("d_model", 768),
        num_heads=e.get("num_heads", 12),
        mlp_dim=e.get("mlp_dim", 3072),
        max_len=e.get("max_len", 512),
        dropout=e.get("dropout", 0.0),
        ln_eps=e.get("ln_eps", 1e-12),
        dtype=policy.compute_dtype,
        param_dtype=policy.param_dtype,
    )
