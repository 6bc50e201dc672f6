"""Llama-3-style decoder — BASELINE.json config 5's model ("Llama-3-8B
sharded data-parallel"; SURVEY.md §2a Models row).

RMSNorm, rotary embeddings (theta 500k), SwiGLU MLP, grouped-query
attention (32 q heads / 8 kv heads at 8B scale), no biases, untied LM
head — the architecture, not the weights (zero-egress container). The
``llama3_8b`` builder defaults to the real 8B dims; tests shrink via
``ModelConfig.extra``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax.ad_checkpoint import checkpoint_name

from pytorch_distributed_nn_tpu.config import ModelConfig
from pytorch_distributed_nn_tpu.models import register
from pytorch_distributed_nn_tpu.nn import head_input
from pytorch_distributed_nn_tpu.nn.attention import MultiHeadAttention
from pytorch_distributed_nn_tpu.nn.dtypes import get_policy
from pytorch_distributed_nn_tpu.nn.quantized import Int8Dense, Int8Embed


class RMSNorm(nn.Module):
    eps: float = 1e-5
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],),
                           self.param_dtype)
        x32 = x.astype(jnp.float32)
        normed = x32 * jax.lax.rsqrt(
            jnp.mean(x32 * x32, axis=-1, keepdims=True) + self.eps
        )
        return (normed * scale).astype(self.dtype)


class LlamaBlock(nn.Module):
    num_heads: int
    num_kv_heads: int
    mlp_dim: int
    rope_theta: float = 500000.0
    norm_eps: float = 1e-5
    attn_impl: str = "auto"
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32
    quantized: bool = False
    cache_dtype: str = "compute"
    fused_proj: bool = False

    @nn.compact
    def __call__(self, x, train: bool = False, decode: bool = False,
                 cache_positions=None, lora=None, lengths=None):
        # inert tag unless the enclosing remat uses a name-aware policy
        # (remat_offload): then this marks the block boundary as
        # offloadable to pinned host memory instead of living in HBM
        # for the whole backward (the MaxText long-context pattern)
        x = checkpoint_name(x, "block_in")
        d = x.shape[-1]
        # the scopes are metadata for obs/scopes.py: the norm and the
        # residual add count with the part they stand around
        with jax.named_scope("mixer"):
            y = RMSNorm(eps=self.norm_eps, dtype=self.dtype,
                        param_dtype=self.param_dtype, name="attn_norm")(x)
            y = MultiHeadAttention(
                num_heads=self.num_heads, head_dim=d // self.num_heads,
                num_kv_heads=self.num_kv_heads, causal=True, rotary=True,
                rope_theta=self.rope_theta, impl=self.attn_impl,
                use_bias=False, dtype=self.dtype,
                param_dtype=self.param_dtype, quantized=self.quantized,
                cache_dtype=self.cache_dtype,
                fused_qkv=self.quantized and self.fused_proj,
                name="attn",
            )(y, decode=decode, cache_positions=cache_positions, lora=lora,
              lengths=lengths)
            x = x + y
        with jax.named_scope("ffn"):
            y = RMSNorm(eps=self.norm_eps, dtype=self.dtype,
                        param_dtype=self.param_dtype, name="mlp_norm")(x)
            if self.quantized:
                dense = lambda f, name: Int8Dense(  # noqa: E731
                    f, dtype=self.dtype, name=name)
            else:
                dense = lambda f, name: nn.Dense(  # noqa: E731
                    f, use_bias=False, dtype=self.dtype,
                    param_dtype=self.param_dtype, name=name)
            if self.quantized and self.fused_proj:
                # one int8 matmul for gate|up (exact: per-out-channel
                # scales are concat-invariant) — decode is per-op-launch
                # bound, see MultiHeadAttention.fused_qkv
                gate_up = dense(2 * self.mlp_dim, "gate_up")(y)
                gate = gate_up[..., :self.mlp_dim]
                up = gate_up[..., self.mlp_dim:]
            else:
                gate = dense(self.mlp_dim, "gate_proj")(y)
                up = dense(self.mlp_dim, "up_proj")(y)
            y = dense(d, "down_proj")(nn.silu(gate) * up)
            return x + y


class Llama(nn.Module):
    vocab_size: int = 128256
    num_layers: int = 32
    d_model: int = 4096
    num_heads: int = 32
    num_kv_heads: int = 8
    mlp_dim: int = 14336
    rope_theta: float = 500000.0
    # rms_norm_eps: 1e-5 for Llama-3 (HF default is 1e-6 — set
    # extra["norm_eps"] to the checkpoint's value when converting)
    norm_eps: float = 1e-5
    remat: bool = False
    remat_offload: bool = False
    attn_impl: str = "auto"
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32
    # weight-only int8 (nn/quantized.py): every kernel stored int8 with
    # per-out-channel scales, dequantized in VMEM by the Pallas matmul.
    # ~8 GB for the true 8B params — the mode that fits the flagship on
    # one 16 GB v5e chip (inference path; training stays float)
    quantized: bool = False
    # decode KV-cache storage ("compute" | "int8"): int8 halves cache
    # HBM via per-(token, head) scales (nn/attention.py), roughly
    # doubling the servable decode batch on one chip
    cache_dtype: str = "compute"
    # quantized path: fused qkv / gate|up projection kernels (fewer,
    # larger int8 matmuls — decode latency is per-op-launch bound;
    # +8% at b=1, docs/design.md "Int8 decode"). Default OFF: the
    # unfused tree is the persisted int8 checkpoint layout contract
    # (ops/pallas/int8_matmul.py storage note), and flipping it
    # silently would break restores of existing quantized trees.
    # bench's decode path and new conversions opt in via
    # model.extra["fused_proj"] = True.
    fused_proj: bool = False

    # the serving engine tells such a model which fed tokens are real
    takes_token_mask = True

    @nn.compact
    def __call__(self, tokens, *, train: bool = False,
                 decode: bool = False, last_only: bool = False,
                 return_hidden: bool = False, cache_positions=None,
                 lora_bank=None, adapter_ids=None, head_rows=None,
                 token_mask=None):
        """``last_only`` returns logits for the final position only
        (B, 1, V) — decode prefill needs just the next-token row, and
        at real vocab sizes the (P-1) unused head projections dominate
        prefill cost. ``head_rows`` (B, K) int32 names the rows instead
        (``nn.head_input``): a served prefill's last real position of
        each padded row, (B, K, V). ``return_hidden`` skips the lm_head
        and returns the final-norm'd (B, T, D) trunk output — the
        chunked-xent path (train/losses.py) applies the head blockwise
        so full logits never materialize. ``cache_positions`` (B,)
        int32: per-row KV cache indices for continuous batching — see
        nn.attention.MultiHeadAttention. ``token_mask`` (B, T) bool
        (decode only) marks the real tokens, a left-aligned prefix of
        each row: the attention is told how many (``lengths``), so that
        a bucket's padding and a slot that is not live attend to nothing
        where the routine can skip them.

        ``lora_bank`` + ``adapter_ids``: per-request LoRA (nn/lora.py).
        The bank is the stacked ``(n, L, ...)`` factor dict; each batch
        row selects its adapter via ``adapter_ids`` (B,) int32 — one
        gather per factor per layer, so rows on different fine-tunes
        share one batched forward (the multi-tenant serving path)."""
        if self.quantized:
            x = Int8Embed(self.vocab_size, self.d_model,
                          dtype=self.dtype, name="tok_embed")(tokens)
        else:
            x = nn.Embed(self.vocab_size, self.d_model,
                         param_dtype=self.param_dtype,
                         name="tok_embed")(tokens).astype(self.dtype)
        if self.remat_offload and not self.remat:
            raise ValueError(
                "remat_offload moves remat-saved block boundaries to "
                "host RAM — it needs model.remat=True (without remat "
                "there are no saved boundaries to offload, and "
                "silently ignoring the flag would let a run expected "
                "to fit via offload OOM instead)"
            )
        if self.remat:
            # remat_offload moves the saved block-boundary activations
            # (the "block_in" tags) to pinned host RAM: HBM then holds
            # only the layer being recomputed, which is what makes
            # 128k-token single-chip training fit (device<->host DMA
            # overlaps with the backward's compute)
            policy = None
            if self.remat_offload:
                policy = jax.checkpoint_policies.\
                    save_and_offload_only_these_names(
                        names_which_can_be_saved=[],
                        names_which_can_be_offloaded=["block_in"],
                        offload_src="device", offload_dst="pinned_host",
                    )
            block_cls = nn.remat(LlamaBlock, static_argnums=(2, 3),
                                 policy=policy)
        else:
            block_cls = LlamaBlock
        if lora_bank is not None:
            from pytorch_distributed_nn_tpu.nn.lora import layer_slice
            ids = adapter_ids
            if ids is None:
                ids = jnp.zeros((tokens.shape[0],), jnp.int32)
        lengths = None if token_mask is None else token_mask.sum(axis=-1)
        for i in range(self.num_layers):
            if lora_bank is None:
                lora = None
            else:
                # gather each row's adapter factors for this layer —
                # lora stays a traced positional so the remat wrapper
                # (static_argnums covers train/decode only) is happy
                lora = tuple(f[ids] for f in layer_slice(lora_bank, i))
            x = block_cls(
                num_heads=self.num_heads, num_kv_heads=self.num_kv_heads,
                mlp_dim=self.mlp_dim, rope_theta=self.rope_theta,
                norm_eps=self.norm_eps,
                attn_impl=self.attn_impl, dtype=self.dtype,
                param_dtype=self.param_dtype, quantized=self.quantized,
                cache_dtype=self.cache_dtype,
                fused_proj=self.fused_proj,
                name=f"layer{i}",
            )(x, train, decode, cache_positions, lora, lengths)
        with jax.named_scope("head"):
            x = head_input(x, last_only, head_rows)
            x = RMSNorm(eps=self.norm_eps, dtype=self.dtype,
                        param_dtype=self.param_dtype, name="final_norm")(x)
            if return_hidden:
                return x
            if self.quantized:
                return Int8Dense(self.vocab_size, dtype=jnp.float32,
                                 name="lm_head")(x)
            return nn.Dense(self.vocab_size, use_bias=False,
                            dtype=jnp.float32, param_dtype=self.param_dtype,
                            name="lm_head")(x)


@register("llama3_8b")
def build_llama3_8b(cfg: ModelConfig) -> Llama:
    policy = get_policy(cfg.dtype, cfg.compute_dtype)
    e = cfg.extra
    return Llama(
        vocab_size=e.get("vocab_size", 128256),
        num_layers=e.get("num_layers", 32),
        d_model=e.get("d_model", 4096),
        num_heads=e.get("num_heads", 32),
        num_kv_heads=e.get("num_kv_heads", 8),
        mlp_dim=e.get("mlp_dim", 14336),
        rope_theta=e.get("rope_theta", 500000.0),
        norm_eps=e.get("norm_eps", 1e-5),
        remat=cfg.remat,
        remat_offload=cfg.remat_offload,
        attn_impl=e.get("attn_impl", "auto"),
        quantized=e.get("quantized", False),
        cache_dtype=e.get("cache_dtype", "compute"),
        fused_proj=e.get("fused_proj", False),
        dtype=policy.compute_dtype,
        param_dtype=policy.param_dtype,
    )
