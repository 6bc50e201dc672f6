"""Multi-head latent attention (MLA) with a latent decode cache.

The attention of DeepSeek-V2 and LongCat-Flash (arXiv:2405.04434 sec.
2.1; arXiv:2509.01322): queries come through a low-rank bottleneck, and
keys and values are both expanded from ONE normalised latent of
``kv_lora_rank`` values a position plus one rotated key of
``qk_rope_head_dim`` values shared by every head::

    q         = a_q * (N(x W_qa) W_qb)      -> heads x [q_nope | q_rope]
    [c | k_r] = x W_kva;  c = a_kv * N(c);  k_r = RoPE(k_r)
    [k_nope | v] = c W_kvb                  -> heads x [nope | v]
    s = (q_nope . k_nope + RoPE(q_rope) . k_r) * m / sqrt(nope + rope)

``a_q``, ``a_kv`` and ``m`` are 1 in the plain form (DeepSeek-V3's;
A.X-K1's); LongCat-Flash multiplies q by ``sqrt(d / q_lora_rank)`` and
the latent by ``sqrt(d / kv_lora_rank)``, and a model whose rotation is
YaRN-scaled passes the per-pair frequencies
(:func:`nn.attention.yarn_frequencies`) and ``m = mscale**2``. The
cache holds ``c`` and ``k_r`` (576 values a position at the
published sizes, where 64 heads of K and V would be 20,480). Two paths
read that one cache:

- **expanded** (prefill, and the uncached forward): K and V are
  expanded from the latent once a call and the attention runs
  blockwise (:mod:`ops.pallas.prefix_attention`): a tile of float32
  scores, ``QUERY_BLOCK x KEY_BLOCK`` a head, lives under a running
  maximum and denominator and is never written out, and a block of
  queries visits only the key blocks at or under its largest position
  (a Pallas kernel on a TPU, the same recurrence in ``jax.numpy``
  elsewhere);
- **absorbed** (a decode round, one new token a row): ``W_kvb``'s key
  half is folded into the query and its value half into the output, so
  the round reads the latent once for all heads and never expands it:
  ``q_lat = q_nope W_kvb^K``, ``s = q_lat . c + q_rope . k_r``,
  ``o = (p . c) W_kvb^V``. The same numbers as the expanded form up to
  rounding.

Rotation pairs the rope dims as (2i, 2i + 1) (the published
"interleaved" form). The dims are de-interleaved once, which moves q
and k alike and leaves every dot product as it was, and then go through
:func:`nn.attention.rotary_embedding`, whose pairs are (i, i + D/2).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from flax import linen as nn

from pytorch_distributed_nn_tpu.models.llama import RMSNorm
from pytorch_distributed_nn_tpu.nn.attention import (
    _row_update,
    rotary_embedding,
)
from pytorch_distributed_nn_tpu.ops.pallas.prefix_attention import (
    KEY_BLOCK,
    QUERY_BLOCK,
    prefix_attention,
    seen_from,
)


def _deinterleave(x):
    """(..., D) with pairs (2i, 2i + 1) -> [evens | odds]."""
    pairs = x.reshape(x.shape[:-1] + (x.shape[-1] // 2, 2))
    return jnp.concatenate([pairs[..., 0], pairs[..., 1]], axis=-1)


def _masked_softmax(scores, visible, dtype):
    scores = jnp.where(visible, scores, -1e30)
    return jax.nn.softmax(scores, axis=-1).astype(dtype)


def expanded_attention(q_nope, q_rope, latent, rope_key, w_kvb, q_pos, *,
                       scale: float, real=None,
                       query_block: int = QUERY_BLOCK,
                       key_block: int = KEY_BLOCK):
    """Attention with K and V expanded from the latent.

    q_nope (B, T, H, dn), q_rope (B, T, H, dr); latent (B, S, r) and
    rope_key (B, S, dr) are the keys' side (the cache, or the T tokens
    themselves); w_kvb (r, H, dn + dv); q_pos (B, T) absolute positions:
    key s is visible to query t iff ``s <= q_pos[b, t]``, and no row
    past a query block's largest position is read. ``real`` (B, T) bool
    marks the fed tokens that are tokens: the rest (a prompt's padding)
    attend to nothing and their rows of the result are zeros. Returns
    (B, T, H, dv) in q's dtype."""
    B, S, dtype = latent.shape[0], latent.shape[1], q_nope.dtype
    H, dn = q_nope.shape[2:]
    kv = jnp.einsum("bsr,rhk->bhsk", latent, w_kvb,
                    preferred_element_type=jnp.float32).astype(dtype)
    # the two score terms as one product: the rotated key is every
    # head's
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(
        rope_key[:, None], (B, H, S, rope_key.shape[-1]))], axis=-1)
    q = jnp.concatenate([q_nope, q_rope], axis=-1).transpose(0, 2, 1, 3)
    out = prefix_attention(q, k, kv[..., dn:], seen_from(q_pos, real),
                           scale=scale, block_q=query_block,
                           block_k=key_block)
    return out.transpose(0, 2, 1, 3)


def absorbed_attention(q_nope, q_rope, latent, rope_key, w_kvb, q_pos, *,
                       scale: float):
    """One query a row against the latent cache, ``w_kvb`` absorbed.

    q_nope (B, H, dn), q_rope (B, H, dr), latent (B, S, r), rope_key
    (B, S, dr), q_pos (B,). Returns (B, H, dv)."""
    dn, dtype = q_nope.shape[-1], q_nope.dtype
    w_k, w_v = w_kvb[..., :dn], w_kvb[..., dn:]
    q_lat = jnp.einsum("bhk,rhk->bhr", q_nope, w_k,
                       preferred_element_type=jnp.float32).astype(dtype)
    s = jnp.einsum("bhr,bsr->bhs", q_lat, latent,
                   preferred_element_type=jnp.float32)
    s += jnp.einsum("bhk,bsk->bhs", q_rope, rope_key,
                    preferred_element_type=jnp.float32)
    visible = jnp.arange(latent.shape[1])[None, :] <= q_pos[:, None]
    p = _masked_softmax(s * scale, visible[:, None, :], dtype)
    o_lat = jnp.einsum("bhs,bsr->bhr", p, latent,
                       preferred_element_type=jnp.float32).astype(dtype)
    return jnp.einsum("bhr,rhk->bhk", o_lat, w_v,
                      preferred_element_type=jnp.float32).astype(dtype)


class MLAttention(nn.Module):
    """Causal multi-head latent attention (see the module docstring).

    ``decode=True`` keeps the latent cache in the flax ``cache``
    collection (``cached_latent`` (B, S, r) and ``cached_rope_key``
    (B, S, dr): leaves ``(slots, positions, ...)`` like every other
    cache the serving engine tree-maps over). ``cache_positions`` (B,)
    is then required: row i's tokens land at its own depth, as in
    :class:`nn.attention.MultiHeadAttention`'s per-row mode (the model
    above keeps the one shared index for the callers that have none).
    One fed token a row takes the absorbed path, more the expanded,
    where ``token_mask`` (B, T) bool, if given, marks the fed tokens
    that are tokens: a prompt's padding attends to nothing."""

    num_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rope_theta: float = 10000000.0
    norm_eps: float = 1e-5
    # what the plain form lacks and one model or another has
    q_multiplier: float = 1.0     # on q, after the up-projection
    kv_multiplier: float = 1.0    # on the normalised latent
    rope_freqs: Optional[tuple] = None   # per pair; None: rope_theta's
    score_factor: float = 1.0     # on the softmax scale (YaRN's mscale**2)
    q_up_name: str = "q_b"        # the query up-projection's leaf
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, decode: bool = False,
                 cache_positions: Optional[jax.Array] = None,
                 token_mask: Optional[jax.Array] = None):
        B, T, d = x.shape
        H, r = self.num_heads, self.kv_lora_rank
        dn, dr, dv = (self.qk_nope_head_dim, self.qk_rope_head_dim,
                      self.v_head_dim)
        dense = lambda f, name: nn.DenseGeneral(  # noqa: E731
            f, use_bias=False, dtype=self.dtype,
            param_dtype=self.param_dtype, name=name)
        norm = lambda name, dtype: RMSNorm(  # noqa: E731
            eps=self.norm_eps, dtype=dtype, param_dtype=self.param_dtype,
            name=name)

        q = dense((H, dn + dr), self.q_up_name)(
            norm("q_norm", self.dtype)(dense(self.q_lora_rank, "q_a")(x)))
        if self.q_multiplier != 1.0:
            q = q * jnp.asarray(self.q_multiplier, self.dtype)
        kv_a = dense(r + dr, "kv_a")(x)
        latent = norm("kv_norm", jnp.float32)(kv_a[..., :r])
        if self.kv_multiplier != 1.0:
            latent = latent * self.kv_multiplier
        latent = latent.astype(self.dtype)
        # a bare kernel, not a Dense: the absorbed path contracts it
        # with the query and with the output, never with the latent
        w_kvb = self.param(
            "kv_b", nn.initializers.lecun_normal(in_axis=0, out_axis=(1, 2)),
            (r, H, dn + dv), self.param_dtype).astype(self.dtype)

        if decode:
            if cache_positions is None:
                raise ValueError("MLAttention's cache is indexed per row: "
                                 "decode=True needs cache_positions")
            starts = cache_positions.astype(jnp.int32)
            positions = starts[:, None] + jnp.arange(T)[None]
        else:
            positions = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
        q_nope = q[..., :dn]
        q_rope, rope_key = rotary_embedding(
            _deinterleave(q[..., dn:]),
            _deinterleave(kv_a[..., r:])[:, :, None, :],
            theta=self.rope_theta, positions=positions,
            freqs=self.rope_freqs)
        q_rope = q_rope.astype(self.dtype)
        rope_key = rope_key[:, :, 0, :].astype(self.dtype)

        scale = (dn + dr) ** -0.5 * self.score_factor
        if decode:
            c_lat = self.variable("cache", "cached_latent", jnp.zeros,
                                  (B, T, r), self.dtype)
            c_key = self.variable("cache", "cached_rope_key", jnp.zeros,
                                  (B, T, dr), self.dtype)
            if self.is_initializing():
                # init only sizes the cache from its (B, max_len) input
                out = jnp.zeros((B, T, H, dv), self.dtype)
            else:
                c_lat.value = _row_update(c_lat.value, latent, starts)
                c_key.value = _row_update(c_key.value, rope_key, starts)
                if T == 1:
                    out = absorbed_attention(
                        q_nope[:, 0], q_rope[:, 0], c_lat.value,
                        c_key.value, w_kvb, starts, scale=scale)[:, None]
                else:
                    out = expanded_attention(
                        q_nope, q_rope, c_lat.value, c_key.value, w_kvb,
                        positions, scale=scale, real=token_mask)
        else:
            out = expanded_attention(q_nope, q_rope, latent, rope_key,
                                     w_kvb, positions, scale=scale,
                                     real=token_mask)
        return nn.DenseGeneral(
            d, axis=(-2, -1), use_bias=False, dtype=self.dtype,
            param_dtype=self.param_dtype, name="out")(out)
