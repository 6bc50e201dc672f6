"""K-EXAONE's language model (``LGAI-EXAONE/K-EXAONE-236B-A23B``'s
``config.json``, ``model_type`` ``exaone_moe``; the layer is EXAONE
4.0's, ``transformers`` 4.57 ``models/exaone4/modeling_exaone4.py``, and
the router DeepSeek-V3's, ``models/deepseek_v3/modeling_deepseek_v3.py``).

A post-norm block: no norm before a sublayer, one on its output::

    h   = x + N(Attn(x))
    out = h + N(FFN(h))

Attention is grouped-query with an RMSNorm over each head's dims of q
and k, and comes in two kinds laid out by ``layer_pattern`` ("LLLG":
three local layers to one global): a *local* layer rotates q and k and
sees a sliding window of ``window`` keys; a *global* layer sees every
key before it and rotates nothing. Served, a local layer's cache is a
ring of ``window`` rows a sequence and a global layer's a row for every
position (:class:`nn.attention.MultiHeadAttention`): two kinds of cache
leaf in one engine, which :meth:`KExaone.leaves_not_by_position` declares.

The first ``first_k_dense`` layers have a dense SwiGLU FFN; the others a
mixture of ``num_experts`` routed experts, ``moe_topk`` picks a token by
sigmoid scores renormalised over the picks and scaled, plus shared
experts every token goes through. The routed part is one rank's share
(:class:`parallel.expert.HeldExpertsMoE`): ``k_exaone`` holds every
expert, ``k_exaone_ep8`` the 16 of rank 0 of 8. The multi-token
prediction module of the checkpoint is not built (the config does not
say how its block is made, and the main model's logits do not depend on
it). The architecture, not the weights. Defaults are the published
sizes; tests shrink them through ``ModelConfig.extra``.

The parameter tree names the dense layers ``dense<j>`` and the sparse
ones ``layer<i>`` (model layer ``first_k_dense + i``), so that every
``layer<i>`` has the same leaves.

Served through the engine's ordinary contract, as
:class:`models.longcat_flash.LongcatFlash`: ``cache_index`` and
``device_counters`` (see :data:`COUNTERS`) ride in the ``cache``
collection beside the attentions' rows.
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
    ring_rows_scored,
)
from pytorch_distributed_nn_tpu.nn.dtypes import get_policy
from pytorch_distributed_nn_tpu.parallel.expert import HeldExpertsMoE

# what a layer counts in one program execution, over real tokens only:
# HeldExpertsMoE's routing counts (none in a dense layer), then the key
# rows inside the real queries' masks and the key rows the program
# read for them (the ring; a full layer's as the call's routine reads
# them, ``nn/attention.cache_rows_read``: a round's key blocks up to a
# row's depth, a blockwise prefill's key tiles, else the row whole)
COUNTERS = ("moe_calls_total", "moe_picks_total", "moe_held_pairs_total",
            "moe_held_experts_touched_total", "attn_rows_attended_total",
            "attn_rows_read_total")


class KExaoneBlock(nn.Module):
    num_heads: int
    num_kv_heads: int
    head_dim: int
    window: int            # 0: a global layer
    mlp_dim: int           # the dense FFN's width; 0: a sparse layer
    expert_mlp_dim: int
    num_experts: int
    moe_topk: int
    routed_scaling: float
    num_shared_experts: int
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
        block's output and its :data:`COUNTERS` after the first."""
        norm = lambda name: RMSNorm(  # noqa: E731
            eps=self.norm_eps, dtype=self.dtype,
            param_dtype=self.param_dtype, name=name)
        T = x.shape[1]
        attn = MultiHeadAttention(
            num_heads=self.num_heads, head_dim=self.head_dim,
            num_kv_heads=self.num_kv_heads, causal=True,
            rotary=self.window > 0, rope_theta=self.rope_theta,
            impl="auto", use_bias=False, window=self.window,
            qk_norm=True, norm_eps=self.norm_eps,
            dtype=self.dtype, param_dtype=self.param_dtype, name="attn")
        with jax.named_scope("kexaone/attn_window" if self.window
                             else "kexaone/attn_full"):
            if decode:
                a = attn(x, decode=True, cache_positions=positions[:, 0],
                         lengths=real.sum(axis=-1))
            else:
                a = attn(x)
        h = x + norm("post_attn_norm")(a)
        if self.mlp_dim:
            with jax.named_scope("kexaone/dense_ffn"):
                f = SwiGLU(self.mlp_dim, dtype=self.dtype,
                           param_dtype=self.param_dtype, name="ffn")(h)
            routing = jnp.zeros((3,), jnp.uint32)
        else:
            with jax.named_scope("kexaone/moe"):
                f, c = HeldExpertsMoE(
                    num_experts=self.num_experts, mlp_dim=self.expert_mlp_dim,
                    k=self.moe_topk, routed_scaling=self.routed_scaling,
                    scoring="sigmoid", renormalize=True,
                    ep_size=self.ep_size, ep_rank=self.ep_rank,
                    dtype=self.dtype, param_dtype=self.param_dtype,
                    name="moe")(h, token_mask=real)
            # every rank computes the shared experts alike, whole
            with jax.named_scope("kexaone/shared_expert"):
                f = f + SwiGLU(
                    self.expert_mlp_dim * self.num_shared_experts,
                    dtype=self.dtype, param_dtype=self.param_dtype,
                    name="shared_expert")(h)
            routing = c[jnp.asarray([0, 2, 3])]   # no zero experts here
        out = h + norm("post_ffn_norm")(f)
        if not decode or self.is_initializing():
            return out, None
        # the ring's rows for each real query; a full layer's as the
        # routine of this call reads them (``cache_rows_read``)
        if self.window:
            inside = jnp.minimum(positions + 1, self.window)
            read = real.sum() * ring_rows_scored(T, self.window)
        else:
            inside = positions + 1
            read = cache_rows_read(attn, T, positions, real)
        return out, jnp.concatenate([routing, jnp.stack([
            jnp.where(real, inside, 0).sum(), read]).astype(jnp.uint32)])


class KExaone(nn.Module):
    vocab_size: int = 153600
    num_layers: int = 48          # dense and sparse together
    d_model: int = 6144
    num_heads: int = 64
    num_kv_heads: int = 8
    head_dim: int = 128
    mlp_dim: int = 18432          # the leading dense layers' FFN
    first_k_dense: int = 1
    layer_pattern: str = "LLLG"   # L: sliding window, G: every key
    window: int = 128
    expert_mlp_dim: int = 2048
    num_experts: int = 128
    moe_topk: int = 8
    routed_scaling: float = 2.5
    num_shared_experts: int = 1
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
        """``(name in the parameter tree, window, dense)`` of every
        layer, in order."""
        if set(self.layer_pattern) - {"L", "G"} or not self.layer_pattern:
            raise ValueError(f"layer_pattern {self.layer_pattern!r}: a "
                             f"string of L (window) and G (global)")
        out = []
        for i in range(self.num_layers):
            local = self.layer_pattern[i % len(self.layer_pattern)] == "L"
            dense = i < self.first_k_dense
            out.append((f"dense{i}" if dense
                        else f"layer{i - self.first_k_dense}",
                        self.window if local else 0, dense))
        return tuple(out)

    def device_counter_names(self) -> tuple:
        """``(metric, labels)`` of each entry of that leaf."""
        return tuple(
            (name, {"kind": kind, "layer": str(i),
                    **({"attn": "window" if window else "full"}
                       if name.startswith("attn_") else {})})
            for kind in KINDS
            for i, (_, window, _) in enumerate(self._layers())
            for name in COUNTERS)

    def leaves_not_by_position(self) -> dict:
        """``{what they are: their paths in the ``cache`` collection}`` of
        the leaves that are not rows by absolute position. Here the
        rings: ``(slots, window, ...)``, row = position mod window. The
        serving engine keeps no prefix store for a model that has any
        (serve/engine.py says why)."""
        return {"ring (a sliding window's rows, position mod window)": tuple(
            (name, "attn", leaf)
            for name, window, _ in self._layers() if window
            for leaf in ("cached_key", "cached_value"))}

    @nn.compact
    def __call__(self, tokens, *, train: bool = False,
                 decode: bool = False, last_only: bool = False,
                 return_hidden: bool = False, cache_positions=None,
                 token_mask=None, head_rows=None):
        """As :class:`models.llama.Llama` (``last_only``,
        ``return_hidden``, ``cache_positions``). ``token_mask`` (B, T)
        bool marks the real tokens, a left-aligned prefix of each row:
        the rest reach no expert, no ring row and no counter (their rows
        of the result mean nothing). ``head_rows`` (B, K) int32: which
        of a sequence's T rows reach the final norm and the head
        (``nn.head_input``; all of them by default)."""
        del train   # no dropout, no auxiliary loss: the forward is one
        B, T = tokens.shape
        x = nn.Embed(self.vocab_size, self.d_model,
                     param_dtype=self.param_dtype,
                     name="tok_embed")(tokens).astype(self.dtype)
        layers = self._layers()
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
        for name, window, dense in layers:
            x, c = KExaoneBlock(
                num_heads=self.num_heads, num_kv_heads=self.num_kv_heads,
                head_dim=self.head_dim, window=window,
                mlp_dim=self.mlp_dim if dense else 0,
                expert_mlp_dim=self.expert_mlp_dim,
                num_experts=self.num_experts, moe_topk=self.moe_topk,
                routed_scaling=self.routed_scaling,
                num_shared_experts=self.num_shared_experts,
                ep_size=self.ep_size, ep_rank=self.ep_rank,
                rope_theta=self.rope_theta, norm_eps=self.norm_eps,
                dtype=self.dtype, param_dtype=self.param_dtype, name=name,
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


def _build(cfg: ModelConfig, ep_size: int) -> KExaone:
    """``extra`` overrides any size by its field's name."""
    policy = get_policy(cfg.dtype, cfg.compute_dtype)
    sizes = {k: v for k, v in cfg.extra.items()
             if k in KExaone.__dataclass_fields__}
    sizes.setdefault("ep_size", ep_size)
    return KExaone(**sizes, dtype=policy.compute_dtype,
                   param_dtype=policy.param_dtype)


@register("k_exaone")
def build_k_exaone(cfg: ModelConfig) -> KExaone:
    """The whole language model: every expert held here."""
    return _build(cfg, ep_size=1)


@register("k_exaone_ep8")
def build_k_exaone_ep8(cfg: ModelConfig) -> KExaone:
    """Rank 0 of 8 chips that share each layer's 128 experts: 16 held;
    attention, shared expert, router and the dense layer whole
    (``extra`` cuts the depth and slices the vocabulary, which are the
    deployment's pipeline stage and its vocabulary shard)."""
    return _build(cfg, ep_size=8)
