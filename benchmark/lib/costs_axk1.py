"""Operations and bytes A.X-K1's served rank needs, from shapes.

The numerators of ``decode_hbm_share`` and ``prefill_flops_share`` in
this model's cell (``configs/ax_k1.json``'s keys). As in ``costs.py``
each counts the least the mathematics asks for: a multiply-add is 2
operations; padding, positions after the query, positions restored from
the prefix store (their latent rows are read, not computed again) and
experts no token picked count nothing.
``benchmark/tests/test_costs_axk1.py`` pins each on a hand-worked shape.
"""

from __future__ import annotations

_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def mla_params(cfg: dict) -> int:
    """One latent attention's matrices: query down and up, the joint
    latent-and-rope-key projection, the expansion to every head's
    no-rope key and value, the output."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return (d * cfg["q_lora_rank"] + cfg["q_lora_rank"] * h * qk
            + d * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])
            + cfg["kv_lora_rank"] * h
            * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"])
            + h * cfg["v_head_dim"] * d)


def expert_params(cfg: dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def router_params(cfg: dict) -> int:
    """The router keeps its whole width: every routed expert of the
    deployment."""
    return cfg["hidden_size"] * cfg["n_routed_experts"] \
        * cfg["expert_parallel"]["ep_size"]


def dense_layer_params(cfg: dict) -> int:
    return mla_params(cfg) + 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def sparse_layer_params_outside_experts(cfg: dict) -> int:
    """A sparse layer without its routed experts: attention, the shared
    experts (one SwiGLU of their joint width), the router."""
    return (mla_params(cfg) + cfg["n_shared_experts"] * expert_params(cfg)
            + router_params(cfg))


def layer_counts(cfg: dict) -> dict:
    dense = min(cfg["first_k_dense_replace"], cfg["num_hidden_layers"])
    return dict(dense=dense, sparse=cfg["num_hidden_layers"] - dense)


def params_outside_experts(cfg: dict) -> int:
    """Every matrix a token goes through whatever it picks, all layers."""
    n = layer_counts(cfg)
    return (n["dense"] * dense_layer_params(cfg)
            + n["sparse"] * sparse_layer_params_outside_experts(cfg))


def latent_bytes_per_position(cfg: dict) -> int:
    """One cached position: the latent and the shared rotated key of
    every layer."""
    return ((cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])
            * _BYTES[cfg["torch_dtype"]] * cfg["num_hidden_layers"])


def decode_round_bytes(cfg: dict, experts_touched: float,
                       rows: float) -> float:
    """What one decode round must read: every matrix outside the routed
    experts and the head's slice once; the held experts some token of
    the round picked (``experts_touched``: their number summed over the
    sparse layers); the latent cache of the ``rows`` positions the
    round's tokens attend over (a token at depth ``p``, itself included,
    attends ``p``; summed over the round's tokens), all layers."""
    return ((params_outside_experts(cfg)
             + cfg["hidden_size"] * cfg["vocab_size"]
             + experts_touched * expert_params(cfg))
            * _BYTES[cfg["torch_dtype"]]
            + rows * latent_bytes_per_position(cfg))


def mask_pairs(tokens: int, cached: int) -> int:
    """(query, key) pairs inside the causal mask of ``tokens`` queries
    that stand behind ``cached`` restored positions: every query sees
    the restored rows, and the lower triangle of its own."""
    return tokens * cached + tokens * (tokens + 1) // 2


def prefill_flops(cfg: dict, tokens: int, cached: int,
                  pairs_per_token_layer: float) -> float:
    """One prefill of ``tokens`` prompt tokens behind ``cached`` restored
    positions (0: the whole prompt), last position to the vocabulary:
    2 x the matrices outside the routed experts per token prefilled (K
    and V are expanded from the latent for each of them, which
    ``mla_params`` counts; the restored positions' expansion is the
    expanded path's own cost, not the mathematics'); scores inside the
    mask at the MLA head sizes (QK^T over nope + rope, PV over v, every
    head); the token-expert pairs routed to this rank's experts
    (``pairs_per_token_layer``: their mean number a token a sparse
    layer); one row of the head."""
    n = layer_counts(cfg)
    score_width = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
                   + cfg["v_head_dim"])
    return (2.0 * params_outside_experts(cfg) * tokens
            + 2.0 * cfg["num_hidden_layers"] * cfg["num_attention_heads"]
            * score_width * mask_pairs(tokens, cached)
            + 2.0 * n["sparse"] * pairs_per_token_layer * expert_params(cfg)
            * tokens
            + 2.0 * cfg["hidden_size"] * cfg["vocab_size"])
