"""Operations and bytes LongCat-Flash's served rank needs, from shapes.

The numerators of ``decode_hbm_share`` and ``prefill_flops_share`` in
this model's cell (``configs/longcat_flash_omni.json``'s keys). As in
``costs.py`` each counts the least the mathematics asks for: a
multiply-add is 2 operations; padding, masked positions and experts no
token picked count nothing. ``benchmark/tests/test_costs_longcat.py``
pins each on a hand-worked shape.
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


def router_params(cfg: dict) -> int:
    """The router keeps its whole width: every routed expert of the
    deployment and the zero-compute ones."""
    routed = cfg["n_routed_experts"] * cfg["expert_parallel"]["ep_size"]
    return cfg["hidden_size"] * (routed + cfg["zero_expert_num"])


def expert_params(cfg: dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * cfg["hidden_size"] * cfg["expert_ffn_hidden_size"]


def layer_params_outside_experts(cfg: dict) -> int:
    """A double layer without its experts: two attentions, two dense
    FFNs, the router."""
    return (2 * mla_params(cfg)
            + 2 * 3 * cfg["hidden_size"] * cfg["ffn_hidden_size"]
            + router_params(cfg))


def latent_bytes_per_position(cfg: dict) -> int:
    """One cached position: the latent and the shared rotated key, for
    the two attentions of every layer."""
    return (2 * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])
            * _BYTES[cfg["torch_dtype"]] * cfg["num_layers"])


def decode_round_bytes(cfg: dict, experts_touched: float,
                       rows: float) -> float:
    """What one decode round must read: every matrix outside the
    experts and the head's slice once; the held experts some token of
    the round picked (``experts_touched``: their number summed over the
    layers); the latent cache of the ``rows`` positions the round's
    tokens attend over, all layers."""
    b = _BYTES[cfg["torch_dtype"]]
    return ((cfg["num_layers"] * layer_params_outside_experts(cfg)
             + cfg["hidden_size"] * cfg["vocab_size"]
             + experts_touched * expert_params(cfg)) * b
            + rows * latent_bytes_per_position(cfg))


def prefill_flops(cfg: dict, prompt_len: int,
                  pairs_per_token_layer: float) -> float:
    """One prompt through the rank, last position to the vocabulary:
    2 x the matrices outside the experts per token (K and V are
    expanded from the latent for each of the prompt's positions, which
    ``mla_params`` counts), causal scores at the MLA head sizes (QK^T
    over 192 and PV over 128, lower triangle), the token-expert pairs
    routed to this rank's experts (``pairs_per_token_layer``: their
    mean number a token a layer; zero-compute picks cost nothing) and
    one row of the head."""
    h = cfg["num_attention_heads"]
    layers = cfg["num_layers"]
    score_width = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
                   + cfg["v_head_dim"])
    return (2.0 * layers * layer_params_outside_experts(cfg) * prompt_len
            + 2.0 * layers * h * score_width * prompt_len * prompt_len
            + 2.0 * layers * pairs_per_token_layer * expert_params(cfg)
            * prompt_len
            + 2.0 * cfg["hidden_size"] * cfg["vocab_size"])
