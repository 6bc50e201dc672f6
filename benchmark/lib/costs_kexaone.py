"""Operations and bytes K-EXAONE's served rank needs, from shapes.

The numerators of ``decode_hbm_share`` and ``prefill_flops_share`` in
this model's cell (``configs/k_exaone_236b.json``'s keys). As in
``costs.py`` each counts the least the mathematics asks for: a
multiply-add is 2 operations; padding, positions outside a mask (beyond
the window, after the query) and experts no token picked count nothing.
``benchmark/tests/test_costs_kexaone.py`` pins each on a hand-worked
shape.
"""

from __future__ import annotations

_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def attn_params(cfg: dict) -> int:
    """One attention's matrices: query, key, value, output (the two
    norm gains of ``head_dim`` are a few hundred bytes: left out)."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    return d * q + 2 * d * kv + q * d


def expert_params(cfg: dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def router_params(cfg: dict) -> int:
    """The router keeps its whole width: every routed expert of the
    deployment."""
    return cfg["hidden_size"] * cfg["num_experts"] \
        * cfg["expert_parallel"]["ep_size"]


def dense_layer_params(cfg: dict) -> int:
    return attn_params(cfg) + 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def sparse_layer_params_outside_experts(cfg: dict) -> int:
    """A sparse layer without its routed experts: attention, the shared
    experts (one SwiGLU of their joint width), the router."""
    return (attn_params(cfg) + cfg["num_shared_experts"] * expert_params(cfg)
            + router_params(cfg))


def layer_counts(cfg: dict) -> dict:
    """How many of the configuration's layers are dense, sparse,
    sliding-window and full."""
    n = cfg["num_hidden_layers"]
    window = sum(t == "sliding_attention" for t in cfg["layer_types"][:n])
    dense = sum(t == "dense" for t in cfg["mlp_layer_types"][:n])
    return dict(dense=dense, sparse=n - dense, window=window, full=n - window)


def params_outside_experts(cfg: dict) -> int:
    """Every matrix a token goes through whatever it picks, all layers."""
    n = layer_counts(cfg)
    return (n["dense"] * dense_layer_params(cfg)
            + n["sparse"] * sparse_layer_params_outside_experts(cfg))


def kv_bytes_per_position(cfg: dict) -> int:
    """One cached position of one layer: a key and a value row."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] \
        * _BYTES[cfg["torch_dtype"]]


def band_pairs(length: int, window: int) -> int:
    """(query, key) pairs inside the causal mask of ``length`` tokens,
    banded to ``window`` keys a query (0: no band)."""
    if not window or length <= window:
        return length * (length + 1) // 2
    return window * (window + 1) // 2 + (length - window) * window


def decode_round_bytes(cfg: dict, experts_touched: float,
                       full_rows: float, ring_rows: float) -> float:
    """What one decode round must read: every matrix outside the routed
    experts and the head's slice once; the held experts some token of
    the round picked (``experts_touched``: their number summed over the
    layers); the cached rows the round's tokens attend: ``full_rows`` a
    full layer (a token at depth ``p``, itself included, attends ``p``)
    and ``ring_rows`` a sliding one (``min(p, window)``), each summed
    over the round's tokens."""
    n = layer_counts(cfg)
    return ((params_outside_experts(cfg)
             + cfg["hidden_size"] * cfg["vocab_size"]
             + experts_touched * expert_params(cfg))
            * _BYTES[cfg["torch_dtype"]]
            + (n["full"] * full_rows + n["window"] * ring_rows)
            * kv_bytes_per_position(cfg))


def prefill_flops(cfg: dict, prompt_len: int,
                  pairs_per_token_layer: float) -> float:
    """One prompt through the rank, last position to the vocabulary:
    2 x the matrices outside the routed experts per token; scores inside
    the mask (QK^T and PV over ``head_dim`` for every query head: the
    lower triangle in a full layer, the band in a sliding one); the
    token-expert pairs routed to this rank's experts
    (``pairs_per_token_layer``: their mean number a token a sparse
    layer); one row of the head."""
    n = layer_counts(cfg)
    q_width = cfg["num_attention_heads"] * cfg["head_dim"]
    pairs = (n["full"] * band_pairs(prompt_len, 0)
             + n["window"] * band_pairs(prompt_len, cfg["sliding_window"]))
    return (2.0 * params_outside_experts(cfg) * prompt_len
            + 2.0 * 2.0 * q_width * pairs
            + 2.0 * n["sparse"] * pairs_per_token_layer * expert_params(cfg)
            * prompt_len
            + 2.0 * cfg["hidden_size"] * cfg["vocab_size"])
