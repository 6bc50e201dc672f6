"""Operations and bytes SDAR's served stage needs, from shapes.

The numerators of ``decode_hbm_share``, ``grouped_experts_hbm_share``
and ``prefill_flops_share`` in this model's cell
(``configs/sdar_30b_a3b.json``'s keys). As in ``costs.py`` each counts
the least the mathematics asks for: a multiply-add is 2 operations;
padding, positions outside the mask and experts no token picked count
nothing. A round computes a block of
``B`` positions a row: the matrices are read once whatever ``B`` is,
the row's cached keys and values once for the block's ``B`` queries.
``benchmark/tests/test_costs_sdar.py`` pins each on a hand-worked shape.
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


def experts_bytes(cfg: dict, experts_touched: float) -> float:
    """The held experts some position picked, read once each
    (``experts_touched``: their number summed over the layers)."""
    return experts_touched * expert_params(cfg) * _BYTES[cfg["torch_dtype"]]


def router_params(cfg: dict) -> int:
    """The router keeps its whole width: every routed expert of the
    deployment."""
    return cfg["hidden_size"] * cfg["num_experts"] \
        * cfg["expert_parallel"]["ep_size"]


def params_outside_experts(cfg: dict) -> int:
    """Every matrix a token goes through whatever it picks, all layers:
    attention, the router and the two norm gains of ``hidden_size``."""
    return cfg["num_hidden_layers"] * (
        attn_params(cfg) + router_params(cfg) + 2 * cfg["hidden_size"])


def kv_bytes_per_position(cfg: dict) -> int:
    """One cached position of one layer: a key and a value row."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] \
        * _BYTES[cfg["torch_dtype"]]


def seen_pairs(start: int, tokens: int, block: int) -> int:
    """(query, key) pairs inside the mask by blocks for the queries at
    positions ``[start, start + tokens)``: the query at ``p`` sees the
    ``p // block * block + block`` keys up to its block's end."""
    return sum(p // block * block + block
               for p in range(start, start + tokens))


def round_bytes(cfg: dict, experts_touched: float, rows_read: float,
                positions: float) -> float:
    """What one round must move: every matrix outside the routed
    experts, the final gain and the head once; the embedding rows of the
    ``positions`` it feeds; the held experts some position picked
    (``experts_touched``: their number summed over the layers); the
    cached rows its live rows attend, once a row a layer (``rows_read``:
    summed over the live rows, the block's own among them), and the
    ``positions`` rows it writes a layer."""
    d = cfg["hidden_size"]
    return ((params_outside_experts(cfg) + d + d * cfg["vocab_size"]
             + positions * d + experts_touched * expert_params(cfg))
            * _BYTES[cfg["torch_dtype"]]
            + cfg["num_hidden_layers"] * (rows_read + positions)
            * kv_bytes_per_position(cfg))


def prefill_flops(cfg: dict, tokens: int, cached: int, block: int,
                  pairs_per_token_layer: float) -> float:
    """``tokens`` prompt positions behind ``cached`` restored ones
    through the stage, no row of the head (a block decoder's prefill
    reads no logit): 2 x the matrices outside the routed experts per
    token; scores inside the mask by blocks (QK^T and PV over
    ``head_dim`` for every query head, the restored rows among the
    keys); the token-expert pairs routed to the held experts
    (``pairs_per_token_layer``: their mean number a token a layer)."""
    n = cfg["num_hidden_layers"]
    q_width = cfg["num_attention_heads"] * cfg["head_dim"]
    matrices = n * (attn_params(cfg) + router_params(cfg))
    return (2.0 * matrices * tokens
            + 2.0 * 2.0 * q_width * n * seen_pairs(cached, tokens, block)
            + 2.0 * n * pairs_per_token_layer * expert_params(cfg) * tokens)
