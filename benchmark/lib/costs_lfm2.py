"""Operations and bytes LFM2's served stage needs, from shapes.

The numerators of ``decode_hbm_share``, ``state_bytes_share``,
``grouped_experts_hbm_share`` and ``prefill_flops_share`` in this
model's cell (``configs/lfm2_8b_a1b.json``'s keys). As in ``costs.py``
each counts the least the mathematics asks for: a multiply-add is 2
operations; padding, positions after the query and experts no token
picked count nothing; a head is counted at its 64 dims whatever a kernel
pads it to. ``benchmark/tests/test_costs_lfm2.py`` pins each on a
hand-worked shape.
"""

from __future__ import annotations

_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def sizes(cfg: dict) -> dict:
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    return dict(d=d, heads=heads, kv=cfg["num_key_value_heads"],
                hd=d // heads)


def layer_counts(cfg: dict) -> dict:
    """How many of the configuration's layers are attention and
    convolution, dense and sparse: the head of ``layer_types``, experts
    from ``num_dense_layers`` on."""
    n = cfg["num_hidden_layers"]
    attn = sum(t == "full_attention" for t in cfg["layer_types"][:n])
    dense = min(cfg["num_dense_layers"], n)
    return dict(attn=attn, conv=n - attn, dense=dense, sparse=n - dense)


def attn_params(cfg: dict) -> int:
    """One attention's matrices: query, key, value, output (the two
    norm gains of 64 are 256 bytes: left out)."""
    z = sizes(cfg)
    q, kv = z["heads"] * z["hd"], z["kv"] * z["hd"]
    return z["d"] * q + 2 * z["d"] * kv + q * z["d"]


def conv_matrix_params(cfg: dict) -> int:
    """A short convolution's two matrix products: ``in_proj`` to the two
    gates and the input, ``out_proj``."""
    d = cfg["hidden_size"]
    return d * 3 * d + d * d


def conv_params(cfg: dict) -> int:
    """Every leaf of the operator: the matrices and the depthwise
    kernel."""
    return conv_matrix_params(cfg) + cfg["conv_L_cache"] * cfg["hidden_size"]


def dense_ffn_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def expert_params(cfg: dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def router_params(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["num_experts"]


def params_outside_experts(cfg: dict) -> int:
    """Every parameter a token goes through whatever it picks: the
    operators with the layers' two norm gains, the dense feed-forwards,
    the routers, the final gain, and the table once (the head is the
    table: the rows a round's tokens look up are among those the head
    reads)."""
    n, d = layer_counts(cfg), cfg["hidden_size"]
    return (n["attn"] * attn_params(cfg) + n["conv"] * conv_params(cfg)
            + cfg["num_hidden_layers"] * 2 * d
            + n["dense"] * dense_ffn_params(cfg)
            + n["sparse"] * router_params(cfg)
            + d + cfg["vocab_size"] * d)


def state_bytes_per_row(cfg: dict) -> int:
    """What one decode round moves of one sequence's state in one
    convolution layer: the ``conv_L_cache - 1`` carried inputs read and
    the one new input written."""
    return cfg["conv_L_cache"] * cfg["hidden_size"] \
        * _BYTES[cfg["torch_dtype"]]


def kv_bytes_per_position(cfg: dict) -> int:
    """One cached position of one attention layer: a key and a value
    row of 8 heads of 64."""
    z = sizes(cfg)
    return 2 * z["kv"] * z["hd"] * _BYTES[cfg["torch_dtype"]]


def decode_round_state_bytes(cfg: dict, active_rows: float) -> float:
    return layer_counts(cfg)["conv"] * active_rows * state_bytes_per_row(cfg)


def experts_bytes(cfg: dict, experts_touched: float) -> float:
    """The held experts some token picked, read once each
    (``experts_touched``: their number summed over the layers)."""
    return experts_touched * expert_params(cfg) * _BYTES[cfg["torch_dtype"]]


def decode_round_bytes(cfg: dict, experts_touched: float,
                       active_rows: float, attended_rows: float) -> float:
    """What one decode round must move: every parameter outside the
    routed experts once; the experts touched (:func:`experts_bytes`);
    for each of the ``active_rows`` sequences the carried inputs of
    every convolution layer (:func:`state_bytes_per_row`) and the row it
    writes in every attention layer; the cached rows the round's tokens
    attend in the attention layers (``attended_rows``: a token at depth
    ``p``, itself included, attends ``p``; summed over the round's
    tokens)."""
    return (params_outside_experts(cfg) * _BYTES[cfg["torch_dtype"]]
            + experts_bytes(cfg, experts_touched)
            + decode_round_state_bytes(cfg, active_rows)
            + layer_counts(cfg)["attn"] * (attended_rows + active_rows)
            * kv_bytes_per_position(cfg))


def prefill_flops(cfg: dict, tokens: int,
                  pairs_per_token_layer: float) -> float:
    """One prompt of ``tokens`` through the stage, last position to the
    vocabulary, matrix products only: 2 x the operators' matrices, the
    dense feed-forwards and the routers per token; scores inside the
    causal mask (QK^T and PV over the 64 dims of every query head); the
    token-expert pairs routed to the held experts
    (``pairs_per_token_layer``: their mean number a token a sparse
    layer); one row of the head. The depthwise convolution and the two
    gates are elementwise (some 10 K operations a token a layer against
    33 M) and are left out: the share is of the matrix unit's peak."""
    n, z = layer_counts(cfg), sizes(cfg)
    per_token = (n["conv"] * conv_matrix_params(cfg)
                 + n["attn"] * attn_params(cfg)
                 + n["dense"] * dense_ffn_params(cfg)
                 + n["sparse"] * (router_params(cfg)
                                  + pairs_per_token_layer
                                  * expert_params(cfg)))
    pairs = tokens * (tokens + 1) // 2
    return (2.0 * per_token * tokens
            + 2.0 * 2.0 * n["attn"] * z["heads"] * z["hd"] * pairs
            + 2.0 * cfg["hidden_size"] * cfg["vocab_size"])
