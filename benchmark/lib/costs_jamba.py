"""Operations and bytes Jamba's served model needs, from shapes.

The numerators of ``decode_hbm_share``, ``state_bytes_share`` and
``prefill_flops_share`` in this model's cell
(``configs/jamba2_3b.json``'s keys). As in ``costs.py`` each counts the
least the mathematics asks for: a multiply-add is 2 operations; padding
and positions after the query count nothing; a state is counted at the
size the configuration states (float32, ``state_dtype`` under
``assumed``), not at what a layout pads it to.
``benchmark/tests/test_costs_jamba.py`` pins each on a hand-worked
shape.
"""

from __future__ import annotations

_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}
_STATE_BYTES = 4   # the SSM state is float32 whatever the weights are


def sizes(cfg: dict) -> dict:
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    return dict(d=d, ff=cfg["intermediate_size"], heads=heads,
                kv=cfg["num_key_value_heads"], hd=d // heads,
                inner=cfg["mamba_expand"] * d, state=cfg["mamba_d_state"],
                conv=cfg["mamba_d_conv"], rank=cfg["mamba_dt_rank"])


def layer_counts(cfg: dict) -> dict:
    """How many of the configuration's layers are attention and Mamba."""
    n = cfg["num_hidden_layers"]
    attn = sum(i % cfg["attn_layer_period"] == cfg["attn_layer_offset"]
               for i in range(n))
    return dict(attn=attn, mamba=n - attn)


def mamba_matrix_params(cfg: dict) -> int:
    """A mixer's four matrix products: ``in_proj``, ``x_proj``,
    ``dt_proj``, ``out_proj``."""
    z = sizes(cfg)
    d, D, N, R = z["d"], z["inner"], z["state"], z["rank"]
    return d * 2 * D + D * (R + 2 * N) + R * D + D * d


def mamba_params(cfg: dict) -> int:
    """Every leaf of a mixer: the matrices, ``dt_proj``'s bias, the
    convolution with its bias, ``A_log``, ``D`` and the three norms."""
    z = sizes(cfg)
    D, N, R, K = z["inner"], z["state"], z["rank"], z["conv"]
    return mamba_matrix_params(cfg) + D + K * D + D + D * N + D + R + 2 * N


def attn_params(cfg: dict) -> int:
    """One attention's matrices: query, key, value, output."""
    z = sizes(cfg)
    q, kv = z["heads"] * z["hd"], z["kv"] * z["hd"]
    return z["d"] * q + 2 * z["d"] * kv + q * z["d"]


def mlp_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def weight_params(cfg: dict) -> int:
    """Every parameter a decode round reads: the layers with their two
    norms, the final norm, and the embedding once (the head is the
    embedding: the rows a round's tokens look up are among those the
    head reads)."""
    n, d = layer_counts(cfg), cfg["hidden_size"]
    return (n["mamba"] * mamba_params(cfg) + n["attn"] * attn_params(cfg)
            + cfg["num_hidden_layers"] * (mlp_params(cfg) + 2 * d)
            + d + cfg["vocab_size"] * d)


def state_bytes_per_row(cfg: dict) -> int:
    """What one decode round moves of one sequence's state in one Mamba
    layer: the SSM state read and written whole (every value changes),
    the ``d_conv - 1`` carried inputs read and the one new input
    written."""
    z = sizes(cfg)
    return (2 * z["inner"] * z["state"] * _STATE_BYTES
            + z["conv"] * z["inner"] * _BYTES[cfg["torch_dtype"]])


def kv_bytes_per_position(cfg: dict) -> int:
    """One cached position of one attention layer: a key and a value
    row."""
    z = sizes(cfg)
    return 2 * z["kv"] * z["hd"] * _BYTES[cfg["torch_dtype"]]


def decode_round_state_bytes(cfg: dict, active_rows: float) -> float:
    return layer_counts(cfg)["mamba"] * active_rows * state_bytes_per_row(cfg)


def decode_round_bytes(cfg: dict, active_rows: float,
                       attended_rows: float) -> float:
    """What one decode round must move: every weight once; for each of
    the ``active_rows`` sequences the state of every Mamba layer
    (:func:`state_bytes_per_row`); the cached rows the round's tokens
    attend in the attention layers (``attended_rows``: a token at depth
    ``p``, itself included, attends ``p``; summed over the round's
    tokens)."""
    return (weight_params(cfg) * _BYTES[cfg["torch_dtype"]]
            + decode_round_state_bytes(cfg, active_rows)
            + layer_counts(cfg)["attn"] * attended_rows
            * kv_bytes_per_position(cfg))


def prefill_flops(cfg: dict, tokens: int) -> float:
    """One prompt of ``tokens`` through the model, last position to the
    vocabulary, matrix products only: 2 x the mixers' four matrices, the
    attentions' four and every MLP per token; scores inside the causal
    mask (QK^T and PV over ``head_dim`` for every query head); one row
    of the head. The convolution and the recurrence are elementwise
    (some 0.5 M operations a token a layer against 230 M) and are left
    out: the share is of the matrix unit's peak."""
    n, z = layer_counts(cfg), sizes(cfg)
    per_token = (n["mamba"] * mamba_matrix_params(cfg)
                 + n["attn"] * attn_params(cfg)
                 + cfg["num_hidden_layers"] * mlp_params(cfg))
    pairs = tokens * (tokens + 1) // 2
    return (2.0 * per_token * tokens
            + 2.0 * 2.0 * n["attn"] * z["heads"] * z["hd"] * pairs
            + 2.0 * cfg["hidden_size"] * cfg["vocab_size"])


def prefill_scan_bytes_floor(cfg: dict, tokens: int, chunk: int) -> float:
    """The least a prefill's selective scans must move, all Mamba layers,
    if each were one kernel that keeps a chunk's states on the core: its
    inputs (the convolved ``c`` and the ``dt_rank + 2 d_state`` values of
    step, B and C a position, in the serving type), its output ``y``, and
    one pass of state a chunk (read at its start, written at its end).
    Since PR 41 that kernel is the op ``selective_scan`` in a trace; no
    metric reads it against this floor yet (``PERF.md`` sec. 7)."""
    z = sizes(cfg)
    b = _BYTES[cfg["torch_dtype"]]
    per_layer = (tokens * (2 * z["inner"] + z["rank"] + 2 * z["state"]) * b
                 + -(-tokens // chunk) * 2 * z["inner"] * z["state"]
                 * _STATE_BYTES)
    return float(layer_counts(cfg)["mamba"] * per_layer)
