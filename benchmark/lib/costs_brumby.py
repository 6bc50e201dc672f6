"""Operations and bytes Brumby's served model needs, from shapes.

The numerators of ``decode_hbm_share``, ``state_bytes_share``,
``prefill_flops_share``, ``retention_step_hbm_share.brumby`` and
``retention_chunk_flops_share.brumby`` in this model's cell
(``configs/brumby_14b.json``'s keys). As in ``costs.py`` each counts the
least the mathematics asks for: a multiply-add is 2 operations; padding
counts nothing. The state is counted in the layout the configuration
states (``assumed.state_layout``: whole tiles of 8, 8,704 rows a head of
128, float32), which is what a round has to move of it whatever moves
it. ``benchmark/tests/test_costs_brumby.py`` pins each on a hand-worked
shape.
"""

from __future__ import annotations

_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}
_STATE_BYTES = 4   # state and normaliser are float32 whatever the weights
_TILE = 8          # the layout's tile: rows of a register


def sizes(cfg: dict) -> dict:
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    return dict(d=d, ff=cfg["intermediate_size"], heads=heads,
                kv=cfg["num_key_value_heads"],
                hd=cfg.get("head_dim", d // heads))


def monomials(hd: int) -> int:
    """Distinct monomials of degree 2 in ``hd`` variables."""
    return hd * (hd + 1) // 2


def state_rows(hd: int) -> int:
    """Rows of a head's state as laid out: for every ``i`` the pairs
    ``(i, j)`` with ``j`` from the start of ``i``'s tile of 8 on."""
    return sum(hd - i // _TILE * _TILE for i in range(hd))


def mixer_params(cfg: dict) -> int:
    """A mixer's five matrices (query, key, value, gate, output) and its
    two head norms."""
    z = sizes(cfg)
    q, kv = z["heads"] * z["hd"], z["kv"] * z["hd"]
    return z["d"] * q + 2 * z["d"] * kv + z["d"] * z["kv"] + q * z["d"] \
        + 2 * z["hd"]


def mlp_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def weight_params(cfg: dict) -> int:
    """Every parameter a decode round reads: the layers with their two
    norms, the final norm and the head. (The embedding's rows a round
    looks up are :func:`decode_round_bytes`'s, a row a sequence.)"""
    d = cfg["hidden_size"]
    return (cfg["num_hidden_layers"]
            * (mixer_params(cfg) + mlp_params(cfg) + 2 * d)
            + d + d * cfg["vocab_size"])


def state_bytes_per_row(cfg: dict) -> int:
    """What one decode round moves of one sequence's state in one
    layer: state and normaliser of every key-value head read and written
    whole (every value changes)."""
    z = sizes(cfg)
    rows = state_rows(z["hd"])
    return 2 * z["kv"] * (rows * z["hd"] + rows) * _STATE_BYTES


def step_bytes_per_row(cfg: dict) -> int:
    """What the op ``retention_step`` has to move for one sequence in
    one layer: the state in and out (the normaliser is not its)."""
    z = sizes(cfg)
    return 2 * z["kv"] * state_rows(z["hd"]) * z["hd"] * _STATE_BYTES


def decode_round_state_bytes(cfg: dict, active_rows: float) -> float:
    return cfg["num_hidden_layers"] * active_rows * state_bytes_per_row(cfg)


def decode_round_bytes(cfg: dict, active_rows: float) -> float:
    """What one decode round must move: every weight once, an
    embedding row for each of the ``active_rows`` sequences, and their
    state in every layer (:func:`state_bytes_per_row`). Nothing grows
    with a sequence's length."""
    b = _BYTES[cfg["torch_dtype"]]
    return (weight_params(cfg) * b
            + active_rows * cfg["hidden_size"] * b
            + decode_round_state_bytes(cfg, active_rows))


def retention_flops(cfg: dict, tokens: int) -> float:
    """The least one layer's retention asks for over a prompt of
    ``tokens`` from an empty state, to every position's output and the
    state behind the last. Building the state is ``phi(k) v^T`` a
    position (``kv x monomials x head_dim`` multiply-adds). The outputs
    come either from the state (``heads x monomials x head_dim`` a
    position) or from the scores (``q . k`` and ``a v`` over
    ``head_dim`` for every query head and every pair ``s <= t``),
    whichever is less: below some 10,000 positions at these sizes that
    is the scores."""
    z = sizes(cfg)
    m = monomials(z["hd"])
    build = 2.0 * z["kv"] * m * z["hd"] * tokens
    by_state = 2.0 * z["heads"] * m * z["hd"] * tokens
    by_scores = 2.0 * 2.0 * z["heads"] * z["hd"] * tokens * (tokens + 1) / 2
    return build + min(by_state, by_scores)


def prefill_flops(cfg: dict, tokens: int) -> float:
    """One prompt of ``tokens`` through the model, last position to the
    vocabulary: 2 x the mixers' and the MLPs' matrices per token, the
    retention (:func:`retention_flops`) in every layer, one row of the
    head."""
    z = sizes(cfg)
    n = cfg["num_hidden_layers"]
    per_token = n * (mixer_params(cfg) - 2 * z["hd"] + mlp_params(cfg))
    return (2.0 * per_token * tokens
            + n * retention_flops(cfg, tokens)
            + 2.0 * cfg["hidden_size"] * cfg["vocab_size"])
