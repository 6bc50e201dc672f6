"""Operations and bytes an algorithm needs, from shapes alone.

These are the numerators of the roofline-style shares. Each counts the
least the mathematics requires (a multiply-add is 2 operations; padding,
recomputation and masked-out positions count nothing), so a share over
100 % means the timing left work out, never that the count is generous.
``benchmark/tests/test_costs.py`` pins each on a hand-worked shape.
"""

from __future__ import annotations

_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


# -- BERT-style encoder, masked-LM head (configs/bert_base.json keys) -----

def encoder_matmul_params(cfg: dict) -> int:
    """Weights that multiply every token: per layer q, k, v, out (4 d^2)
    and the two MLP matrices (2 d ff); the MLM transform (d^2) and the
    decoder to the vocabulary (d V). Embedding lookups are gathers."""
    d, ff = cfg["hidden_size"], cfg["intermediate_size"]
    per_layer = 4 * d * d + 2 * d * ff
    return (cfg["num_hidden_layers"] * per_layer + d * d
            + d * cfg["vocab_size"])


def encoder_train_flops_per_sample(cfg: dict, seq_len: int) -> float:
    """Forward: 2 x matmul params per token, plus bidirectional
    attention's QK^T and PV (2 x 2 T d per token per layer). Training
    is 3 x forward (backward is two matmuls for each forward one)."""
    d = cfg["hidden_size"]
    per_token = 2 * encoder_matmul_params(cfg) \
        + cfg["num_hidden_layers"] * 4 * seq_len * d
    return 3.0 * per_token * seq_len


# -- decoder-only LM with grouped-query attention (mistral7b_v03 keys) ----

def decoder_layer_params(cfg: dict) -> int:
    d, ff = cfg["hidden_size"], cfg["intermediate_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    return d * q + 2 * d * kv + q * d + 3 * d * ff


def decoder_kv_bytes_per_position(cfg: dict) -> int:
    """One cached position: a key and a value row per layer."""
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    return 2 * kv * _BYTES[cfg["torch_dtype"]] * cfg["num_hidden_layers"]


def decoder_round_weight_bytes(cfg: dict) -> int:
    """What one decode round must read whatever the batch: every layer's
    matrices and the LM head, once. (Embedding rows are one gather per
    slot and norm gains a few kilobytes: left out.)"""
    return (cfg["num_hidden_layers"] * decoder_layer_params(cfg)
            + cfg["hidden_size"] * cfg["vocab_size"]) \
        * _BYTES[cfg["torch_dtype"]]


def decoder_prefill_flops(cfg: dict, prompt_len: int) -> float:
    """One prompt through the model, last position to the vocabulary:
    2 x layer params per token, causal attention (QK^T and PV over the
    lower triangle: 2 x 2 x q_width x L^2 / 2 per layer), and one row of
    the LM head."""
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    layers = cfg["num_hidden_layers"]
    return (2.0 * layers * decoder_layer_params(cfg) * prompt_len
            + layers * 2.0 * q * prompt_len * prompt_len
            + 2.0 * cfg["hidden_size"] * cfg["vocab_size"])
