"""``costs_jamba`` on shapes small enough to work by hand, and on the
published sizes against the issue's table."""

from pathlib import Path

import pytest

from benchmark.lib import common, costs_jamba as c

# d 8, d_inner 16, d_state 4, d_conv 3, dt_rank 2; 4 heads of 2 over 1
# key-value head; six layers M A M M A M
SMALL = dict(hidden_size=8, num_attention_heads=4, num_key_value_heads=1,
             intermediate_size=12, num_hidden_layers=6, attn_layer_period=3,
             attn_layer_offset=1, mamba_expand=2, mamba_d_state=4,
             mamba_d_conv=3, mamba_dt_rank=2, vocab_size=10,
             torch_dtype="bfloat16")


def test_parameter_counts_by_hand():
    assert c.layer_counts(SMALL) == dict(attn=2, mamba=4)
    # in 8x32, x_proj 16x(2+8), dt_proj 2x16, out 16x8
    assert c.mamba_matrix_params(SMALL) == 256 + 160 + 32 + 128 == 576
    # + dt bias 16, conv 3x16 + 16, A_log 16x4, D 16, norms 2 + 4 + 4
    assert c.mamba_params(SMALL) == 576 + 16 + 48 + 16 + 64 + 16 + 10 == 746
    # q 8x8, k and v 8x2 each, out 8x8
    assert c.attn_params(SMALL) == 64 + 16 + 16 + 64 == 160
    assert c.mlp_params(SMALL) == 3 * 8 * 12 == 288
    # layers' norms 6 x 2 x 8, final norm 8, embedding 10 x 8 once
    assert c.weight_params(SMALL) \
        == 4 * 746 + 2 * 160 + 6 * 288 + 96 + 8 + 80 == 5216


def test_a_decode_round_moves_weights_state_and_rows():
    # the SSM state 16x4 float32 read and written, 2 carried inputs read
    # and 1 written, bf16
    assert c.state_bytes_per_row(SMALL) == 2 * 64 * 4 + 3 * 16 * 2 == 608
    assert c.kv_bytes_per_position(SMALL) == 2 * 1 * 2 * 2 == 8
    assert c.decode_round_state_bytes(SMALL, 3) == 4 * 3 * 608 == 7296
    # 3 active rows that attend 7, 9 and 4 positions
    assert c.decode_round_bytes(SMALL, 3, 20) \
        == 5216 * 2 + 7296 + 2 * 20 * 8 == 18048
    # no row active: the weights alone
    assert c.decode_round_bytes(SMALL, 0, 0) == 10432


def test_a_prefill_counts_matrix_products_and_the_mask():
    # per token: 4 mixers' matrices, 2 attentions, 6 MLPs
    per_token = 4 * 576 + 2 * 160 + 6 * 288
    assert per_token == 4352
    # 5 tokens: 15 pairs in the triangle, QK^T and PV over 4 heads of 2
    assert c.prefill_flops(SMALL, 5) \
        == 2 * 4352 * 5 + 2 * 2 * 2 * 4 * 2 * 15 + 2 * 8 * 10 == 44640
    # the scan's floor: c and y (16 each) and step, B, C (2 + 8) a
    # position in bf16, the state twice a chunk in float32; four layers
    assert c.prefill_scan_bytes_floor(SMALL, 5, 2) \
        == 4 * (5 * (32 + 10) * 2 + 3 * 2 * 64 * 4) == 7824
    assert c.prefill_scan_bytes_floor(SMALL, 4, 4) \
        == 4 * (4 * 42 * 2 + 1 * 512) == 3392


def test_the_published_sizes_are_the_issues_table():
    cfg = common.load_json(Path(__file__).resolve().parents[1]
                           / "configs" / "jamba2_3b.json")
    assert c.layer_counts(cfg) == dict(attn=2, mamba=26)
    assert c.mamba_params(cfg) == pytest.approx(41.3e6, rel=5e-3)
    assert c.attn_params(cfg) == pytest.approx(13.8e6, rel=5e-3)
    assert c.mlp_params(cfg) == pytest.approx(62.9e6, rel=5e-3)
    assert c.weight_params(cfg) * 2 == pytest.approx(6.06e9, rel=5e-3)
    # 9.3 MB a slot in 26 layers, held; a round reads and writes it
    held = 26 * (5120 * 16 * 4 + 3 * 5120 * 2)
    assert held == pytest.approx(9.3e6, rel=5e-3)
    assert c.decode_round_state_bytes(cfg, 64) \
        == pytest.approx(64 * 26 * (2 * 327680 + 4 * 10240))
    # 1 KB a token in the two attention layers
    assert 2 * c.kv_bytes_per_position(cfg) == 1024
    # ~6 GFLOP a token
    assert c.prefill_flops(cfg, 1024) / 1024 == pytest.approx(6e9, rel=0.05)
