"""``costs_brumby`` on shapes small enough to work by hand, and on the
published sizes against the issue's arithmetic."""

from pathlib import Path

import pytest

from benchmark.lib import common, costs_brumby as c

# d 32, 4 query heads over 2 key-value heads of 16 (two tiles of 8: a
# state of 8 x 16 + 8 x 8 = 192 rows for 136 monomials), three layers
SMALL = dict(hidden_size=32, num_attention_heads=4, num_key_value_heads=2,
             head_dim=16, intermediate_size=12, num_hidden_layers=3,
             vocab_size=10, torch_dtype="bfloat16")


@pytest.mark.parametrize("hd,rows,distinct", [
    (8, 64, 36), (16, 192, 136), (128, 8704, 8256)])
def test_the_states_rows_by_hand(hd, rows, distinct):
    # a tile of 8 values of i holds j from its own start on: 8 x (hd -
    # 8 b) rows in tile b; the distinct monomials are hd (hd + 1) / 2 and
    # each diagonal tile holds its 28 pairs below the diagonal twice
    assert c.state_rows(hd) == rows \
        == sum(8 * (hd - 8 * b) for b in range(hd // 8))
    assert c.monomials(hd) == distinct == rows - (hd // 8) * 28


def test_parameter_counts_by_hand():
    # q 32x64, k and v 32x32 each, gate 32x2, out 64x32, two head norms
    assert c.mixer_params(SMALL) \
        == 2048 + 1024 + 1024 + 64 + 2048 + 32 == 6240
    assert c.mlp_params(SMALL) == 3 * 32 * 12 == 1152
    # three layers with their two norms, final norm 32, head 32 x 10
    assert c.weight_params(SMALL) \
        == 3 * (6240 + 1152 + 64) + 32 + 320 == 22720


def test_a_decode_round_moves_weights_and_state():
    # two heads' state 192 x 16 and normaliser 192, float32, in and out
    assert c.state_bytes_per_row(SMALL) \
        == 2 * 2 * (192 * 16 + 192) * 4 == 52224
    assert c.step_bytes_per_row(SMALL) == 2 * 2 * 192 * 16 * 4 == 49152
    assert c.decode_round_state_bytes(SMALL, 3) == 3 * 3 * 52224 == 470016
    # 3 active rows: the weights, three embedding rows, the state
    assert c.decode_round_bytes(SMALL, 3) \
        == 22720 * 2 + 3 * 32 * 2 + 470016 == 515648
    # no row active: the weights alone
    assert c.decode_round_bytes(SMALL, 0) == 45440


def test_a_prefill_counts_matrix_products_and_the_cheaper_retention():
    # building the state: 2 heads x 136 monomials x 16 a position
    build = 2 * 2 * 136 * 16
    by_state = 2 * 4 * 136 * 16          # a position, from the state
    # 5 tokens: 15 pairs, q . k and a v over 16 for 4 heads: the scores
    assert c.retention_flops(SMALL, 5) \
        == build * 5 + 2 * 2 * 4 * 16 * 15 == 47360
    # 200 tokens: 20,100 pairs cost more than 200 positions from the
    # state (the two meet at 135)
    assert 2 * 2 * 4 * 16 * 20100 > by_state * 200
    assert c.retention_flops(SMALL, 200) == (build + by_state) * 200
    # per token: three mixers' matrices (not their norms) and three MLPs
    per_token = 3 * (6240 - 32 + 1152)
    assert c.prefill_flops(SMALL, 5) \
        == 2 * per_token * 5 + 3 * 47360 + 2 * 32 * 10 == 363520


def test_the_published_sizes_are_the_issues_arithmetic():
    cfg = common.load_json(Path(__file__).resolve().parents[1]
                           / "configs" / "brumby_14b.json")
    assert cfg["num_hidden_layers"] == 8
    # a layer 330.3 M parameters, 0.661 GB; 8 layers and the head 6.84 GB
    layer = c.mixer_params(cfg) + c.mlp_params(cfg) + 2 * 5120
    assert layer == pytest.approx(330.3e6, rel=1e-3)
    assert c.weight_params(cfg) * 2 == pytest.approx(6.84e9, rel=2e-3)
    # 35.9 MB a layer a sequence as laid out (the issue's 34 MB is the
    # 8,256 distinct monomials; the layout is 5.4 % over)
    held = c.state_bytes_per_row(cfg) / 2
    assert held == pytest.approx(35.93e6, rel=1e-3)
    assert held / (8 * 8256 * 129 * 4) == pytest.approx(8704 / 8256)
    # 16 rows: 9.2 GB of state in and out beside 6.84 GB of weights: 57 %
    state = c.decode_round_state_bytes(cfg, 16)
    assert state == pytest.approx(9.20e9, rel=2e-3)
    assert state / c.decode_round_bytes(cfg, 16) \
        == pytest.approx(0.573, abs=3e-3)
    # ~101 MFLOP a token a layer from the state; at the cell's median
    # prompt the scores are the cheaper outputs
    assert c.retention_flops(cfg, 20000) / 20000 \
        == pytest.approx(101.4e6, rel=1e-3)
    assert c.retention_flops(cfg, 3072) \
        == pytest.approx(3072 * 16.9e6 + 4 * 40 * 128 * 3072 * 3073 / 2,
                         rel=1e-3)
    assert c.retention_flops(cfg, 3072) < 0.5 * 3072 * 101.4e6
    assert c.prefill_flops(cfg, 3072) / 3072 \
        == pytest.approx(8 * (2 * 330.3e6 + 48.4e6), rel=5e-3)
