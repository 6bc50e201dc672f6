"""``costs_lfm2`` on shapes small enough to work by hand, and on the
published sizes against the issue's arithmetic; no share of a roofline
can pass 100 % when the traced time is at least what the chip's peak
allows for the counted work."""

from pathlib import Path

from benchmark.lib import common, costs_lfm2 as c

SMALL = dict(hidden_size=8, num_attention_heads=4, num_key_value_heads=2,
             intermediate_size=12, moe_intermediate_size=4, num_experts=2,
             num_experts_per_tok=2, num_dense_layers=1, conv_L_cache=3,
             layer_types=["conv", "full_attention", "conv", "conv"],
             num_hidden_layers=3, vocab_size=10, torch_dtype="bfloat16")
CFG = common.load_json(Path(__file__).resolve().parents[1]
                       / "configs" / "lfm2_8b_a1b.json")


def test_parameter_counts_by_hand():
    # the list's head: conv, attention, conv; the first one dense
    assert c.layer_counts(SMALL) == dict(attn=1, conv=2, dense=1, sparse=2)
    # q 8x(4x2), k and v 8x(2x2) each, out (4x2)x8
    assert c.attn_params(SMALL) == 64 + 32 + 32 + 64 == 192
    # in_proj 8x24, out_proj 8x8; with the 3 taps of 8
    assert c.conv_matrix_params(SMALL) == 192 + 64 == 256
    assert c.conv_params(SMALL) == 256 + 24 == 280
    assert c.dense_ffn_params(SMALL) == 3 * 8 * 12 == 288
    assert c.expert_params(SMALL) == 3 * 8 * 4 == 96
    assert c.router_params(SMALL) == 16
    # one attention, two convolutions, three layers' two gains, one
    # dense feed-forward, two routers, the final gain and the table
    assert c.params_outside_experts(SMALL) \
        == 192 + 2 * 280 + 3 * 16 + 288 + 2 * 16 + 8 + 80 == 1208
    # two carried inputs read and one written, 8 wide, bf16
    assert c.state_bytes_per_row(SMALL) == 3 * 8 * 2 == 48
    # a key and a value row of 2 heads x 2, bf16
    assert c.kv_bytes_per_position(SMALL) == 2 * 4 * 2 == 16


def test_round_bytes_by_hand():
    # 3 experts touched over the layers, 5 active rows, 40 rows attended
    assert c.experts_bytes(SMALL, 3) == 3 * 96 * 2 == 576
    assert c.decode_round_state_bytes(SMALL, 5) == 2 * 5 * 48 == 480
    # 1208 x 2 B + the experts + the state + one attention layer's
    # 40 rows attended and 5 written at 16 B
    assert c.decode_round_bytes(SMALL, 3, 5, 40) \
        == 2416 + 576 + 480 + 45 * 16 == 4192


def test_prefill_flops_by_hand():
    # 6 tokens, 1.5 pairs a token a sparse layer: a token's matrices
    # 2 x 256 + 192 + 288 + 2 x (16 + 1.5 x 96) = 1312; 21 pairs inside
    # the causal mask at 4 heads of 2; one row of the head
    assert c.prefill_flops(SMALL, 6, 1.5) \
        == 2 * 1312 * 6 + 4 * 1 * 4 * 2 * 21 + 2 * 8 * 10 == 16576.0


def test_published_sizes_match_the_issues_arithmetic():
    assert c.layer_counts(CFG) == dict(attn=3, conv=11, dense=2, sparse=12)
    assert round(c.expert_params(CFG) / 1e6, 2) == 11.01
    assert round(32 * c.expert_params(CFG) / 1e6, 1) == 352.3
    assert round(c.attn_params(CFG) / 1e6, 2) == 10.49
    assert round(c.conv_params(CFG) / 1e6, 2) == 16.78
    assert round(c.dense_ffn_params(CFG) / 1e6, 2) == 44.04
    whole = c.params_outside_experts(CFG) + 12 * 32 * c.expert_params(CFG)
    assert round(whole / 1e9, 2) == 4.67
    assert round(whole * 2 / 1e9, 2) == 9.33
    # 2 KB a position an attention layer; 12 KB of state a row a layer
    assert c.kv_bytes_per_position(CFG) == 2048
    assert c.state_bytes_per_row(CFG) == 12288
    # an expert is 22.0 MB; a round that touches all 32 of 12 layers
    # reads 8.46 GB of them
    assert round(c.experts_bytes(CFG, 1) / 1e6, 1) == 22.0
    assert round(c.experts_bytes(CFG, 12 * 32) / 1e9, 2) == 8.46
    # 64 active rows at depth ~1,000: ~9.7 GB, 11.9 ms at 819 GB/s, of
    # which the state is under a thousandth
    need = c.decode_round_bytes(CFG, 12 * 32, 64, 64 * 1000)
    assert 9.6e9 < need < 9.8e9
    assert c.decode_round_state_bytes(CFG, 64) / need < 1e-3
    # a prompt of 1,000 tokens: 1.67 GFLOP a token (the issue's figure)
    flops = c.prefill_flops(CFG, 1000, 4.0)
    assert 1.6e12 < flops < 1.75e12


def test_a_share_cannot_pass_100_at_the_chips_peaks():
    """The shares the readers make of these numbers divide by the traced
    time at the chip's peak: each is at most 100 % for any time the chip
    can do the counted work in, since nothing is counted twice: the table
    once though it is both embedding and head, a touched expert once
    though it runs in two chunks, a head at 64 dims though the core pads
    it to 128."""
    peaks = common.peaks("TPU v5 lite")
    need = c.decode_round_bytes(CFG, 12 * 32, 64, 64 * 4096)
    # every parameter of the stage once and every cache row there is
    held = 9.335e9 + 1.616e9 + 11 * 64 * 2 * 2048 * 2
    assert need < held * 1.002
    least_s = need / peaks["hbm_bytes_per_s"]
    assert 100.0 * need / (least_s * peaks["hbm_bytes_per_s"]) <= 100.0
    # the matrix products of a 4,096-token prompt, every pair computed:
    # under the stage's dense count (every expert for every token)
    dense = 2.0 * 4096 * (c.params_outside_experts(CFG)
                          + 12 * 32 * c.expert_params(CFG))
    assert c.prefill_flops(CFG, 4096, 4.0) < dense
