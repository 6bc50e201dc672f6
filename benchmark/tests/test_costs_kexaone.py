"""``costs_kexaone`` on shapes small enough to work by hand, and on the
published sizes against the issue's table."""

from pathlib import Path

import pytest

from benchmark.lib import common, costs_kexaone as c

SMALL = dict(hidden_size=8, num_attention_heads=4, num_key_value_heads=2,
             head_dim=2, intermediate_size=16, moe_intermediate_size=4,
             num_experts=2, num_experts_per_tok=2, num_shared_experts=1,
             num_hidden_layers=5, sliding_window=3,
             layer_types=["sliding_attention"] * 3 + ["full_attention",
                                                      "sliding_attention"],
             mlp_layer_types=["dense"] + ["sparse"] * 4,
             vocab_size=10, torch_dtype="bfloat16",
             expert_parallel=dict(ep_size=2, ep_rank=0))


def test_parameter_counts_by_hand():
    # q 8x(4x2), k and v 8x(2x2) each, out (4x2)x8
    assert c.attn_params(SMALL) == 64 + 32 + 32 + 64 == 192
    assert c.expert_params(SMALL) == 3 * 8 * 4 == 96
    assert c.router_params(SMALL) == 8 * (2 * 2) == 32
    assert c.dense_layer_params(SMALL) == 192 + 3 * 8 * 16 == 576
    # attention, one shared expert, the router
    assert c.sparse_layer_params_outside_experts(SMALL) \
        == 192 + 96 + 32 == 320
    assert c.layer_counts(SMALL) == dict(dense=1, sparse=4, window=4, full=1)
    assert c.params_outside_experts(SMALL) == 576 + 4 * 320 == 1856
    # a key and a value row of 2 heads x 2, bf16
    assert c.kv_bytes_per_position(SMALL) == 2 * 4 * 2 == 16


@pytest.mark.parametrize("length,window,pairs", [
    (5, 0, 15),        # the lower triangle: 1 + 2 + 3 + 4 + 5
    (2, 3, 3),         # shorter than the window: the triangle still
    (3, 3, 6),
    (5, 3, 12),        # 1 + 2 + 3, then 3 + 3
    (1, 3, 1),
])
def test_band_pairs_by_hand(length, window, pairs):
    assert c.band_pairs(length, window) == pairs


def test_decode_round_bytes_by_hand():
    # (1856 outside + head 8 x 10 + 3 experts x 96) x 2 B
    # + (1 full layer x 20 rows + 4 rings x 9 rows) x 16 B
    assert c.decode_round_bytes(SMALL, 3, 20, 9) \
        == (1856 + 80 + 288) * 2 + (20 + 36) * 16 == 5344


def test_prefill_flops_by_hand():
    # matrices 2 x 1856 x 5; scores 2 x 2 x q width 8 x (15 + 4 x 12)
    # pairs; held pairs 2 x 4 layers x 0.5 x 96 x 5; head 2 x 8 x 10
    assert c.prefill_flops(SMALL, 5, 0.5) \
        == 18560 + 2016 + 1920 + 160 == 22656


def test_published_sizes_are_the_issues_table():
    cfg = common.load_json(Path(__file__).resolve().parents[1]
                           / "configs" / "k_exaone_236b.json")
    assert c.attn_params(cfg) == 50331648 + 2 * 6291456 + 50331648
    assert c.expert_params(cfg) == 37748736
    assert c.router_params(cfg) == 6144 * 128
    # the issue's 151.8 M a sparse layer outside its routed experts and
    # 453.0 M for layer 0 (norm gains left out here)
    assert c.sparse_layer_params_outside_experts(cfg) \
        == pytest.approx(151.8e6, rel=1e-3)
    assert c.dense_layer_params(cfg) == pytest.approx(453.0e6, rel=1e-3)
    assert c.layer_counts(cfg) == dict(dense=1, sparse=7, window=6, full=2)
    assert c.kv_bytes_per_position(cfg) == 4096
    # a round of 32 rows at ~800 positions, 14 of 16 experts a layer:
    # 0.91 + 7 x 0.30 outside, 0.24 head, 7 x 14 x 75.5 MB of experts,
    # 2 x 32 x 800 and 6 x 32 x 128 rows of 4 KB: ~10.9 GB (the issue's
    # 11.8 counts the full layers' whole padded rows)
    assert 10.6e9 < c.decode_round_bytes(cfg, 7 * 14, 32 * 800, 32 * 128) \
        < 11.0e9
    # a 1,024-token prompt: ~3.9 TFLOP (the issue's estimate)
    assert 3.7e12 < c.prefill_flops(cfg, 1024, 1.0) < 4.2e12
