"""``costs_sdar`` on shapes small enough to work by hand, and on the
published sizes against the issue's table."""

from pathlib import Path

import pytest

from benchmark.lib import common, costs_sdar as c

SMALL = dict(hidden_size=8, num_attention_heads=4, num_key_value_heads=2,
             head_dim=2, moe_intermediate_size=4, num_experts=2,
             num_experts_per_tok=2, num_hidden_layers=3, vocab_size=10,
             torch_dtype="bfloat16",
             expert_parallel=dict(ep_size=1, ep_rank=0))


def test_parameter_counts_by_hand():
    # q 8x(4x2), k and v 8x(2x2) each, out (4x2)x8
    assert c.attn_params(SMALL) == 64 + 32 + 32 + 64 == 192
    assert c.expert_params(SMALL) == 3 * 8 * 4 == 96
    assert c.router_params(SMALL) == 8 * 2 == 16
    # three layers of attention, router and two gains of 8
    assert c.params_outside_experts(SMALL) == 3 * (192 + 16 + 16) == 672
    # a key and a value row of 2 heads x 2, bf16
    assert c.kv_bytes_per_position(SMALL) == 2 * 4 * 2 == 16
    # five experts touched over the layers, bf16
    assert c.experts_bytes(SMALL, 5) == 5 * 96 * 2 == 960


@pytest.mark.parametrize("start,tokens,block,pairs", [
    (0, 4, 4, 16),      # one block: each of 4 queries sees all 4
    (0, 8, 4, 48),      # 4 x 4 + 4 x 8
    (4, 4, 4, 32),      # the second block alone, the first among its keys
    (0, 5, 1, 15),      # blocks of one: the causal triangle
    (8, 2, 4, 24),      # two queries of the third block see 12 each
])
def test_seen_pairs_by_hand(start, tokens, block, pairs):
    assert c.seen_pairs(start, tokens, block) == pairs


def test_round_bytes_by_hand():
    # (672 outside + final gain 8 + head 8 x 10 + 12 positions x 8
    #  + 5 experts x 96) x 2 B + 3 layers x (40 rows + 12 written) x 16 B
    assert c.round_bytes(SMALL, 5, 40, 12) \
        == (672 + 8 + 80 + 96 + 480) * 2 + 3 * 52 * 16 == 5168


def test_prefill_flops_by_hand():
    # 8 tokens behind 4 restored, blocks of 4, 1.5 pairs a token a layer:
    # matrices 3 x (192 + 16) = 624; pairs inside the mask 4 x 8 + 4 x 12
    assert c.prefill_flops(SMALL, 8, 4, 4, 1.5) \
        == 2 * 624 * 8 + 4 * 8 * 3 * 80 + 2 * 3 * 1.5 * 96 * 8 == 24576.0


def test_published_sizes_match_the_issues_table():
    cfg = common.load_json(Path(__file__).resolve().parents[1]
                           / "configs" / "sdar_30b_a3b.json")
    assert c.attn_params(cfg) == 2 * 2048 * 4096 + 2 * 2048 * 512
    assert round(c.attn_params(cfg) / 1e6, 1) == 18.9
    assert round(c.expert_params(cfg) / 1e6, 2) == 4.72
    assert round(c.experts_bytes(cfg, 1) / 1e6, 1) == 9.4
    layer = c.params_outside_experts(cfg) / 7 + 128 * c.expert_params(cfg)
    assert round(layer / 1e6) == 623
    whole = 7 * layer + 2 * 151936 * 2048
    assert round(whole * 2 / 1e9, 2) == 9.97
    # 2 KB a position a layer
    assert c.kv_bytes_per_position(cfg) == 2048
    # a round of 64 live rows at depth ~1,000 that touches every expert:
    # 8.46 GB of experts, 0.62 of head, 0.27 outside the experts and
    # 0.92 of cache rows: 12.5 ms at 819 GB/s
    need = c.round_bytes(cfg, 7 * 128, 64 * 1000, 256)
    assert 10.2e9 < need < 10.35e9


def test_the_programs_generation_is_the_configurations():
    """``serving.program_model`` hands the registered name eight sizes
    and none of the generation's, and the reference reads the file's
    ``generation``: the two are held together here, not by a run that
    comes out not correct."""
    from benchmark.lib import serving
    from pytorch_distributed_nn_tpu.config import ModelConfig
    from pytorch_distributed_nn_tpu.models import get_model

    cfg = common.load_json(Path(__file__).resolve().parents[1]
                           / "configs" / "sdar_30b_a3b.json")
    gen = dict(cfg["generation"])
    published = gen.pop("as_published")
    assert serving.program_model(cfg).block_decoding() == gen
    # the family's own name steps as published
    family = get_model(ModelConfig(name="sdar_moe")).block_decoding()
    assert {k: family[k] for k in published} == published


def test_the_token_table_is_drawn_as_a_kernel():
    """The leaf's name decides the draw (``benchmark/lib/weights.py``):
    ``tok_embed/table`` comes out at ``vocab ** -0.5``, the mask
    token's row among them, where a leaf called ``embedding`` would be
    of unit variance and outweigh the context at every position a round
    decides (``PERF.md`` sec. 6, PR 42)."""
    import numpy as np

    from benchmark.lib import weights

    data = Path(__file__).parent / "data"
    cfg = common.load_json(data / "tiny_sdar.json")
    ref = common.load_module(
        Path(__file__).resolve().parents[1] / "configs"
        / "sdar_30b_a3b_ref.py", "benchmark_ref_sdar_for_a_test")
    spec = ref.param_spec(cfg)
    assert [n for n, _ in spec["top"]] == [
        "tok_embed/table", "final_norm/scale", "lm_head/kernel"]
    table = np.asarray(weights.top(5, spec)["tok_embed/table"], np.float32)
    assert table.shape == (cfg["vocab_size"], cfg["hidden_size"])
    assert abs(table.std() * cfg["vocab_size"] ** 0.5 - 1) < 0.05
