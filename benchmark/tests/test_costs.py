"""The FLOP and byte functions, pinned on hand-worked shapes."""

from pathlib import Path

from benchmark.lib import common, costs

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def test_encoder_flops_on_a_hand_worked_shape():
    # d 4, ff 8, 1 layer, vocab 10, T 2:
    # matmul params 4*16 + 2*32 = 128 (layer) + 16 (mlm dense) + 40 = 184
    # per token: 2*184 = 368, attention 1 layer * 4*T*d = 32 -> 400
    # per sample: 400 * 2 tokens = 800 forward, x3 = 2400
    cfg = dict(hidden_size=4, intermediate_size=8, num_hidden_layers=1,
               vocab_size=10)
    assert costs.encoder_matmul_params(cfg) == 184
    assert costs.encoder_train_flops_per_sample(cfg, 2) == 2400.0


def test_bert_base_is_about_85_gflop_a_sample():
    cfg = common.load_json(CONFIGS / "bert_base.json")
    assert costs.encoder_matmul_params(cfg) == 108_965_376
    got = costs.encoder_train_flops_per_sample(cfg, 128)
    assert abs(got - 85.50e9) < 0.05e9


def test_decoder_counts_on_a_hand_worked_shape():
    # d 8, 2 q heads / 1 kv head of 4, ff 16, 2 layers, vocab 32, bf16
    cfg = dict(hidden_size=8, intermediate_size=16, num_attention_heads=2,
               num_key_value_heads=1, head_dim=4, num_hidden_layers=2,
               vocab_size=32, torch_dtype="bfloat16")
    # q 8*8 + k,v 2*8*4 + out 8*8 + mlp 3*8*16 = 64 + 64 + 64 + 384
    assert costs.decoder_layer_params(cfg) == 576
    # one position: k and v rows of 4 values, 2 bytes, 2 layers
    assert costs.decoder_kv_bytes_per_position(cfg) == 2 * 4 * 2 * 2
    # (2 * 576 + 8 * 32) * 2 bytes
    assert costs.decoder_round_weight_bytes(cfg) == 2816
    # prompt of 3: 2*2*576*3 + 2 layers * 2*8*9 + 2*8*32
    assert costs.decoder_prefill_flops(cfg, 3) == 6912 + 288 + 512


def test_mistral_layer_is_218m_params():
    cfg = common.load_json(CONFIGS / "mistral7b_v03.json")
    assert costs.decoder_layer_params(cfg) == 218_103_808
    # 4,096 B per token per layer
    assert costs.decoder_kv_bytes_per_position(cfg) \
        == 4096 * cfg["num_hidden_layers"]
