"""Model zoo: every family initialises, runs forward, and trains a few
steps distributed (8 fake devices) with descending loss."""

import jax
import numpy as np
import pytest

from pytorch_distributed_nn_tpu.config import ModelConfig, get_config
from pytorch_distributed_nn_tpu.models import available_models, get_model
from pytorch_distributed_nn_tpu.runtime.mesh import MeshSpec, make_mesh
from pytorch_distributed_nn_tpu.train.trainer import Trainer

_LONGCAT = dict(num_layers=2, d_model=32, num_heads=2, mlp_dim=64,
                vocab_size=101, q_lora_rank=16, kv_lora_rank=8,
                qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
                expert_mlp_dim=16, num_experts=8, num_zero_experts=4,
                moe_topk=2)

_KEXAONE = dict(num_layers=5, d_model=32, num_heads=4, num_kv_heads=2,
                head_dim=8, mlp_dim=64, vocab_size=101, window=4,
                expert_mlp_dim=16, num_experts=8, moe_topk=2)

_AXK1 = dict(num_layers=3, d_model=32, num_heads=2, mlp_dim=64,
             vocab_size=101, q_lora_rank=16, kv_lora_rank=8,
             qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
             expert_mlp_dim=16, num_experts=8, moe_topk=2, n_group=4,
             topk_group=2, rope_original_positions=8)

_SDAR = dict(num_layers=2, d_model=32, num_heads=4, num_kv_heads=2,
             head_dim=8, vocab_size=101, expert_mlp_dim=16, num_experts=8,
             moe_topk=2, mask_token_id=100)

TINY = {
    "mlp": dict(),
    "lenet": dict(),
    "resnet50": dict(stage_sizes=(1, 1), width=8, num_classes=10),
    "bert_base": dict(num_layers=2, d_model=32, num_heads=2, mlp_dim=64,
                      vocab_size=101, max_len=64),
    "transformer_lm": dict(num_layers=2, d_model=32, num_heads=2,
                           mlp_dim=64, vocab_size=101, max_len=64),
    "llama3_8b": dict(num_layers=2, d_model=32, num_heads=4, num_kv_heads=2,
                      mlp_dim=64, vocab_size=101),
    "moe_lm": dict(num_layers=2, d_model=32, num_heads=2, mlp_dim=64,
                   num_experts=4, k=2, vocab_size=101, max_len=64),
    "vit": dict(num_layers=2, d_model=32, num_heads=2, mlp_dim=64,
                patch_size=4),
    "longcat_flash": dict(_LONGCAT),
    "longcat_flash_ep32": dict(_LONGCAT, num_experts=64),  # 2 of 64 held
    "k_exaone": dict(_KEXAONE),
    "k_exaone_ep8": dict(_KEXAONE, num_experts=16),  # 2 of 16 held
    "ax_k1": dict(_AXK1),
    "ax_k1_ep16": dict(_AXK1, num_experts=32),  # 2 of 32 held
    "jamba": dict(num_layers=4, d_model=32, num_heads=4, num_kv_heads=1,
                  mlp_dim=64, vocab_size=101, attn_layer_period=2,
                  attn_layer_offset=1, mamba_dt_rank=4),
    "lfm2_8b_a1b": dict(num_layers=4, d_model=32, num_heads=4,
                        num_kv_heads=2, mlp_dim=64, vocab_size=101,
                        expert_mlp_dim=16, num_experts=8, moe_topk=2),
    "brumby": dict(num_layers=2, d_model=32, num_heads=4, num_kv_heads=2,
                   head_dim=8, mlp_dim=64, vocab_size=101),
    "sdar_moe": dict(_SDAR),
    "sdar_30b_a3b_seq2": dict(_SDAR),   # the cell's steps: 2, sequential
}

IMAGE_INPUT = {
    "mlp": (28, 28),
    "lenet": (28, 28),
    "resnet50": (32, 32, 3),
    "vit": (32, 32, 3),
}


def test_registry_complete():
    assert set(available_models()) == set(TINY)


@pytest.mark.parametrize("name", sorted(TINY))
def test_forward_shapes_finite(name):
    cfg = ModelConfig(name=name, compute_dtype="float32", extra=TINY[name])
    model = get_model(cfg)
    rng = jax.random.key(0)
    if name in IMAGE_INPUT:
        x = np.random.RandomState(0).randn(2, *IMAGE_INPUT[name]).astype(
            np.float32)
        n_out = TINY[name].get("num_classes", 10)
        expect = (2, n_out)
    else:
        x = np.random.RandomState(0).randint(0, 101, size=(2, 16),
                                             dtype=np.int32)
        expect = (2, 16, 101)
    variables = model.init(rng, x, train=False)
    logits = model.apply(variables, x, train=False)
    assert logits.shape == expect
    assert np.all(np.isfinite(np.asarray(logits)))


def _tiny_train(preset, model_name, dataset, steps=4, **data_kw):
    cfg = get_config(preset)
    cfg.steps = steps
    cfg.log_every = 1
    cfg.data.prefetch = 0
    cfg.data.dataset = dataset
    cfg.data.batch_size = 16
    cfg.model.name = model_name
    cfg.model.extra = TINY[model_name]
    cfg.model.compute_dtype = "float32"
    cfg.model.remat = False
    cfg.parallel.strategy = "dp"
    cfg.mesh = MeshSpec(data=8)
    for key, value in data_kw.items():
        setattr(cfg.data, key, value)
    trainer = Trainer(cfg, mesh=make_mesh(cfg.mesh.resolve(8)))
    trainer.train()
    return trainer.losses()


def test_resnet_trains():
    losses = _tiny_train("resnet50_dp", "resnet50", "cifar10")
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]


def test_vit_trains():
    losses = _tiny_train("lenet_cifar10", "vit", "cifar10", steps=6)
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]


def test_lenet_preset_trains():
    losses = _tiny_train("lenet_cifar10", "lenet", "cifar10", steps=6)
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]


def test_bert_mlm_trains():
    losses = _tiny_train("bert_base_buckets", "bert_base",
                         "mlm_synthetic", steps=6, seq_len=16,
                         vocab_size=101)
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]


def test_transformer_lm_trains():
    losses = _tiny_train("bert_base_buckets", "transformer_lm",
                         "lm_synthetic", steps=6, seq_len=16,
                         vocab_size=101)
    assert np.isfinite(losses).all()


def test_llama_trains():
    cfg = get_config("llama3_8b_zero")
    cfg.steps = 6
    cfg.log_every = 1
    cfg.optim.warmup_steps = 0  # tiny run: warm lr from step 0
    cfg.optim.lr = 1e-3
    cfg.data.prefetch = 0
    cfg.data.batch_size = 16
    cfg.data.seq_len = 16
    cfg.data.vocab_size = 101
    cfg.model.extra = TINY["llama3_8b"]
    cfg.model.compute_dtype = "float32"
    cfg.model.remat = False
    cfg.parallel.strategy = "dp"
    cfg.mesh = MeshSpec(data=8)
    trainer = Trainer(cfg, mesh=make_mesh(cfg.mesh.resolve(8)))
    trainer.train()
    losses = trainer.losses()
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]


def test_gqa_heads_shape():
    from pytorch_distributed_nn_tpu.nn.attention import dot_product_attention

    q = np.random.RandomState(0).randn(2, 8, 4, 16).astype(np.float32)
    k = np.random.RandomState(1).randn(2, 8, 2, 16).astype(np.float32)
    v = np.random.RandomState(2).randn(2, 8, 2, 16).astype(np.float32)
    out = dot_product_attention(q, k, v, causal=True)
    assert out.shape == (2, 8, 4, 16)


def test_causal_masking_blocks_future():
    from pytorch_distributed_nn_tpu.nn.attention import dot_product_attention

    rng = np.random.RandomState(0)
    q = rng.randn(1, 6, 2, 8).astype(np.float32)
    k = rng.randn(1, 6, 2, 8).astype(np.float32)
    v = rng.randn(1, 6, 2, 8).astype(np.float32)
    out_full = dot_product_attention(q, k, v, causal=True)
    # changing the future must not change position 0
    k2, v2 = k.copy(), v.copy()
    k2[:, 3:], v2[:, 3:] = 9.0, -9.0
    out_mod = dot_product_attention(q, k2, v2, causal=True)
    np.testing.assert_allclose(out_full[:, 0], out_mod[:, 0], rtol=1e-5)
    assert not np.allclose(out_full[:, 5], out_mod[:, 5])


def test_remat_with_dropout_traces():
    """remat blocks must treat `train` as static or dropout crashes."""
    cfg = ModelConfig(name="transformer_lm", compute_dtype="float32",
                      remat=True,
                      extra={**TINY["transformer_lm"], "dropout": 0.1})
    model = get_model(cfg)
    x = np.zeros((2, 8), np.int32)
    variables = model.init(jax.random.key(0), x, train=False)
    out = model.apply(variables, x, train=True,
                      rngs={"dropout": jax.random.key(1)})
    assert np.isfinite(np.asarray(out)).all()


def test_dropout_trains_under_dp():
    cfg = get_config("bert_base_buckets")
    cfg.steps = 3
    cfg.log_every = 1
    cfg.data.prefetch = 0
    cfg.data.dataset = "mlm_synthetic"
    cfg.data.batch_size = 16
    cfg.data.seq_len = 16
    cfg.data.vocab_size = 101
    cfg.model.name = "bert_base"
    cfg.model.extra = {**TINY["bert_base"], "dropout": 0.1}
    cfg.model.compute_dtype = "float32"
    cfg.parallel.strategy = "dp"
    cfg.mesh = MeshSpec(data=8)
    trainer = Trainer(cfg, mesh=make_mesh(cfg.mesh.resolve(8)))
    trainer.train()
    assert np.isfinite(trainer.losses()).all()


def test_dropout_trains_under_dp_explicit():
    cfg = get_config("bert_base_buckets")
    cfg.steps = 3
    cfg.log_every = 1
    cfg.data.prefetch = 0
    cfg.data.dataset = "mlm_synthetic"
    cfg.data.batch_size = 16
    cfg.data.seq_len = 16
    cfg.data.vocab_size = 101
    cfg.model.name = "bert_base"
    cfg.model.extra = {**TINY["bert_base"], "dropout": 0.1}
    cfg.model.compute_dtype = "float32"
    cfg.parallel.strategy = "dp_explicit"
    cfg.mesh = MeshSpec(data=8)
    trainer = Trainer(cfg, mesh=make_mesh(cfg.mesh.resolve(8)))
    trainer.train()
    assert np.isfinite(trainer.losses()).all()


def test_llama_remat_offload_matches_remat():
    """remat_offload moves saved block boundaries to pinned host RAM —
    a memory-layout choice only. Losses must track plain remat exactly
    (same recompute, same math; the long-context enabler must never
    change training).

    Plain jit (no mesh shardings): the annotate_device_placement
    custom-call the offload inserts is TPU-runtime territory — the CPU
    backend can't execute it under a sharded jit, and XLA's SPMD
    partitioner rejects it on multi-device meshes ("Side-effect HLO
    must have sharding"). Both are upstream limitations consistent
    with the feature's purpose: offload buys back HBM on ONE chip; at
    pod scale sequence parallelism is the long-context tool
    (docs/design.md). This covers the model wiring (policy
    construction, boundary tag, gradient math)."""
    import jax.numpy as jnp

    def run(offload):
        cfg = ModelConfig(name="llama3_8b", remat=True,
                          remat_offload=offload, compute_dtype="float32",
                          extra=TINY["llama3_8b"])
        model = get_model(cfg)
        tokens = jnp.arange(32, dtype=jnp.int32).reshape(2, 16) % 101
        params = model.init(jax.random.key(0), tokens, train=False)

        def loss(p):
            return model.apply(p, tokens, train=True).astype(
                jnp.float32).sum()

        return jax.jit(jax.grad(loss))(params)

    base, off = run(False), run(True)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(np.asarray(b),
                                                np.asarray(a),
                                                rtol=1e-6, atol=1e-7),
        base, off,
    )


def test_resnet_s2d_stem_matches_conv7_exactly():
    """The MLPerf space-to-depth stem is the SAME linear map as the 7x7
    stride-2 stem — conv7_to_s2d_kernel rewrites the kernel exactly, so
    full-model logits must agree to float tolerance (models/resnet.py).
    """
    import jax.numpy as jnp

    from pytorch_distributed_nn_tpu.models.resnet import (
        ResNet,
        conv7_to_s2d_kernel,
        space_to_depth,
    )

    kw = dict(stage_sizes=(1, 1), width=8, num_classes=5)
    m7 = ResNet(**kw, stem="conv7")
    ms = ResNet(**kw, stem="s2d")
    x = jax.random.normal(jax.random.key(0), (2, 32, 32, 3), jnp.float32)
    v7 = m7.init(jax.random.key(1), x, train=False)
    # transplant: same weights, stem kernel rewritten
    params = jax.tree.map(lambda a: a, v7["params"])
    k7 = params.pop("conv_init")["kernel"]
    params["conv_init_s2d"] = {"kernel": conv7_to_s2d_kernel(k7)}
    ref = m7.apply(v7, x, train=False)
    got = ms.apply({"params": params,
                    "batch_stats": v7["batch_stats"]}, x, train=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)
    # and the raw s2d layout: block channel order (bh, bw, c)
    y = space_to_depth(x, 2)
    assert y.shape == (2, 16, 16, 12)
    np.testing.assert_array_equal(np.asarray(y[0, 0, 0, :3]),
                                  np.asarray(x[0, 0, 0]))
    np.testing.assert_array_equal(np.asarray(y[0, 0, 0, 3:6]),
                                  np.asarray(x[0, 0, 1]))
    np.testing.assert_array_equal(np.asarray(y[0, 0, 0, 6:9]),
                                  np.asarray(x[0, 1, 0]))
