"""Analytic FLOPs / MFU counting (utils/flops.py).

The XLA-cost-model count is the bench's MFU numerator; these tests pin
it against independently derivable closed forms so a counting regression
can't silently inflate MFU (VERDICT.md round-1 Missing #2).
"""

import numpy as np
import pytest

import jax

from pytorch_distributed_nn_tpu.config import get_config
from pytorch_distributed_nn_tpu.models import get_model
from pytorch_distributed_nn_tpu.utils import flops as flops_mod


def _param_count(model, x_shape, x_dtype):
    import jax.numpy as jnp

    variables = jax.eval_shape(
        lambda: model.init(jax.random.key(0),
                           jnp.zeros(x_shape, x_dtype), train=False)
    )
    return {
        "/".join(str(k.key) for k in path): int(np.prod(leaf.shape))
        for path, leaf in jax.tree_util.tree_flatten_with_path(variables)[0]
    }


def test_mlp_fwd_flops_is_2n():
    # A pure-dense net's forward is exactly 2 FLOPs per parameter per
    # sample (one multiply + one add per weight; bias adds counted too).
    cfg = get_config("mlp_mnist")
    model = get_model(cfg.model)
    counted = flops_mod.fwd_flops(model, (1, 28, 28, 1), np.float32)
    n = sum(_param_count(model, (1, 28, 28, 1), np.float32).values())
    assert counted == pytest.approx(2.0 * n, rel=0.01)


def test_llama_train_flops_matches_closed_form():
    # XLA's count vs 6N + 12*L*T*d per token (PaLM appendix B), N = the
    # matmul-participating params (everything but the embedding lookup
    # table; norm scales are noise). Dense-attention path so the T^2
    # score matmuls are really traced.
    T = 512
    cfg = get_config("llama3_8b_zero")
    cfg.model.extra = dict(num_layers=2, d_model=256, num_heads=8,
                           num_kv_heads=4, mlp_dim=1024, vocab_size=1024)
    cfg.model.remat = False
    cfg.data.seq_len = T
    cfg.data.vocab_size = 1024
    counted = 3.0 * flops_mod.fwd_flops(
        get_model(cfg.model), (1, T), np.int32
    )
    params = _param_count(get_model(cfg.model), (1, T), np.int32)
    n_matmul = sum(
        v for k, v in params.items() if "embedding" not in k.lower()
    )
    closed = T * flops_mod.lm_train_flops_per_token(
        n_matmul, n_layers=2, seq_len=T, d_model=256
    )
    # rope/softmax/norm elementwise work makes XLA's count a bit higher
    assert counted == pytest.approx(closed, rel=0.15)
    assert counted >= closed  # never undercount vs the matmul floor


def test_train_flops_per_sample_scales_with_seq_len():
    cfg = get_config("llama3_longcontext")
    cfg.model.extra = dict(num_layers=2, d_model=256, num_heads=8,
                           num_kv_heads=8, mlp_dim=512, vocab_size=512)
    cfg.data.vocab_size = 512
    cfg.data.seq_len = 256
    f1 = flops_mod.train_flops_per_sample(cfg)
    cfg.data.seq_len = 512
    f2 = flops_mod.train_flops_per_sample(cfg)
    assert f2 > 1.9 * f1  # superlinear in T (attention is quadratic)


def test_resnet_counted_convs_exceed_param_bound():
    # Conv FLOPs reuse weights spatially: the count must far exceed the
    # 2N dense bound, and land near the public ResNet-50 figure
    # (~4.1 GMACs -> ~8.2 GFLOPs fwd at 224^2).
    cfg = get_config("resnet50_dp")
    model = get_model(cfg.model)
    counted = flops_mod.fwd_flops(model, (1, 224, 224, 3), np.float32)
    assert 7e9 < counted < 9e9


def test_train_flops_forces_dense_attention(monkeypatch):
    # A Pallas flash kernel is a custom call XLA's cost model scores as
    # 0 FLOPs; the counter must override attn_impl so long-context MFU
    # keeps its dominant T^2 term.
    from pytorch_distributed_nn_tpu import models as models_mod

    seen = {}
    real = models_mod.get_model

    def spy(model_cfg):
        seen["extra"] = dict(model_cfg.extra)
        seen["remat"] = model_cfg.remat
        return real(model_cfg)

    # flops.py imports get_model from the models package at call time
    monkeypatch.setattr(
        "pytorch_distributed_nn_tpu.models.get_model", spy
    )
    cfg = get_config("llama3_longcontext")
    cfg.model.extra.update(num_layers=1, d_model=128, num_heads=4,
                           num_kv_heads=4, mlp_dim=256, vocab_size=256,
                           attn_impl="flash")
    cfg.data.seq_len = 128
    cfg.data.vocab_size = 256
    flops_mod.train_flops_per_sample(cfg)
    assert seen["extra"]["attn_impl"] == "xla"
    assert seen["remat"] is False


def test_peak_lookup_and_mfu():
    class FakeDev:
        device_kind = "TPU v5 lite"

    assert flops_mod.peak_flops_per_chip(FakeDev()) == 197e12
    got = flops_mod.mfu(100.0, 197e10, device=FakeDev())
    assert got == pytest.approx(1.0)

    class Cpu:
        device_kind = "cpu"

    assert flops_mod.peak_flops_per_chip(Cpu()) is None
    assert flops_mod.mfu(100.0, 1e12, device=Cpu()) is None

    class UnknownTpu:
        platform = "tpu"
        device_kind = "TPU v99"

    # a chip without a peak is an error on the measuring path, not
    # "MFU n/a"
    with pytest.raises(KeyError, match="v99"):
        flops_mod.mfu(100.0, 1e12, device=UnknownTpu())


def test_static_input_specs_match_real_datasets():
    # flops counting derives input shapes from config alone (no file
    # I/O); the static table must track the real dataset specs
    from pytorch_distributed_nn_tpu.data import get_dataset

    for name, shape in flops_mod._IMAGE_SPECS.items():
        spec = get_dataset(name, seed=0, batch_size=1).spec
        assert spec.x_shape == shape, name
        assert spec.x_dtype == np.float32
    for name in ("lm_synthetic", "mlm_synthetic"):
        spec = get_dataset(name, seed=0, batch_size=1, seq_len=64,
                           vocab_size=128).spec
        assert spec.x_shape == (64,)
        assert spec.x_dtype == np.int32


def test_reader_input_specs_match_real_readers(tmp_path):
    # the mnist_idx/cifar10_bin static shapes in _input_spec must track
    # what the real readers derive from actual files
    import sys

    sys.path.insert(0, "tests")
    import test_readers as tr

    from pytorch_distributed_nn_tpu.data import get_dataset as gd
    from pytorch_distributed_nn_tpu.config import get_config as gc

    (tmp_path / "mnist").mkdir()
    (tmp_path / "cifar").mkdir()
    tr.mnist_dir(tmp_path / "mnist", n_train=32, n_test=16)
    tr.cifar_dir(tmp_path / "cifar", n_per_batch=16, n_test=8)
    cfg = gc("mlp_mnist")
    for name, sub in (("mnist_idx", "mnist"), ("cifar10_bin", "cifar")):
        cfg.data.dataset = name
        cfg.data.path = str(tmp_path / sub)
        spec = gd(name, seed=0, batch_size=1,
                  path=cfg.data.path).spec
        shape, dtype = flops_mod._input_spec(cfg)
        assert shape == spec.x_shape, name
        assert dtype == spec.x_dtype, name
