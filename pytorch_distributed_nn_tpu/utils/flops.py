"""Analytic model-FLOPs counting and MFU.

MFU (model FLOPs utilization) = achieved model FLOPs/s divided by the
chip's peak dense FLOPs/s. "Model FLOPs" is the *algorithmic* cost of a
training step — forward FLOPs x 3 (the backward pass costs ~2x forward
for matmul/conv networks: one pass for dL/dW, one for dL/dx) — counted
on the un-rematerialized forward. Recompute inserted by
``jax.checkpoint`` is real hardware work but NOT useful model work, so
it does not count (the PaLM-appendix / MLPerf convention); MFU therefore
penalizes remat exactly as it should.

Forward FLOPs come from XLA's own cost model applied to the lowered
(pre-optimization) HLO of the forward pass: the compiler literally
counts every conv and dot at the traced shapes. This is the "counted
convs" number for ResNet and agrees with the ``6N + 12*L*T^2*d`` closed
form for transformer LMs (cross-checked in tests/test_flops.py). Note
XLA counts a multiply-accumulate as 2 FLOPs, so ResNet-50 fwd at 224^2
is ~8.2 GFLOPs here, not the "4.1 GFLOPs" MAC-count papers quote.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# Peak dense bf16 FLOP/s per chip, keyed by substring of
# ``device.device_kind`` (lowercased). Public figures from the TPU
# product pages / "How to Scale Your Model".
PEAK_BF16_FLOPS = {
    "v6e": 918e12,
    "v6 lite": 918e12,
    "v5p": 459e12,
    "v5e": 197e12,
    "v5 lite": 197e12,
    "v5litepod": 197e12,
    "v4": 275e12,
    "v3": 123e12,
    "v2": 46e12,
}


def peak_flops_per_chip(device=None, dtype=None) -> float | None:
    """Peak dense FLOP/s for ``device`` (default: jax.devices()[0]) at
    ``dtype`` (default bf16). None off-TPU (the CPU test platform); a
    TPU whose ``device_kind`` is not in the table is an error, not
    "MFU n/a". TPUs run f32 matmuls at half the bf16 MXU rate, so an
    f32-compute model's MFU must be judged against the f32 peak."""
    if device is None:
        device = jax.devices()[0]
    kind = (getattr(device, "device_kind", "") or "").lower()
    for key, val in PEAK_BF16_FLOPS.items():
        if key in kind:
            if dtype is not None and jnp.dtype(dtype) == jnp.float32:
                return val / 2.0
            return val
    if getattr(device, "platform", "") == "tpu":
        raise KeyError(
            f"no peak FLOP/s known for TPU device_kind "
            f"{device.device_kind!r}; add it to PEAK_BF16_FLOPS")
    return None


def fwd_flops(model, x_shape: tuple, x_dtype) -> float:
    """XLA-counted forward FLOPs of ``model.apply`` on one batch of
    shape ``x_shape``.

    Lowering is fully abstract (no params are materialized, nothing
    executes); the count is exact for the traced shapes and scales
    linearly in the leading batch dim for every model here, so callers
    can count at batch 1 and multiply.
    """
    x = jax.ShapeDtypeStruct(tuple(x_shape), x_dtype)

    def init():
        return model.init(jax.random.key(0),
                          jnp.zeros(x.shape, x.dtype), train=False)

    variables = jax.eval_shape(init)

    def fwd(v, xb):
        return model.apply(v, xb, train=False)

    # The count is a property of the traced HLO, not of the device, and
    # the TPU backend's Lowered carries no cost model (its
    # cost_analysis() is None — seen on the v5e, PR 23), so lower for
    # the host CPU backend: same trace, same shapes, same convs and
    # dots. Needs a cpu backend in the process, which JAX_PLATFORMS
    # "cpu", "tpu,cpu" or unset all give; "tpu" alone raises here.
    cpu = jax.local_devices(backend="cpu")[0]
    with jax.default_device(cpu):
        analysis = jax.jit(fwd).lower(variables, x).cost_analysis()
    if not isinstance(analysis, dict) or "flops" not in analysis:
        raise RuntimeError(
            f"XLA cost analysis returned no flops: {analysis!r}"
        )
    return float(analysis["flops"])


# Input shapes of the synthetic/token datasets, derivable from config
# alone — counting FLOPs must not re-read a multi-GB data file just for
# .spec (tests cross-check these against the real dataset specs).
_IMAGE_SPECS = {
    "mnist": (28, 28),
    "cifar10": (32, 32, 3),
    "imagenet_synthetic": (224, 224, 3),
}
_TOKEN_DATASETS = ("lm_synthetic", "mlm_synthetic", "token_file")


def _input_spec(cfg):
    import numpy as np

    if cfg.data.dataset in _IMAGE_SPECS:
        return _IMAGE_SPECS[cfg.data.dataset], np.float32
    if cfg.data.dataset in _TOKEN_DATASETS:
        return (cfg.data.seq_len,), np.int32
    # file readers with format-fixed (or config-derived) shapes — never
    # rescan an ImageNet-sized tree or reload a corpus just for .spec
    if cfg.data.dataset == "cifar10_bin":
        return (32, 32, 3), np.float32
    if cfg.data.dataset == "mnist_idx":
        # idx files encode arbitrary dims — probe the real header when a
        # path is configured (a wrong hardcode would silently mis-scale
        # MFU); (28, 28) only as the no-path default
        if cfg.data.path:
            from pathlib import Path

            from pytorch_distributed_nn_tpu.data.readers import (
                _find_one,
                read_idx_header,
            )

            imgs = _find_one(Path(cfg.data.path), "train-images-idx3-ubyte")
            if imgs is not None:
                _, dims = read_idx_header(imgs)
                return tuple(dims[1:]), np.float32
        return (28, 28), np.float32  # the idx standard layout
    if cfg.data.dataset == "image_folder":
        s = cfg.data.image_size
        return (s, s, 3), np.float32
    # array_file and friends: the shape lives in the file/config
    from pytorch_distributed_nn_tpu.data import get_dataset

    spec = get_dataset(
        cfg.data.dataset, seed=0, batch_size=1,
        seq_len=cfg.data.seq_len, vocab_size=cfg.data.vocab_size,
        path=cfg.data.path, token_dtype=cfg.data.token_dtype,
        image_size=cfg.data.image_size,
    ).spec
    return spec.x_shape, spec.x_dtype


def train_flops_per_sample(cfg) -> float:
    """Analytic training FLOPs for ONE sample of ``cfg``'s model on
    ``cfg``'s data shapes: 3 x forward (see module docstring).

    For LMs a "sample" is one full sequence of ``cfg.data.seq_len``
    tokens, matching how the bench counts samples/sec.
    """
    from pytorch_distributed_nn_tpu.models import get_model

    import dataclasses

    # Count the *algorithm*, not the benched implementation: remat off
    # (recompute isn't model work), and dense-XLA attention — a Pallas
    # flash/ring kernel is a custom call the HLO cost model scores as 0
    # FLOPs, which would silently drop the dominant T^2 term at long
    # context.
    model_cfg = dataclasses.replace(
        cfg.model, remat=False,
        extra={**cfg.model.extra, "attn_impl": "xla"},
    )
    model = get_model(model_cfg)
    x_shape, x_dtype = _input_spec(cfg)
    return 3.0 * fwd_flops(model, (1, *x_shape), x_dtype)


def lm_train_flops_per_token(n_params: int, n_layers: int,
                             seq_len: int, d_model: int) -> float:
    """The 6N + 12*L*T*d closed form (PaLM appendix B): per-token
    training FLOPs of a dense transformer LM with N matmul-participating
    params. Used as the independent cross-check of the XLA count."""
    return 6.0 * n_params + 12.0 * n_layers * seq_len * d_model


def mfu(samples_per_sec_chip: float, flops_per_sample: float,
        device=None, dtype=None) -> float | None:
    """Achieved / peak FLOPs for one chip; None off-TPU. ``dtype`` is
    the model's COMPUTE dtype (``model.dtype``): f32 runs against the
    halved f32 peak (see peak_flops_per_chip)."""
    peak = peak_flops_per_chip(device, dtype=dtype)
    if peak is None:
        return None
    return samples_per_sec_chip * flops_per_sample / peak
