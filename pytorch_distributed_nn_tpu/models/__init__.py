"""Model zoo — every family named by BASELINE.json configs 1-5 plus the
reference's classic small nets (SURVEY.md §2a Models row), as flax.linen
modules with bf16 compute and optional remat."""

from __future__ import annotations

from typing import Any, Callable

from pytorch_distributed_nn_tpu.config import ModelConfig

_REGISTRY: dict[str, Callable[..., Any]] = {}


def register(name: str):
    def wrap(builder):
        _REGISTRY[name] = builder
        return builder

    return wrap


def get_model(cfg: ModelConfig):
    """Build the flax module for a ModelConfig. Builders accept the config
    and return a linen Module."""
    # import for registration side effects
    from pytorch_distributed_nn_tpu.models import (  # noqa: F401
        ax_k1,
        bert,
        brumby,
        jamba,
        k_exaone,
        lenet,
        lfm2_moe,
        llama,
        longcat_flash,
        mlp,
        moe_lm,
        resnet,
        sdar_moe,
        transformer_lm,
        vit,
    )

    if cfg.name not in _REGISTRY:
        raise KeyError(
            f"unknown model {cfg.name!r}; have {sorted(_REGISTRY)}"
        )
    if cfg.remat_offload and cfg.name != "llama3_8b":
        # only the llama builder consumes the flag; silently dropping
        # it would let a run expected to fit via host offload OOM
        # instead (the same failure mode llama.py guards against for
        # offload-without-remat)
        raise ValueError(
            f"remat_offload is implemented for llama3_8b only; model "
            f"{cfg.name!r} would silently ignore it"
        )
    return _REGISTRY[cfg.name](cfg)


def available_models() -> list[str]:
    from pytorch_distributed_nn_tpu.models import (  # noqa: F401
        ax_k1,
        bert,
        brumby,
        jamba,
        k_exaone,
        lenet,
        lfm2_moe,
        llama,
        longcat_flash,
        mlp,
        moe_lm,
        resnet,
        sdar_moe,
        transformer_lm,
        vit,
    )

    return sorted(_REGISTRY)
