#!/usr/bin/env python
"""Checkpoint conversion: torch/HF weights ↔ framework checkpoints.

Import (torch → here): load a ``torch.save``'d state_dict (torch pickle
zip via ``torch.load(weights_only=True)``) or an HF ``.safetensors``
file (via ``safetensors.torch``), map it onto the preset's model via
utils/torch_interop, and write a framework checkpoint that
``scripts/train.py --resume`` / ``scripts/generate.py
--checkpoint-dir`` consume directly:

    python scripts/convert.py --arch llama3 --preset llama3_8b_zero \
        --torch-checkpoint llama.pt --out runs/llama_ckpt \
        --model.extra '{"num_layers":2,"d_model":64,...}'

Export (here → torch): read the latest framework checkpoint and write an
HF-layout state_dict torch can load:

    python scripts/convert.py --arch llama3 --preset llama3_8b_zero \
        --export runs/llama_ckpt --torch-checkpoint out.pt ...

The model dims must match the weights being converted — set them via
``--model.extra`` exactly as for training (a mismatch fails with the
offending shapes, nothing half-loads).
"""

from __future__ import annotations

import argparse
import sys

sys.path.insert(0, ".")  # run from repo root without install

from pytorch_distributed_nn_tpu.runtime.device import (
    configure_compile_cache,
)

configure_compile_cache()


def _load_state_dict(path: str):
    if str(path).endswith(".safetensors"):
        from safetensors.torch import load_file

        return load_file(path)
    import torch

    return torch.load(path, map_location="cpu", weights_only=True)


def _converted_params(arch: str, state_dict, model_cfg):
    """Returns (params, model_state_or_None) — model_state carries the
    non-param variable collections (ResNet BatchNorm running stats)."""
    from pytorch_distributed_nn_tpu.utils import torch_interop as ti

    e = model_cfg.extra
    if arch == "llama3":
        return ti.llama_params_from_torch(
            state_dict,
            num_layers=e.get("num_layers", 32),
            num_heads=e.get("num_heads", 32),
            num_kv_heads=e.get("num_kv_heads", 8),
        ), None
    if arch == "bert":
        return ti.bert_params_from_torch(
            state_dict,
            num_layers=e.get("num_layers", 12),
            num_heads=e.get("num_heads", 12),
        ), None
    if arch == "gpt2":
        return ti.gpt2_params_from_torch(
            state_dict,
            num_layers=e.get("num_layers", 12),
            num_heads=e.get("num_heads", 12),
        ), None
    if arch == "resnet50":
        return ti.resnet50_params_from_torch(
            state_dict,
            stage_sizes=tuple(e.get("stage_sizes", (3, 4, 6, 3))),
            stem=e.get("stem", "conv7"),
        )
    if arch == "vit":
        return ti.vit_params_from_torch(
            state_dict,
            num_layers=e.get("num_layers", 6),
            num_heads=e.get("num_heads", 3),
        ), None
    if arch == "lenet":
        return ti.lenet_params_from_torch(state_dict), None
    if arch == "mlp":
        return ti.mlp_params_from_torch(state_dict), None
    raise ValueError(
        f"unknown --arch {arch!r} (llama3 | bert | gpt2 | resnet50 | "
        "vit | lenet | mlp)"
    )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    ap.add_argument("--arch", required=True,
                    choices=("llama3", "bert", "gpt2", "resnet50",
                             "vit", "lenet", "mlp"))
    ap.add_argument("--preset", required=True)
    ap.add_argument("--torch-checkpoint", required=True,
                    help="torch state_dict file (read on import, "
                         "written on export)")
    ap.add_argument("--out", default="",
                    help="framework checkpoint dir to write (import mode)")
    ap.add_argument("--export", default="",
                    help="framework checkpoint dir to read (export mode)")
    args, rest = ap.parse_known_args(argv)
    if bool(args.out) == bool(args.export):
        ap.error("exactly one of --out (import) / --export is required")

    import jax
    import numpy as np
    import torch

    from pytorch_distributed_nn_tpu.config import get_config, parse_overrides
    from pytorch_distributed_nn_tpu.train.checkpoint import CheckpointManager
    from pytorch_distributed_nn_tpu.train.trainer import Trainer

    cfg = get_config(args.preset, **parse_overrides(rest))
    cfg.steps = 0
    cfg.checkpoint_dir = ""  # Trainer must not auto-resume anything
    # Norm epsilons need no special handling: the model builders default
    # to the HF-conventional values (bert 1e-12, gpt2 1e-5, llama3
    # 1e-5), so every consumer of the converted checkpoint — convert,
    # eval, generate, resume — reconstructs the same model. Checkpoints
    # trained with nonstandard eps still need --model.extra everywhere.
    trainer = Trainer(cfg)

    if args.out:
        state_dict = _load_state_dict(args.torch_checkpoint)
        converted, model_state = _converted_params(args.arch, state_dict,
                                                   cfg.model)
        if cfg.parallel.strategy == "pipeline":
            # pipeline checkpoints hold STACKED stage params — restack
            # the flat converted tree so train.py --resume consumes it
            from pytorch_distributed_nn_tpu.parallel.pipeline import (
                partition_for,
                stack_stage_params,
            )

            interleaved = (cfg.parallel.pipeline_schedule
                           == "interleaved")
            converted = stack_stage_params(
                converted, partition_for(trainer.model),
                max(cfg.mesh.pipe, 1),
                n_chunks=(max(cfg.parallel.pipe_chunks, 1)
                          if interleaved else 1),
                chunked=interleaved,
            )
        from pytorch_distributed_nn_tpu.runtime.mesh import place_like

        try:
            placed = place_like(converted, trainer.state.params)
            state = trainer.state.replace(params=placed)
            if model_state is not None:  # e.g. BatchNorm running stats
                state = state.replace(model_state=place_like(
                    model_state, trainer.state.model_state))
        except ValueError as e:
            raise SystemExit(
                f"converted weights do not fit the configured model "
                f"(set --model.extra to the checkpoint's dims): {e}"
            ) from e
        mgr = CheckpointManager(args.out, async_save=False)
        mgr.save(state, data_step=0,
                 extra_meta={"converted_from": args.torch_checkpoint},
                 force=True)
        mgr.close()
        print(f"wrote framework checkpoint: {args.out} "
              f"(step 0, arch {args.arch})")
        return 0

    mgr = CheckpointManager(args.export, async_save=False)
    state, meta = mgr.restore(trainer.state)
    mgr.close()
    exporters = ("llama3", "bert", "gpt2", "vit", "resnet50")
    if args.arch not in exporters:
        raise SystemExit(
            f"export supports --arch {' | '.join(exporters)}"
        )
    from pytorch_distributed_nn_tpu.utils import torch_interop as ti

    params = state.params
    if cfg.parallel.strategy == "pipeline":
        from pytorch_distributed_nn_tpu.parallel.pipeline import (
            partition_for,
            unstack_stage_params,
        )

        interleaved = cfg.parallel.pipeline_schedule == "interleaved"
        params = unstack_stage_params(
            jax.device_get(params), partition_for(trainer.model),
            n_chunks=(max(cfg.parallel.pipe_chunks, 1)
                      if interleaved else 1),
            chunked=interleaved,
        )
    host_params = jax.tree.map(
        lambda x: np.asarray(jax.device_get(x), np.float32), params
    )
    if args.arch == "resnet50":
        host_stats = jax.tree.map(
            lambda x: np.asarray(jax.device_get(x), np.float32),
            dict(state.model_state),
        )
        sd = ti.resnet50_params_to_torch(
            host_params, host_stats,
            stage_sizes=tuple(cfg.model.extra.get("stage_sizes",
                                                  (3, 4, 6, 3))),
        )
    else:
        sd = {
            "llama3": ti.llama_params_to_torch,
            "bert": ti.bert_params_to_torch,
            "gpt2": ti.gpt2_params_to_torch,
            "vit": ti.vit_params_to_torch,
        }[args.arch](host_params)
    torch.save(sd, args.torch_checkpoint)
    print(f"wrote torch state_dict: {args.torch_checkpoint} "
          f"(from step {meta['step']})")
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
