#!/usr/bin/env python
"""Evaluation entrypoint: checkpoint -> held-out loss/accuracy.

Usage:
    python scripts/eval.py --preset mlp_mnist --checkpoint-dir runs/ckpt \
        [--batches 16] [--a.b config overrides ...]

Restores the latest checkpoint into the preset's model and runs the
held-out evaluation stream (same-task batches from a step range training
cannot reach — train/trainer.py). Prints one JSON line.

NOTE: for token_file/array_file datasets the eval stream is IN-SAMPLE
(drawn from the training rows/tokens) unless the run set
``--data.holdout_frac`` > 0 to reserve a true held-out split — use the
same value here that training used, or the "held-out" rows were trained
on. Synthetic streams are infinite and always genuinely held out.
"""

from __future__ import annotations

import argparse
import json
import sys

sys.path.insert(0, ".")  # run from repo root without install

from pytorch_distributed_nn_tpu.runtime.device import (
    configure_compile_cache,
)

configure_compile_cache()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--preset", required=True)
    ap.add_argument("--checkpoint-dir", required=True)
    ap.add_argument("--batches", type=int, default=16)
    args, rest = ap.parse_known_args(argv)

    from pytorch_distributed_nn_tpu.config import get_config, parse_overrides
    from pytorch_distributed_nn_tpu.runtime import bootstrap
    from pytorch_distributed_nn_tpu.runtime.mesh import MeshSpec, place_like
    from pytorch_distributed_nn_tpu.train.trainer import Trainer

    bootstrap.initialize()
    cfg = get_config(args.preset, **parse_overrides(rest))
    cfg.steps = 0  # restore only; no training

    pipeline_params = None
    if cfg.parallel.strategy == "pipeline":
        # Pipeline checkpoints hold STACKED stage params ({'stages',
        # 'rest'}); evaluate() needs the flat tree. Restore against a
        # stacked template built from a fresh init (no pipeline mesh
        # needed — restore places to the template's single-device
        # layout), unstack, and evaluate under plain dp.
        from pytorch_distributed_nn_tpu.parallel.pipeline import (
            restore_unstacked_params,
        )

        pipeline_params = restore_unstacked_params(
            cfg, args.checkpoint_dir
        )
        if pipeline_params is None:
            print(f"no checkpoint found in {args.checkpoint_dir}",
                  file=sys.stderr)
            return 1
        cfg.parallel.strategy = "dp"
        cfg.mesh = MeshSpec()  # drop the pipe axis for eval
        cfg.checkpoint_dir = ""
    else:
        cfg.checkpoint_dir = args.checkpoint_dir
        cfg.resume = True

    trainer = Trainer(cfg)
    if pipeline_params is not None:
        trainer.state = trainer.state.replace(
            params=place_like(pipeline_params, trainer.state.params)
        )
    elif trainer.ckpt is None or trainer.ckpt.latest_step() is None:
        print(f"no checkpoint found in {args.checkpoint_dir}",
              file=sys.stderr)
        return 1
    rec = trainer.evaluate(num_batches=args.batches)
    trainer.close()
    print(json.dumps(dict(step=rec.step, eval_loss=round(rec.loss, 6),
                          eval_accuracy=round(rec.accuracy, 6),
                          batches=args.batches)))
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
