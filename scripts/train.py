#!/usr/bin/env python
"""Training entrypoint — the reference's ``train.py`` (SURVEY.md §1
Launch/Entrypoints rows), TPU-native.

Usage:
    python scripts/train.py --preset mlp_mnist [--steps 100]
        [--optim.lr 0.05] [--parallel.strategy dp_explicit] ...

Multi-host: launch one process per host with RANK/WORLD_SIZE/MASTER_ADDR
(torch-style) or COORDINATOR_ADDRESS/NUM_PROCESSES/PROCESS_ID env vars;
see pytorch_distributed_nn_tpu.runtime.bootstrap.
"""

from __future__ import annotations

import logging
import sys

sys.path.insert(0, ".")  # run from repo root without install

from pytorch_distributed_nn_tpu.runtime.device import (
    configure_compile_cache,
)

configure_compile_cache()

from pytorch_distributed_nn_tpu.config import get_config, parse_overrides
from pytorch_distributed_nn_tpu.runtime import bootstrap
from pytorch_distributed_nn_tpu.train.trainer import Trainer


def main(argv: list[str]) -> int:
    logging.basicConfig(
        level=logging.INFO,
        format="%(asctime)s %(name)s %(levelname)s %(message)s",
    )
    overrides = parse_overrides(argv)
    preset = overrides.pop("preset", "mlp_mnist")
    info = bootstrap.initialize()
    cfg = get_config(preset, **overrides)
    # context manager: closes the metrics JSONL handle and drains async
    # checkpoint writes even when train() raises
    with Trainer(cfg) as trainer:
        history = trainer.train()
    if info.is_coordinator and history:
        final = history[-1]
        print(f"final: step={final.step} loss={final.loss:.4f}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
