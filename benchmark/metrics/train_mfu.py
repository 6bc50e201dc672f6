"""``train_mfu``

The benchmark's own operations per sample (3 x forward, from shapes:
``costs.encoder_train_flops_per_sample``) times samples a second a chip,
over the chip's published bf16 peak.
"""

from benchmark.lib import readers


def read(run: dict):
    return readers.train_mfu_pct(run)
