"""``train_throughput``

Samples through optimizer steps in the window, over its seconds and
the chips; the window's last call ends on a ``block_until_ready``.
"""

from benchmark.lib import readers


def read(run: dict):
    return readers.samples_per_s_per_chip(run)
