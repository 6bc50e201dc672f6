"""``data_wait_share.train``

Host clock around the loader's ``next`` (the trainer's goodput meter,
phase ``data``), over the window's wall.
"""

from benchmark.lib import readers


def read(run: dict):
    return readers.data_wait_share_pct(run)
