"""``decode_hbm_share.chat``

See ``readers.decode_hbm_share_pct``.
"""

from benchmark.lib import readers


def read(run: dict):
    return readers.decode_hbm_share_pct(run)
