"""``peak_hbm_share``

``memory_stats()['peak_bytes_in_use']`` plus what loaded programs
reserve, over ``bytes_limit`` on the fullest chip, read when the window
closed (before the reference ran).
"""

from benchmark.lib import readers


def read(run: dict):
    return readers.peak_hbm_share_pct(run)
