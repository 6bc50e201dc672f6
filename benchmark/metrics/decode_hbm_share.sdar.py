"""``decode_hbm_share.sdar``

Bytes a round needs (the matrices outside the experts, the head, the
held experts its positions picked by the program's counters, the cached
rows attended once a row and the block's rows written) over the traced
``serve_step`` time at the chip's peak bandwidth. See
``readers_sdar.decode_hbm_share_pct``.
"""

from benchmark.lib import readers_sdar


def read(run: dict):
    return readers_sdar.decode_hbm_share_pct(run)
