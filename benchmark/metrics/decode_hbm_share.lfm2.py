"""``decode_hbm_share.lfm2``

Bytes a decode round must move (every parameter outside the experts
once, the table once for the tied head; the experts some row picked at
22.0 MB each, by the program's counters; for each active row the
carried inputs of eleven layers and the cached rows attended and
written in three) over the traced ``serve_step`` time at the chip's
peak bandwidth. See ``readers_lfm2.decode_hbm_share_pct``.
"""

from benchmark.lib import readers_lfm2


def read(run: dict):
    return readers_lfm2.decode_hbm_share_pct(run)
