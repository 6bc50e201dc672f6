"""``decode_hbm_share.jamba``

Bytes a decode round must move (every weight once, the embedding once
for the tied head; for each active row, by the program's counters, the
SSM state of 26 layers read and written and their carried inputs; the
cached rows attended in the two attention layers) over the traced
``serve_step`` time at the chip's peak bandwidth. See
``readers_jamba.decode_hbm_share_pct``.
"""

from benchmark.lib import readers_jamba


def read(run: dict):
    return readers_jamba.decode_hbm_share_pct(run)
