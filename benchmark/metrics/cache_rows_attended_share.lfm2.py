"""``cache_rows_attended_share.lfm2``

In the three attention layers, cache rows inside the decode rounds'
masks over cache rows the rounds scored (a row's whole padded 4,096),
by the program's counters. See
``readers_lfm2.cache_rows_attended_share_pct``.
"""

from benchmark.lib import readers_lfm2


def read(run: dict):
    return readers_lfm2.cache_rows_attended_share_pct(run)
