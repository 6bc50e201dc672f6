"""``held_experts_touched_share.lfm2``

Of the 32 experts a layer holds (all of them), the share some active
row picked in a decode round, from the program's counters: 64 rows x 4
picks miss a given expert with (7/8)^64, so nearly every round reads
nearly every expert. See ``readers_lfm2.held_experts_touched_share_pct``.
"""

from benchmark.lib import readers_lfm2


def read(run: dict):
    return readers_lfm2.held_experts_touched_share_pct(run)
