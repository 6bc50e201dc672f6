"""``held_experts_touched_share.sdar``

Distinct held experts some position picked, a layer a round, in % of
the 128 held: what share of the expert weights a round reads.
"""

from benchmark.lib import readers_sdar


def read(run: dict):
    return readers_sdar.held_experts_touched_share_pct(run)
