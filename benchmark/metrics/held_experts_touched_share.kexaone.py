"""``held_experts_touched_share.kexaone``

Distinct held experts some token picked, a sparse layer a decode round,
in % of the experts held: what share of the expert weights a round
reads.
"""

from benchmark.lib import readers_kexaone


def read(run: dict):
    return readers_kexaone.held_experts_touched_share_pct(run)
