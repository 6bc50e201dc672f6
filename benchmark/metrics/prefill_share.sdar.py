"""``prefill_share.sdar``

Engine wall in prefill (admitted to the end of the prefill: a block
decoder's first token is its first block's commit, rounds later) over
the window.
"""

from benchmark.lib import readers_sdar


def read(run: dict):
    return readers_sdar.prefill_share_pct(run)
