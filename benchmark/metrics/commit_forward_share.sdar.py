"""``commit_forward_share.sdar``

Of the forwards of a block, the share that found no position masked
and committed the block (``block_commits_total`` over
``block_forwards_total``): what fusing a commit with the next block's
first step would take off the rounds.
"""

from benchmark.lib import readers_sdar


def read(run: dict):
    return readers_sdar.commit_forward_share_pct(run)
