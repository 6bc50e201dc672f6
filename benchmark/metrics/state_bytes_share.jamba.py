"""``state_bytes_share.jamba``

Of the bytes a decode round must move, the share that is recurrent
state: how much of the round the mechanism is. See
``readers_jamba.state_bytes_share_pct``.
"""

from benchmark.lib import readers_jamba


def read(run: dict):
    return readers_jamba.state_bytes_share_pct(run)
