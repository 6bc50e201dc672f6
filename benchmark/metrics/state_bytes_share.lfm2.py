"""``state_bytes_share.lfm2``

Of the bytes a decode round must move, the share that is the eleven
convolution layers' carried inputs: how little of the round the state
is here (a few KB a row a layer, where Jamba's SSM state is a sixth of
its round). See ``readers_lfm2.state_bytes_share_pct``.
"""

from benchmark.lib import readers_lfm2


def read(run: dict):
    return readers_lfm2.state_bytes_share_pct(run)
