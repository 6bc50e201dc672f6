"""``device_idle_share.chat``

1 - the union of chip operation intervals over the traced window,
mean over the chips used.
"""

from benchmark.lib import readers


def read(run: dict):
    return readers.device_idle_share_pct(run)
