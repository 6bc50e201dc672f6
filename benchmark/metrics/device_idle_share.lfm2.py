"""``device_idle_share.lfm2``

1 - the union of chip operation intervals over the traced window.
"""

from benchmark.lib import readers


def read(run: dict):
    return readers.device_idle_share_pct(run)
