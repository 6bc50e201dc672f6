"""``device_idle_share``

1 - the union of chip operation intervals over the traced window,
mean over the chips used (the closed-loop served cells).
"""

from benchmark.lib import readers


def read(run: dict):
    return readers.device_idle_share_pct(run)
