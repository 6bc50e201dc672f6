"""``idle_unattributed_share.chat``

Idle time of chip 0 that no serve-loop span covers, in % of the
traced window. With the four other ``idle_*_share.chat`` it sums to
``device_idle_share.chat``.
"""

from benchmark.lib import host_spans


def read(run: dict):
    return host_spans.idle_share_pct(run, "unattributed")
