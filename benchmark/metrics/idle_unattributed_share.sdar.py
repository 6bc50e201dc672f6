"""``idle_unattributed_share.sdar``

Idle time of chip 0 under neither class this cell reports, in % of the
traced window, with the little under ``serve/retire`` and
``serve/parked`` (the closed loop never parks). With the admit and
round-return shares it sums to ``device_idle_share.sdar``.
"""

from benchmark.lib import host_spans


def read(run: dict):
    return host_spans.idle_share_pct(run, "unattributed",
                                     also=("retire", "parked"))
