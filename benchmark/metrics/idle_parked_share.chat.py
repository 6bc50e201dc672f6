"""``idle_parked_share.chat``

Idle time of chip 0 under ``serve/parked``: the loop had nothing to
do, in % of the traced window.
"""

from benchmark.lib import host_spans


def read(run: dict):
    return host_spans.idle_share_pct(run, "parked")
