"""``idle_retire_share.chat``

Idle time of chip 0 under a ``serve/retire`` span outside an
admission pass, in % of the traced window.
"""

from benchmark.lib import host_spans


def read(run: dict):
    return host_spans.idle_share_pct(run, "retire")
