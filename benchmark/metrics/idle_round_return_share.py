"""``idle_round_return_share``

Idle time of chip 0 under ``serve/decode`` or ``serve/round_host``
(outside an admission pass): the per-token return to the host, in % of
the traced window.
"""

from benchmark.lib import host_spans


def read(run: dict):
    return host_spans.idle_share_pct(run, "round_return")
