"""``idle_round_return_share.sdar``

Idle time of chip 0 under ``serve/decode`` or ``serve/round_host``:
the per-round return to the host, in % of the traced window.
"""

from benchmark.lib import host_spans


def read(run: dict):
    return host_spans.idle_share_pct(run, "round_return")
