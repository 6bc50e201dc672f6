"""``idle_unattributed_share.docs``

Idle time of chip 0 under neither class this cell reports, in % of
the traced window: what no serve-loop span covers, and with it the
little under ``serve/retire`` and ``serve/parked`` (the closed loop
never parks; the log line gives all five apart). With
``idle_admit_share.docs`` and ``idle_round_return_share.docs`` it sums
to ``device_idle_share.docs``.
"""

from benchmark.lib import host_spans


def read(run: dict):
    return host_spans.idle_share_pct(run, "unattributed",
                                     also=("retire", "parked"))
