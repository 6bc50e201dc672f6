"""``idle_unattributed_share``

Idle time of chip 0 under neither class a closed-loop cell reports, in
% of the traced window: what no serve-loop span covers, and with it
the little under ``serve/retire`` and ``serve/parked`` (a closed loop
never parks; the log line gives all five apart). With
``idle_admit_share`` and ``idle_round_return_share`` it sums to
``device_idle_share``.
"""

from benchmark.lib import host_spans


def read(run: dict):
    return host_spans.idle_share_pct(run, "unattributed",
                                     also=("retire", "parked"))
