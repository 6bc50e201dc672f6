"""``idle_admit_share``

Idle time of chip 0 under a ``serve/admit`` span (with everything
nested in it: fresh cache, restore, prefill, row insert, retire), in %
of the traced window.
"""

from benchmark.lib import host_spans


def read(run: dict):
    return host_spans.idle_share_pct(run, "admit")
