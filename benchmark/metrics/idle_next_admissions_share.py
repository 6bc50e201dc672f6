"""``idle_next_admissions_share``

Idle time of chip 0 under ``serve/next_admissions`` (the scheduler's
admission pass, before ``serve/admit`` opens) and under none of the
four classes, in % of the traced window. With the remainder the reader
logs it sums to the cell's ``idle_unattributed_share``.
"""

from benchmark.lib import loop_spans


def read(run: dict):
    return loop_spans.next_admissions_share_pct(run)
