"""``loop_next_admissions_share.chat``

Seconds the serve loop's thread spent in the scheduler's admission
pass (phase ``next_admissions``: prefix match, reservation, shedding
of cached blocks), over the window's. From the loop's own
round records inside ``[t0, t1)``, in any run (the chat cell).
"""

from benchmark.lib import loop_records


def read(run: dict):
    return loop_records.phase_share_pct(run, "next_admissions")
