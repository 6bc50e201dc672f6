"""``loop_admit_share``

Seconds the serve loop's thread spent admitting (phase ``admit``, with
every prefill, restore, row write and first-token wait nested in it),
over the window's. From the loop's own
round records inside ``[t0, t1)``, in any run (the closed-loop served cells).
"""

from benchmark.lib import loop_records


def read(run: dict):
    return loop_records.phase_share_pct(run, "admit")
