"""``loop_unaccounted_share``

Seconds of the serve loop's thread that no phase holds (``other`` =
wall - sum of the phases), over the window's: keeps the partition
honest. From the loop's own
round records inside ``[t0, t1)``, in any run (the closed-loop served cells).
"""

from benchmark.lib import loop_records


def read(run: dict):
    return loop_records.phase_share_pct(run, "other")
