"""``loop_fetch_wait_share``

Seconds the serve loop's thread waited for the chip (phase ``fetch``:
the ``np.asarray`` of the round in flight), over the window's: near 0
the host sets the pace. From the loop's own
round records inside ``[t0, t1)``, in any run (the closed-loop served cells).
"""

from benchmark.lib import loop_records


def read(run: dict):
    return loop_records.phase_share_pct(run, "fetch")
