"""``retention_step_hbm_share.brumby``

The ``retention_step`` kernel's own roofline in the decode round: the
bytes the traced executions had to move (each active row's
matrix-valued state in and out, by ``costs_brumby`` and the program's
counters of active rows) over their device time inside ``serve_step``,
at the chip's peak bandwidth. The op and its counters are this model's
alone.
"""

from benchmark.lib import readers_brumby


def read(run: dict):
    return readers_brumby.retention_step_hbm_share_pct(run)
