"""``grouped_experts_hbm_share.lfm2``

The ``grouped_experts`` kernel's own roofline in the decode round: the
bytes of the experts the traced rounds touched, by the program's
counters, over the device time of the kernel's executions inside
``serve_step``, at the chip's peak bandwidth (at eight rows an expert
the experts' bytes bound it). See
``readers_lfm2.grouped_experts_hbm_share_pct``.
"""

from benchmark.lib import readers_lfm2


def read(run: dict):
    return readers_lfm2.grouped_experts_hbm_share_pct(run)
