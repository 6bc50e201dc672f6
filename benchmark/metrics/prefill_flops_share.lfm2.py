"""``prefill_flops_share.lfm2``

Operations the traced prefills needed, matrix products only (the
operators' matrices, the dense feed-forwards, the routers, the pairs
routed to held experts, scores inside the mask at heads of 64, one row
of the head), each execution charged its own span's ``tokens``, over
the traced ``serve_prefill`` time at the chip's peak. See
``readers_lfm2.prefill_flops_share_pct``.
"""

from benchmark.lib import readers_lfm2


def read(run: dict):
    return readers_lfm2.prefill_flops_share_pct(run)
