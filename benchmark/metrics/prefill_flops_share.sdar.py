"""``prefill_flops_share.sdar``

Operations the traced prefills needed (the matrices outside the
experts for the tokens really prefilled, scores inside the mask by
blocks, the pairs routed by the program's counters; no head: the
prefill reads no logit) over the traced ``serve_prefill`` time at the
chip's peak, each execution charged its own ``serve/prefill_into``
span's ``tokens``. See ``readers_sdar.prefill_flops_share_pct``.
"""

from benchmark.lib import readers_sdar


def read(run: dict):
    return readers_sdar.prefill_flops_share_pct(run)
