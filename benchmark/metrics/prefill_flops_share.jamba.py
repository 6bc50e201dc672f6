"""``prefill_flops_share.jamba``

Operations the traced prefills needed, matrix products only (the
mixers' four matrices, the attentions', every MLP, scores inside the
mask, one row of the head), each execution charged its own span's
``tokens``, over the traced ``serve_prefill`` time at the chip's peak.
The scan is elementwise and has no kernel or scope of its own yet: its
time is in the denominator, its work in no numerator. See
``readers_jamba.prefill_flops_share_pct``.
"""

from benchmark.lib import readers_jamba


def read(run: dict):
    return readers_jamba.prefill_flops_share_pct(run)
