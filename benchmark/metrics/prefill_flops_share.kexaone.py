"""``prefill_flops_share.kexaone``

Operations the prompts need (projections, scores inside the mask: the
band in sliding layers and the lower triangle in full ones, the dense
FFN, the shared expert, token-expert pairs on held experts by the
program's counters, one head row) over the traced ``serve_prefill`` time
at the chip's peak. See ``readers_kexaone.prefill_flops_share_pct``.
"""

from benchmark.lib import readers_kexaone


def read(run: dict):
    return readers_kexaone.prefill_flops_share_pct(run)
