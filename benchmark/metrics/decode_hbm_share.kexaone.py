"""``decode_hbm_share.kexaone``

Bytes a decode round needs (the dense layer, the matrices outside the
routed experts, the head's slice, the held experts the round's tokens
picked by the program's counters, the cached rows attended in full
layers and in rings) over the traced ``serve_step`` time at the chip's
peak bandwidth. See ``readers_kexaone.decode_hbm_share_pct``.
"""

from benchmark.lib import readers_kexaone


def read(run: dict):
    return readers_kexaone.decode_hbm_share_pct(run)
