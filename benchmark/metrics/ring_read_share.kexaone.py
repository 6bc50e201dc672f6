"""``ring_read_share.kexaone``

Of all cache rows a decode round scores, the share scored in ring
(sliding-window) layers. Expected 6 x 128 / (6 x 128 + 2 x 4096) =
8.6 %; 75 % would mean the rings are not rings.
"""

from benchmark.lib import readers_kexaone


def read(run: dict):
    return readers_kexaone.ring_read_share_pct(run)
