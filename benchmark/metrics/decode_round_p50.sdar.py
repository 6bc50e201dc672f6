"""``decode_round_p50.sdar``

Median of ``engine.round_seconds`` inside the window: one forward of
a block of 4 positions for every live row.
"""

from benchmark.lib import readers


def read(run: dict):
    return readers.decode_round_p50_ms(run)
