"""``decode_round_p50.lfm2``

Median of ``engine.round_seconds`` inside the window.
"""

from benchmark.lib import readers


def read(run: dict):
    return readers.decode_round_p50_ms(run)
